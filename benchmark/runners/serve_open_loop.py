"""Runner of the traffic kind ``serve_open_loop``: the LLM server
behind the HTTP proxy, offered streamed POST /v1/completions on a
fixed schedule.

This process starts the runtime, the proxy and (through the runtime)
the replica that owns the chip; it never initializes a JAX backend.
The load generator is a child process (benchmark/loadgen.py).

``control_reports`` (``run.py --control``) runs the reference check
alone, with the program made wrong in a named way, and no window.

Phases: build (the cell's EngineConfig, LLMConfig and schedules; no
runtime yet) -> reference check on the chip (a task that builds the
same engine, see reference_check.py) -> deploy -> warm every prefill
bucket the traffic uses and the decode program -> window -> (steady
cells) drain -> stats and series -> shut down -> the result. What is
particular to the model family comes from the configuration file's
program module (benchmark/programs/).
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import time
import types
import urllib.request
from typing import Any, Dict, List, Tuple

from benchmark import harness, reference_check, traffic

DEPLOY_TIMEOUT_S = 900.0
REQUEST_TIMEOUT_S = 600.0
MAX_MEAN_LAG_S = 0.010
_SERIES = re.compile(r"^(ray_tpu_\w+?)(\{[^}]*\})? (\S+)$")


def _log(*a) -> None:
    print("[serve_open_loop]", *a, flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout: float = REQUEST_TIMEOUT_S) -> Dict[str, Any]:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def program_series() -> Dict[str, float]:
    """Every ray_tpu_* series as the driver's registry holds them now,
    ``name{labels}`` -> value: the engine's, and what a model brings
    outside it (a state cache's, a dispatch's), for a reader to find."""
    from ray_tpu.util import metrics
    out = {}
    for line in metrics.prometheus_text().splitlines():
        m = _SERIES.match(line)
        if m:
            out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return out


def _scrape(base: str) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """/v1/stats makes the replica flush its buffered step metrics;
    they reach the driver's registry a moment later."""
    stats = _get(base + "/stats")
    time.sleep(0.5)
    return stats, program_series()


def _body(request: Dict[str, Any], mix: Dict[str, Any], no_eos) -> Dict[str, Any]:
    return {"prompt": request["prompt"], "max_tokens": request["max_tokens"],
            "temperature": mix.get("temperature", 0.0), "stream": True,
            "logit_bias": no_eos}


def _run_loadgen(spec: Dict[str, Any]) -> Dict[str, Any]:
    proc = subprocess.Popen(
        [sys.executable, os.path.join(harness.HERE, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    try:
        out, _ = proc.communicate(json.dumps(spec).encode(),
                                  timeout=spec["window_s"] + 300)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise harness.BenchError(f"load generator exited {proc.returncode}")
    return json.loads(out)


def _buckets(lengths: List[int], max_seq: int) -> List[int]:
    """Prompt lengths (tokens, BOS included) that land in each prefill
    bucket the traffic uses: the engine pads to powers of two."""
    seen = {}
    for n in lengths:
        bucket = 1
        while bucket < n:
            bucket *= 2
        seen.setdefault(min(bucket, max_seq), n)
    return sorted(seen.values())


def _window(port: int, requests, mix, no_eos,
            seconds: float, drain: bool) -> Dict[str, Any]:
    return _run_loadgen({
        "host": "127.0.0.1", "port": port, "path": "/v1/completions",
        "window_s": seconds, "drain": drain,
        "drain_timeout_s": mix.get("drain_timeout_s", 60.0),
        "requests": [{"due": r["due"], "body": _body(r, mix, no_eos)}
                     for r in requests]})


def build(cell: Dict[str, Any], args) -> types.SimpleNamespace:
    """What a run of the cell is made of, before any runtime: the
    EngineConfig and LLMConfig the replica gets, the reference check's
    sizes, and the schedule(s) the seed deals."""
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.serve.llm import LLMConfig

    config, mix = cell["config_file"], cell["traffic_file"]
    sizes = config["serving"]
    seed = args.seed % harness.SEED_MODULUS
    program = harness.program_for(config["program"])
    engine = EngineConfig(
        model=program.serving_model(config, sizes["max_seq"], args.rehearse),
        max_batch=sizes["max_batch"], max_seq=sizes["max_seq"], seed=seed)
    llm = LLMConfig(model_id=cell["config"], engine=engine,
                    use_tpu=not args.rehearse,
                    tpu_chips_per_replica=cell["chips"],
                    max_ongoing_requests=sizes["max_ongoing_requests"])
    routed = program.routed(config)
    check_lens, check_tokens = reference_check.check_sizes(config, routed)
    check_limits = reference_check.check_limits(config, routed)
    scale = ({"prompt": 0.1, "output": 0.1} if args.rehearse else None)
    rates = args.sweep or [mix["rate_rps"]]
    return types.SimpleNamespace(
        program=program, engine=engine, llm=llm, routed=routed,
        check_lens=[min(n, sizes["max_seq"] // 2) for n in check_lens],
        check_tokens=check_tokens, check_limits=check_limits, rates=rates,
        schedules=[traffic.open_loop_schedule(
            {**mix, "rate_rps": rate}, args.seed, args.seconds, scale)
            for rate in rates],
        drain=bool(mix.get("drain", True)) or bool(args.sweep),
        no_eos={str(ByteTokenizer.eos_id): -100})


def _reference_check(cell: Dict[str, Any], built, rehearse: bool,
                     control=None) -> Dict[str, Any]:
    """The program against the plain reference, on the chip.
    ``control``: one of the program module's ``controls``, for
    control_reports; the report then carries every reading."""
    import ray_tpu

    check = ray_tpu.get(
        ray_tpu.remote(num_tpus=cell["chips"])(
            reference_check.check_serving).remote(
            built.engine, cell["config_file"]["reference"],
            built.check_lens, built.check_tokens, built.engine.seed,
            built.routed, built.check_limits,
            control=control and control[1], readings=control is not None),
        timeout=DEPLOY_TIMEOUT_S)
    _log("reference check" + (f" under control {control[0]}:" if control
                              else ":"), json.dumps(check))
    where = check["device"]
    if not rehearse and where["platform"] != "tpu":
        raise harness.BenchError(f"the worker computes on {where}")
    if len(where["device_ids"]) != cell["chips"]:
        raise harness.BenchError(
            f"cell asks for {cell['chips']} chips, the worker sees "
            f"{where['device_ids']}")
    return check


def control_reports(cell: Dict[str, Any], args) -> List[Dict[str, Any]]:
    """``run.py --control a,b``: the cell's reference check alone, at
    the cell's sizes and on its seed, once under each named control of
    the program module (``none``: the sound program). No window, no
    replica, no metric: what comes back is one report a control."""
    import ray_tpu

    built = build(cell, args)
    known = {"none": None, **getattr(built.program, "controls",
                                     lambda config: {})(cell["config_file"])}
    names = [n.strip() for n in args.control.split(",")]
    unknown = [n for n in names if n not in known]
    if unknown:
        raise harness.BenchError(
            f"program {cell['config_file']['program']!r} has no control "
            f"{unknown} (has {sorted(known)})")
    ray_tpu.init(**({"num_tpus": cell["chips"]} if args.rehearse else {}))
    try:
        return [{"control": name, "workload": cell["name"],
                 "seed": built.engine.seed,
                 "report": _reference_check(cell, built, args.rehearse,
                                            (name, known[name]))}
                for name in names]
    finally:
        ray_tpu.shutdown()


def _warm_up(port: int, built, mix: Dict[str, Any]) -> None:
    """Each prefill bucket the schedules use, then a full decode batch."""
    warm_lens = _buckets([len(r["prompt"]) + 1 for s in built.schedules
                          for r in s], built.engine.max_seq)
    warm = [{"due": 0.0, "prompt": "w" * (n - 1), "max_tokens": 4}
            for n in warm_lens]
    for w in warm:     # one at a time: each compiles its bucket
        got = _window(port, [w], mix, built.no_eos, 1.0, True)
        if got["records"][0]["error"]:
            raise harness.BenchError(
                f"warm-up request failed: {got['records'][0]}")
    got = _window(port, [dict(w, due=0.01 * i) for i, w in
                         enumerate(warm * 3)], mix, built.no_eos, 1.0, True)
    bad = [r for r in got["records"] if r["error"]]
    if bad:
        raise harness.BenchError(f"warm-up failed: {bad[:2]}")


def run(cell: Dict[str, Any], args, t_start: float) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.config import HTTPOptions
    from ray_tpu.serve.llm import build_openai_app

    mix = cell["traffic_file"]
    built = build(cell, args)
    ray_tpu.init(**({"num_tpus": cell["chips"]} if args.rehearse else {}))
    seen: Dict[str, Any] = {}       # what _result judges and reduces
    try:
        check = _reference_check(cell, built, args.rehearse)
        # -- deploy ---------------------------------------------------
        port = _free_port()
        http = HTTPOptions(port=port)
        base = f"http://{http.host}:{port}/v1"
        serve.start(http_options=http, proxy=True)
        if args.trace:
            from benchmark.runners import serve_trace
            app = serve_trace.traced_app(built.llm)
        else:
            app = build_openai_app(config=built.llm)
        serve.run(app, route_prefix="/v1", timeout_s=DEPLOY_TIMEOUT_S)
        seen["ready_s"] = time.monotonic() - t_start
        _warm_up(port, built, mix)
        seen["stats_before"], seen["series_before"] = _scrape(base)
        t_scrape0 = time.monotonic()
        # -- the window(s) ---------------------------------------------
        results = seen["results"] = []
        pending = None
        for rate, schedule in zip(built.rates, built.schedules):
            if args.trace:
                pending = serve_trace.PendingTrace(
                    base, mix.get("trace_after_s", 5.0),
                    mix.get("trace_seconds", 3.0))
            got = _window(port, schedule, mix, built.no_eos,
                          args.seconds, built.drain)
            got["rate_rps"] = rate
            results.append(got)
            if args.sweep:
                _log("sweep", json.dumps(_sweep_row(got, args.seconds)))
        seen["setup_s"] = results[0]["t0_monotonic"] - t_start
        seen["series_window_s"] = time.monotonic() - t_scrape0
        seen["stats_after"], seen["series_after"] = _scrape(base)
        seen["trace"] = pending.collect() if pending else None
    finally:
        logs = _log_tails() if args.dump else ""
        serve.shutdown()
        ray_tpu.shutdown()

    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        with open(os.path.join(args.dump, f"{cell['name']}.serve.json"),
                  "w") as f:
            json.dump({**seen, "check": check, "logs": logs}, f)
    return _result(cell, args, built, check, seen)


def _result(cell: Dict[str, Any], args, built, check: Dict[str, Any],
            seen: Dict[str, Any]) -> Dict[str, Any]:
    """After the runtime has shut down: the first window's records
    into what was measured, what was observed, and ``correct``."""
    got, traced = seen["results"][0], seen["trace"]
    stats0, stats1 = seen["stats_before"], seen["stats_after"]
    requests, drain, where = built.schedules[0], built.drain, check["device"]
    sent = [(r, q["max_tokens"]) for r, q in zip(got["records"], requests)
            if r["sent"] is not None]
    unsent = len(got["records"]) - len(sent)
    records = [r for r, _ in sent]
    # a saturated cell is not drained: what is still in flight at the
    # close is neither attempted nor failed
    done = [(r, n) for r, n in sent
            if drain or r["finished"] or r["error"]]
    records_done = [r for r, _ in done]
    failed = [r for r, _ in done if r["error"] or not r["finished"]]
    wrong = [r for r, n in done if r["finished"] and not r["error"]
             and len(r["token_times"]) != n]
    if not records_done:
        raise harness.BenchError("no request finished inside the window")
    lat = harness.open_loop_latencies(records_done)
    _log(f"generator lag: worst {lat['lag_worst_s'] * 1e3:.2f} ms, mean "
         f"{lat['lag_mean_s'] * 1e3:.3f} ms; unsent {unsent}; in flight at "
         f"the close {got['in_flight_at_end']}; window ended "
         f"{got['ended_s']:.2f} s"
         + ("; THE GENERATOR WAS STARVED: discard this run"
            if lat["lag_mean_s"] > MAX_MEAN_LAG_S else ""))
    measured = {
        "setup_s": seen["setup_s"],
        "ttft_p50_ms": harness.percentile(lat["ttft_s"], 0.50) * 1e3,
        "itl_p90_ms": harness.percentile(lat["gaps_s"], 0.90) * 1e3,
        "serve_tok_s": harness.tokens_inside(records, args.seconds)
        / args.seconds,
    }
    _log("also:", json.dumps({
        "ttft_p50_ms": measured["ttft_p50_ms"],
        "ttft_p90_ms": harness.percentile(lat["ttft_s"], 0.9) * 1e3,
        "ttft_max_ms": max(lat["ttft_s"]) * 1e3,
        "itl_p50_ms": harness.percentile(lat["gaps_s"], 0.5) * 1e3,
        "itl_p90_ms": measured["itl_p90_ms"],
        "itl_p95_ms": harness.percentile(lat["gaps_s"], 0.95) * 1e3,
        "serve_tok_s": measured["serve_tok_s"],
        "requests": len(records), "gaps": len(lat["gaps_s"]),
        "ready_s": seen["ready_s"]}))
    dev0, dev1 = stats0["device"], stats1["device"]
    compiled = dev1["compile_seconds"] - dev0["compile_seconds"]
    kernels_ok, kernel_note = _kernels_ok(stats1, built.program,
                                          args.rehearse)
    # How late the generator ran is printed above and decides nothing:
    # latency runs from the due time, so a late send is charged to the
    # system, and a send that comes late offers less load, so a starved
    # generator can only make a run read worse, never better. On the
    # chip's machine it ran 1.1 ms late on average and 3 ms at worst
    # below the knee, with lone spikes of 0.1 s when saturated; a run
    # with a mean over MAX_MEAN_LAG_S says so and is one to discard.
    # The conjuncts of ``correct``, each a number beside what it has to
    # be, and before them the check's statistics beside their limits
    # (``check`` is their verdict): the result's last line carries them,
    # so that the ledger tells an output's fault (check, wrong_counts)
    # from a run's (the rest). How late the generator ran and the worst
    # first token are shown, not judged.
    conjuncts = {
        "check": [int(bool(check["ok"])), 1],
        "wrong_counts": [len(wrong), 0],
        "kernels_ok": [int(kernels_ok), 1],
        "compiled_in_window_s": [compiled, 0.0], "unsent": [unsent, 0],
        "replica_replaced": [int(dev1["pid"] != dev0["pid"]), 0],
        "fallbacks": [len(stats1["flash_fallbacks"]), 0],
        "platform_ok": [int(dev1["platform"] == where["platform"]), 1]}
    fell = [name for name, (value, wanted) in conjuncts.items()
            if value != wanted]
    correct = not fell
    compared = {**reference_check.compared(check), **conjuncts,
                "lag_worst_s": [lat["lag_worst_s"], None],
                "ttft_max_ms": [max(lat["ttft_s"]) * 1e3, None]}
    if not correct:
        _log("NOT CORRECT:", json.dumps({
            "fell": fell, "compared": compared, "kernels": kernel_note,
            "replica_pids": [dev0["pid"], dev1["pid"]]}))
    observed = {
        "client": lat, "series_before": seen["series_before"],
        "series_after": seen["series_after"],
        "series_window_s": seen["series_window_s"], "trace": traced,
        "cell": cell}
    device, breakdown = harness.device_and_breakdown(dev1, traced)
    return {"correct": correct and not args.rehearse,
            "attempted": len(records_done), "failed": len(failed),
            "measured": measured, "observed": observed, "device": device,
            "breakdown": breakdown, "compared": compared}


def _log_tails() -> str:
    """The last 60 lines of the four newest worker logs."""
    from ray_tpu.core import runtime as runtime_mod
    rt = runtime_mod.get_runtime_or_none()
    if rt is None:
        return ""
    logs = []
    for node in rt.nodes.values():
        log_dir = os.path.join(node.session_dir, "logs")
        logs += [os.path.join(log_dir, f) for f in os.listdir(log_dir)]
    out = []
    for path in sorted(logs, key=os.path.getmtime)[-4:]:
        with open(path, errors="replace") as f:
            out.append(f"--- {path} ---\n" + "".join(f.readlines()[-60:]))
    return "\n".join(out)


def _kernels_ok(stats: Dict[str, Any], program, rehearse: bool):
    """Every program the engine lowered holds the Pallas kernels its
    family's module asks of a program of that name."""
    if rehearse:
        return True, "rehearsal: the CPU holds no kernels"
    missing = []
    for name, found in stats["programs"].items():
        missing += [f"{name}:{w}" for w in harness.missing_kernels(
            found, program.kernels(name))]
    if not stats["programs"]:
        missing.append("no program reported")
    return not missing, missing


def _sweep_row(got: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    recs = [r for r in got["records"] if r["sent"] is not None]
    lat = harness.open_loop_latencies(recs)
    at_close = sum(1 for r in recs
                   if not r["token_times"] or r["token_times"][-1] > seconds)
    started_late = sum(1 for r in recs if not r["token_times"]
                       or r["token_times"][0] > seconds)
    return {"rate_rps": got["rate_rps"], "requests": len(recs),
            "unfinished_at_close": at_close,
            "not_started_at_close": started_late,
            "drained_s": got["ended_s"],
            "ttft_p50_ms": harness.percentile(lat["ttft_s"], 0.5) * 1e3,
            "ttft_p90_ms": harness.percentile(lat["ttft_s"], 0.9) * 1e3,
            "itl_p50_ms": harness.percentile(lat["gaps_s"], 0.5) * 1e3,
            "itl_p95_ms": harness.percentile(lat["gaps_s"], 0.95) * 1e3,
            "tok_s_in_window": harness.tokens_inside(recs, seconds)
            / seconds,
            "errors": sum(1 for r in recs if r["error"])}
