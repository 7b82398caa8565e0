"""Does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at Llama-2-7B widths (dim 4096, 32 heads of 128, hidden 11008, vocab
32000; cut in depth only, weights from a seed, byte tokenizer):

  0. kernel self-check: every Pallas kernel against its jax.numpy
     reference at the shapes the later phases use;
  1. train, one chip: JaxTrainer -> worker -> jitted adamw step on
     llama_loss, tokens through ray_tpu.data;
  2. serve, one chip: serve.run(build_openai_app(...)) behind the HTTP
     proxy, concurrent POST /v1/completions, one streamed;
  3. on a four-chip host: train again with one worker driving an
     fsdp=4 mesh, serve again with four one-chip replicas.

This process never initializes a JAX backend: every phase runs in
worker processes the runtime spawns, one after another, and a chip is
handed on when its worker exits. It refuses to run without a TPU. The
last line of stdout is one JSON object, {"ok": true, "device":
{"platform", "kind", "count"}}, printed only when every phase passed;
the exit code is 0 only then. This check measures no rate and claims
no gain.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Sequence

# Whole-run budget: the caller allows 1200 s, compilation included.
BUDGET_S = 1150
# One wait for a deployment to come up or a request to come back: a cold
# 16-layer replica took 75 s to construct, its first requests compile.
SERVE_TIMEOUT_S = 600.0
SEED = 0
# adamw on a repeating batch of random tokens: large enough that six
# steps lower the loss through bf16 weights
LEARNING_RATE = 1e-3

# A kernel result may differ from its reference by this share of the
# reference's largest magnitude. Operands are bf16 on both sides (2^-8
# relative spacing) and accumulation is float32, and on a TPU the
# float32 reference's own matmuls run in bf16 passes, so honest
# differences are a few bf16 ulps of the largest terms; a wrong mask,
# scale, block index or a dropped block is O(1).
KERNEL_TOL = 2e-2


class PhaseError(RuntimeError):
    """A phase ran and what came out is wrong."""


# ---------------------------------------------------------------------
# phase 0: kernels (runs inside one num_tpus=1 task)
# ---------------------------------------------------------------------

def check_kernels(attn_shapes: Sequence[tuple], rms_shapes: Sequence[tuple],
                  int8_shape: tuple, decode_shape: tuple,
                  expect_kernels: bool = True) -> Dict[str, Any]:
    """Each pallas_call site against its reference. ``attn_shapes``:
    (batch, seq, heads, head_dim, with_grads); ``rms_shapes``: x shapes;
    ``int8_shape``: (rows, k, n); ``decode_shape``: a serving cache's
    (layers, slots, rows, kv_heads, head_dim) and the query heads a KV
    head. With ``expect_kernels`` every checked program must hold its
    kernel in the lowered text."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.accelerators import jax_backend
    from ray_tpu.ops import attention as att
    from ray_tpu.ops import quant_matmul as qm
    from ray_tpu.ops import rmsnorm as rn

    jax_backend.track_compile_time()
    results: Dict[str, Any] = {}

    def close(name, got, want):
        got = jnp.asarray(got, jnp.float32)
        want = jnp.asarray(want, jnp.float32)
        if got.shape != want.shape:
            raise PhaseError(f"{name}: shape {got.shape} != {want.shape}")
        scale = float(jnp.max(jnp.abs(want)))
        err = float(jnp.max(jnp.abs(got - want))) / max(scale, 1e-30)
        if not err <= KERNEL_TOL:  # also catches NaN
            raise PhaseError(
                f"{name}: max error {err:.3g} of the reference's max "
                f"{scale:.3g} exceeds {KERNEL_TOL}")
        results[name] = round(err, 5)

    def held(name, jitted, args, wanted):
        found = jax_backend.pallas_kernels(jitted.lower(*args).as_text())
        results[name + ".kernels"] = found
        if expect_kernels:
            _require_kernels(name, found, wanted, [])

    def ref_attention(q, k, v):
        # one example at a time: the reference materializes the
        # [heads, seq, seq] float32 scores
        return jax.lax.map(
            lambda qkv: att._attention_reference(
                qkv[0][None], qkv[1][None], qkv[2][None], True)[0],
            (q, k, v))

    for b, s, h, d, with_grads in attn_shapes:
        keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
        q, k, v, g = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
                      for kk in keys)
        tag = f"flash[{b},{s},{h},{d}]"
        fwd = jax.jit(lambda q, k, v: att.flash_attention(q, k, v, True))
        held(tag, fwd, (q, k, v), ["flash_fwd"])
        close(tag, fwd(q, k, v), jax.jit(ref_attention)(q, k, v))
        if not with_grads:
            continue

        def grads(fn):
            return jax.jit(lambda q, k, v, g: jax.vjp(fn, q, k, v)[1](g))

        kern = grads(lambda q, k, v: att.flash_attention(q, k, v, True))
        held(tag + ".grad", kern, (q, k, v, g),
             ["flash_fwd", "flash_dq", "flash_dkv"])
        for name, a, r in zip(("dq", "dk", "dv"), kern(q, k, v, g),
                              grads(ref_attention)(q, k, v, g)):
            close(f"{tag}.{name}", a, r)

    for shape in rms_shapes:
        kx, kw = jax.random.split(jax.random.PRNGKey(SEED + 1))
        x = jax.random.normal(kx, shape, jnp.bfloat16)
        w = 1.0 + 0.1 * jax.random.normal(kw, shape[-1:], jnp.bfloat16)
        tag = f"rms_norm{list(shape)}"
        fn = jax.jit(lambda x, w: rn.rms_norm(x, w, 1e-5))
        held(tag, fn, (x, w), ["rms_norm"])
        close(tag, fn(x, w), rn._rms_norm_reference(x, w, 1e-5))

    rows, kdim, ndim = int8_shape
    kx, kw = jax.random.split(jax.random.PRNGKey(SEED + 2))
    x = jax.random.normal(kx, (rows, kdim), jnp.bfloat16)
    w8, scale = qm.quantize_int8(
        jax.random.normal(kw, (kdim, ndim), jnp.float32) * kdim ** -0.5)
    tag = f"int8_matmul[{rows},{kdim},{ndim}]"
    fn = jax.jit(lambda x, w8, scale: qm.int8_matmul(x, w8, scale))
    held(tag, fn, (x, w8, scale), ["int8_matmul"])
    close(tag, fn(x, w8, scale),
          x.astype(jnp.float32) @ (w8.astype(jnp.float32) * scale[None, :]))

    *cache_shape, n_rep = decode_shape
    layers, slots, cache_rows, kv_heads, head_dim = cache_shape
    keys = jax.random.split(jax.random.PRNGKey(SEED + 3), 3)
    q = jax.random.normal(keys[0], (slots, kv_heads, n_rep, head_dim),
                          jnp.bfloat16)
    ck, cv = (jax.random.normal(kk, cache_shape, jnp.bfloat16)
              for kk in keys[1:])
    # a parked slot, the cache's last row, and the rest in between
    pos = (jnp.arange(slots, dtype=jnp.int32) * 131) % cache_rows
    pos = pos.at[-1].set(cache_rows - 1)
    tag = f"decode_attention{list(decode_shape)}"
    fn = jax.jit(lambda q, ck, cv, pos: att.decode_attention(
        q, ck, cv, layers - 1, pos, jnp.bfloat16))
    held(tag, fn, (q, ck, cv, pos), ["decode_attention"])
    close(tag, fn(q, ck, cv, pos), att._decode_attention_reference(
        q, ck, cv, layers - 1, pos, jnp.bfloat16))

    return {"checks": results, "device": jax_backend.device_report(),
            "flash_fallbacks": list(att.kernel_fallbacks)}


def phase_kernels(*shapes):
    import ray_tpu

    task = ray_tpu.remote(num_tpus=1)(check_kernels)
    return ray_tpu.get(task.remote(*shapes), timeout=BUDGET_S)


# ---------------------------------------------------------------------
# phases 1 and 3a: train
# ---------------------------------------------------------------------

def train_programs(cfg, mesh, opt):
    """What the trainer phase jits, for its loop and for the
    compile-only test: ``init(key) -> (params, opt_state)``, the
    shardings of that state under the fsdp rules (the optimizer's
    moments shard like the weights they belong to), and the adamw step
    on llama_loss with kernels running per shard of `mesh`."""
    import jax
    import optax

    from ray_tpu.models.llama import (llama_init, llama_loss,
                                      llama_sharding_rules)
    from ray_tpu.parallel.sharding import infer_sharding

    def init(key):
        params = llama_init(key, cfg)
        return params, opt.init(params)

    shardings = infer_sharding(
        jax.eval_shape(init, jax.random.PRNGKey(SEED)), mesh,
        llama_sharding_rules("fsdp"))

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: llama_loss(p, tokens, targets, cfg, mesh))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return init, shardings, train_step


def _train_loop(config: Dict[str, Any]) -> None:
    """One rank of the trainer: adamw on llama_loss over a mesh of all
    the chips this worker owns."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import ray_tpu.train as train
    from ray_tpu.accelerators import jax_backend
    from ray_tpu.ops import attention as att
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    compile_s0 = jax_backend.compile_seconds()
    cfg, batch = config["model"], config["batch"]
    mesh = make_mesh(MeshSpec(fsdp=config["fsdp"]))
    init, shardings, train_step = train_programs(
        cfg, mesh, optax.adamw(LEARNING_RATE))
    # Initialised under jit straight into the target sharding: eagerly,
    # llama_init draws every stacked weight in float32 on device 0
    # before casting (2.9 GB for one [16, 4096, 11008]).
    params, opt_state = jax.jit(init, out_shardings=shardings)(
        jax.random.PRNGKey(SEED))
    state_bytes = sum(x.nbytes for x in jax.tree.leaves((params, opt_state)))
    batches = train.get_dataset_shard("train").iter_device_batches(
        batch_size=batch, dtypes=jnp.int32,
        sharding=NamedSharding(mesh, P(("data", "fsdp"))))
    step = None
    for i, b in enumerate(batches):
        if step is None:
            lowered = jax.jit(train_step, donate_argnums=(0, 1)).lower(
                params, opt_state, b["tokens"], b["targets"])
            kernels = jax_backend.pallas_kernels(lowered.as_text())
            step = lowered.compile()
            memory = step.memory_analysis()
        params, opt_state, loss = step(params, opt_state, b["tokens"],
                                       b["targets"])
        train.report({"step": i, "loss": float(loss)})
    n_params = cfg.num_params()
    train.report({
        "summary": True, "kernels": kernels,
        "flash_fallbacks": list(att.kernel_fallbacks),
        "device": jax_backend.device_report(),
        # this loop's share, should the worker have run a task before
        "compile_seconds": round(
            jax_backend.compile_seconds() - compile_s0, 3),
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "params": n_params,
        "state_bytes_per_param": round(state_bytes / n_params, 2),
        "compiled_gb_per_chip": {
            "arguments": round(memory.argument_size_in_bytes / 2**30, 2),
            "temporaries": round(memory.temp_size_in_bytes / 2**30, 2)}})


def _token_rows(batch: int, seq: int, vocab: int):
    """map_batches fn: row id -> (tokens, targets). Rows repeat with
    period `batch`, so every batch holds the same sequences and a few
    steps must lower the loss."""
    def tokenize(rows):
        import numpy as np
        seqs = np.stack([
            np.random.default_rng(SEED + int(i) % batch).integers(
                0, vocab, seq + 1) for i in rows["id"]])
        return {"tokens": seqs[:, :-1].astype(np.int32),
                "targets": seqs[:, 1:].astype(np.int32)}
    return tokenize


def phase_train(model, *, batch: int, seq: int, steps: int, chips: int,
                kernels: Sequence[str],
                storage_path: str = None) -> Dict[str, Any]:
    """JaxTrainer with ONE worker owning ``chips`` chips as an fsdp
    mesh. ``kernels``: the Pallas kernels the compiled step must hold."""
    import ray_tpu.data as rd
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ds = rd.range(batch * steps, parallelism=1).map_batches(
        _token_rows(batch, seq, model.vocab_size))
    result = JaxTrainer(
        _train_loop,
        train_loop_config={"model": model, "batch": batch, "fsdp": chips},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpu_chips_per_worker=chips),
        run_config=RunConfig(name=f"chip_smoke_train_{chips}",
                             storage_path=storage_path),
        datasets={"train": ds}).fit()
    if result.error is not None:
        raise PhaseError(f"trainer returned an error: {result.error!r}")
    history = result.metrics_history
    losses = [m["loss"] for m in history if "loss" in m]
    summary = history[-1]
    if len(losses) != steps or not summary.get("summary"):
        raise PhaseError(f"expected {steps} steps and a summary, got "
                         f"{len(losses)} losses: {history}")
    if not all(l == l and abs(l) != float("inf") for l in losses):
        raise PhaseError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise PhaseError(f"loss did not fall on a repeating batch: {losses}")
    _require_kernels("train step", summary["kernels"], kernels,
                     summary["flash_fallbacks"])
    if len(summary["device"]["device_ids"]) != chips:
        raise PhaseError(f"worker asked for {chips} chips, sees "
                         f"{summary['device']['device_ids']}")
    return {"losses": [round(l, 4) for l in losses],
            "first_report_device": history[0].get("device"),
            **{k: v for k, v in summary.items() if k != "summary"}}


def _require_kernels(what: str, found: List[str], wanted: Sequence[str],
                     fallbacks: List[str]) -> None:
    missing = [w for w in wanted
               if not any(f.startswith(w + "(") for f in found)]
    if missing:
        raise PhaseError(f"{what}: the config asks for {list(wanted)} "
                         f"and the lowered program holds no {missing} "
                         f"(found {found})")
    if fallbacks:
        raise PhaseError(f"{what}: flash attention fell back to the "
                         f"O(S^2) reference for {fallbacks}")


# ---------------------------------------------------------------------
# phases 2 and 3b: serve
# ---------------------------------------------------------------------

def _post(url: str, body: Dict[str, Any], timeout: float) -> bytes:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read()


def phase_serve(llm_config, *, prompt_lens: Sequence[int], max_tokens: int,
                prefill_kernels: Sequence[str],
                decode_kernels: Sequence[str]) -> Dict[str, Any]:
    """serve.run(build_openai_app) behind the HTTP proxy; one concurrent
    POST /v1/completions per entry of ``prompt_lens`` (bytes), the
    first one streamed; then GET /v1/stats until every replica has
    answered."""
    from ray_tpu import serve
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.serve.config import HTTPOptions
    from ray_tpu.serve.llm import build_openai_app

    http = HTTPOptions()
    base = f"http://{http.host}:{http.port}/v1"
    t0 = time.monotonic()
    serve.start(http_options=http, proxy=True)
    try:
        serve.run(build_openai_app(config=llm_config), route_prefix="/v1",
                  timeout_s=SERVE_TIMEOUT_S)
        ready_seconds = round(time.monotonic() - t0, 1)
        # random weights may pick EOS; the count asked for must come out
        no_eos = {str(ByteTokenizer.eos_id): -100}
        answers: List[Any] = [None] * len(prompt_lens)

        def ask(i: int, n_bytes: int) -> None:
            prompt = "".join(chr(97 + (i + j) % 26) for j in range(n_bytes))
            body = {"prompt": prompt, "max_tokens": max_tokens,
                    "temperature": 0.0, "logit_bias": no_eos}
            try:
                if i == 0:
                    raw = _post(base + "/completions",
                                {**body, "stream": True},
                                SERVE_TIMEOUT_S).decode()
                    events = [l[6:] for l in raw.splitlines()
                              if l.startswith("data: ")]
                    chunks = [json.loads(e) for e in events[:-1]]
                    # one chunk per token, a closing chunk, then [DONE]
                    done = events[-1] == "[DONE]" and \
                        chunks[-1]["choices"][0]["finish_reason"] == "stop"
                    answers[i] = len(chunks) - 1 if done else raw[-300:]
                else:
                    out = json.loads(_post(base + "/completions", body,
                                           SERVE_TIMEOUT_S))
                    answers[i] = out["usage"]["completion_tokens"]
            except Exception as exc:  # noqa: BLE001 — reported below
                answers[i] = repr(exc)

        threads = [threading.Thread(target=ask, args=(i, n))
                   for i, n in enumerate(prompt_lens)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(SERVE_TIMEOUT_S + 30)
        if answers != [max_tokens] * len(prompt_lens):
            raise PhaseError(f"asked for {max_tokens} tokens from each of "
                             f"{len(prompt_lens)} requests, got {answers}")
        # /v1/stats is routed like any request: ask until every
        # replica has spoken
        replicas: Dict[int, Dict[str, Any]] = {}
        deadline = time.monotonic() + 120
        while (len(replicas) < llm_config.num_replicas
               and time.monotonic() < deadline):
            with urllib.request.urlopen(base + "/stats", timeout=120) as r:
                stats = json.loads(r.read())
            replicas[stats["device"]["pid"]] = stats
        if len(replicas) < llm_config.num_replicas:
            raise PhaseError(f"{llm_config.num_replicas} replicas asked "
                             f"for, {len(replicas)} answered /v1/stats")
    finally:
        serve.shutdown()
    generated = sum(s["total_generated"] for s in replicas.values())
    if generated != max_tokens * len(prompt_lens):
        raise PhaseError(f"replicas generated {generated} tokens in all, "
                         f"expected {max_tokens * len(prompt_lens)}")
    for stats in replicas.values():
        for name, found in stats["programs"].items():
            _require_kernels(
                name, found, prefill_kernels if name.startswith("prefill")
                else decode_kernels, stats["flash_fallbacks"])
    return {"answers": answers, "ready_seconds": ready_seconds,
            "replicas": list(replicas.values())}


# ---------------------------------------------------------------------
# main: the real sizes, and the refusal to run without a chip
# ---------------------------------------------------------------------

def _fail(message: str) -> "NoReturn":  # noqa: F821
    sys.stderr.write(f"chip_smoke: FAILED: {message}\n")
    sys.exit(1)


def _log_tails() -> str:
    """The last 40 lines of the three newest worker logs under
    <session>/logs/."""
    from ray_tpu.core import runtime as runtime_mod
    rt = runtime_mod.get_runtime_or_none()
    if rt is None:
        return ""
    logs = []
    for node in rt.nodes.values():
        log_dir = os.path.join(node.session_dir, "logs")
        logs += [os.path.join(log_dir, f) for f in os.listdir(log_dir)]
    out = []
    for path in sorted(logs, key=os.path.getmtime)[-3:]:
        with open(path, errors="replace") as f:
            tail = f.readlines()[-40:]
        out.append(f"--- {path} ---\n{''.join(tail)}")
    return "\n".join(out)


def _show(phase: str, report: Dict[str, Any], seconds: float) -> None:
    print(f"[chip_smoke] {phase}: ok in {seconds:.1f}s")
    print(json.dumps(report, indent=1, default=str))
    sys.stdout.flush()


def main() -> None:
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms and platforms.split(",")[0].strip() != "tpu":
        _fail(f"JAX_PLATFORMS={platforms!r} does not put the TPU first; "
              "this check runs on a TPU only")

    def on_alarm(signum, frame):
        raise TimeoutError(f"not done after {BUDGET_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(BUDGET_S)

    import ray_tpu
    from ray_tpu.accelerators import TpuAcceleratorManager, jax_backend
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMConfig

    chips = TpuAcceleratorManager.num_chips_on_node()
    if chips < 1:
        _fail("no TPU chip on this machine: looked for /dev/accel* and "
              "numbered entries of /dev/vfio")
    ray_tpu.init()
    phases: Dict[str, Any] = {}
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < 1:
            _fail(f"cluster_resources() has no TPU ({chips} chips "
                  "detected on the node)")
        # a worker that owns every chip of the machine: what JAX reports
        # there is what it reports to any process that sees them all
        where = ray_tpu.get(ray_tpu.remote(num_tpus=int(have))(
            jax_backend.device_report).remote(), timeout=300)
        if where["platform"] != "tpu":
            _fail(f"a num_tpus={int(have)} worker computes on {where}, "
                  "not a TPU")
        if len(where["device_ids"]) != int(have):
            _fail(f"the cluster has {have} TPU and a worker owning them "
                  f"all sees devices {where['device_ids']}")
        print(f"[chip_smoke] {where['platform']}, "
              f"{where['device_kind']}, chips {where['device_ids']}")

        def run(name, fn, *args, **kwargs):
            t0 = time.monotonic()
            try:
                report = fn(*args, **kwargs)
            except BaseException as exc:
                sys.stderr.write(_log_tails() + "\n")
                _fail(f"phase {name}: {type(exc).__name__}: {exc}")
            _check_tpu(name, report, where["device_kind"])
            phases[name] = report
            _show(name, report, time.monotonic() - t0)

        flash = ["flash_fwd", "flash_dq", "flash_dkv"]
        seq = 2048
        run("0_kernels", phase_kernels,
            # the train step's attention (one chip: batch 4; four chips:
            # batch 2 a shard) and one prefill bucket
            [(4, seq, 32, 128, True), (2, seq, 32, 128, True),
             (1, 512, 32, 128, False)],
            # train rows, a prefill bucket, the decode batch
            [(4, seq, 4096), (1, 512, 4096), (8, 1, 4096)],
            # on no later phase: Llama-2-7B's FFN width (11008) divides
            # neither of its block sizes
            (8, 4096, 4096),
            # the serve phase's cache, two layers of it, one query head
            # a KV head
            (2, 8, 1024, 32, 128, 1))
        run("1_train_1chip", phase_train,
            LlamaConfig.llama2_7b(n_layers=4, max_seq_len=seq,
                                  ce_chunk_tokens=4096),
            batch=4, seq=seq, steps=6, chips=1,
            kernels=flash + ["rms_norm"])
        engine = EngineConfig(
            model=LlamaConfig.llama2_7b(n_layers=16, max_seq_len=1024),
            max_batch=8, max_seq=1024, seed=SEED)
        serve_kw = dict(max_tokens=32,
                        prefill_kernels=["flash_fwd", "rms_norm"],
                        decode_kernels=["decode_attention", "rms_norm"])
        # with the BOS token these land in prefill buckets 256 and 512
        lens = [128, 160, 200, 255, 300, 384, 448, 511]
        run("2_serve_1chip", phase_serve,
            LLMConfig(model_id="llama2-7b-16l", engine=engine,
                      use_tpu=True), prompt_lens=lens, **serve_kw)
        if have >= 4:
            run("3_train_4chip", phase_train,
                LlamaConfig.llama2_7b(n_layers=16, max_seq_len=seq,
                                      ce_chunk_tokens=4096),
                batch=8, seq=seq, steps=6, chips=4,
                kernels=flash + ["rms_norm"])
            run("3_serve_4replicas", phase_serve,
                LLMConfig(model_id="llama2-7b-16l", engine=engine,
                          use_tpu=True, num_replicas=4),
                prompt_lens=lens * 2, **serve_kw)
            ids = [r["device"]["visible_chips"]
                   for r in phases["3_serve_4replicas"]["replicas"]]
            if len(set(ids)) != 4 or None in ids:
                _fail(f"four replicas report chips {ids}, not four "
                      "different ones")
    finally:
        signal.alarm(0)
        ray_tpu.shutdown()
    # after shutdown: the log monitor echoes worker output to stdout,
    # and the result must be the last line
    print(f"[chip_smoke] passed: {sorted(phases)}; no rate measured")
    print(summary_line(where))


def summary_line(where: Dict[str, Any]) -> str:
    """The result the caller parses, exactly ``ok`` and ``device``:
    the device as JAX reported it to a worker owning every chip
    (``where``: its ``device_report()``)."""
    return json.dumps({"ok": True, "device": {
        "platform": where["platform"], "kind": where["device_kind"],
        "count": len(where["device_ids"])}})


def _check_tpu(phase: str, report: Dict[str, Any], kind: str) -> None:
    """Every device report in a phase's result names the TPU."""
    def walk(node):
        if isinstance(node, dict):
            if "platform" in node and "device_kind" in node:
                yield node
            for v in node.values():
                yield from walk(v)
        elif isinstance(node, list):
            for v in node:
                yield from walk(v)

    seen = list(walk(report))
    bad = [d for d in seen
           if d["platform"] != "tpu" or d["device_kind"] != kind]
    if not seen or bad:
        _fail(f"phase {phase}: device reports {seen} are not all "
              f"'tpu' / {kind!r}")


if __name__ == "__main__":
    main()
