"""whereis: step-time attribution from the flight-recorder journal.

Answers "where did my step time go" by folding the merged (clock-
aligned) journals into per-step fractions:

* **compute** — FWD/BWD/STEP instruction time on pipeline stages,
* **comms**   — SEND/RECV channel time plus collective-hop time,
* **data_wait** — prefetch consumer stalls (the trainer starving on
  input) measured on the consuming process,
* **bubble**  — ``1 - compute/wall`` per stage, the SAME formula the
  live pipeline report uses (so the measured number here must agree
  with ``PipelineRunner.step()``'s within noise),
* **idle**    — whatever the named categories don't cover.

Podracer RL runs (category ``rl``) get their own rollup: time is
attributed into **acting** (env-runner rollouts), **inference-wait**
(batched policy forwards the actors block on), **learning** (learner
updates) and **weight-sync** (quantized weight broadcasts), plus the
learner's replay-queue wait — the Sebulba version of "where did my
step time go".

Usage::

    ray_tpu.whereis()                      # live, after some steps ran
    ray_tpu.flight_journal("run.json")     # dump for offline analysis
    python -m ray_tpu.devtools.whereis run.json

The theoretical bubble is recomputed from the schedule parameters the
stage spans carry (``(S-1)/(M+S-1)`` for 1F1B/GPipe) and printed next
to the measured one — the gap is what schedule tuning can recover.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

_COMPUTE_OPS = ("FWD", "BWD", "STEP")
_COMMS_OPS = ("SEND", "RECV")


def attribution(journals: Optional[Dict[str, List[tuple]]] = None
                ) -> Dict[str, Any]:
    """Fold journals (label -> aligned event tuples) into the
    attribution report. With no argument, reads the live merged
    journals from the flight recorder."""
    if journals is None:
        from ray_tpu.util import flight_recorder
        journals = flight_recorder.merged_journals()

    # per (stage, step): wall/compute from the stage_step envelope,
    # comms summed from SEND/RECV instruction spans
    per: Dict[tuple, Dict[str, float]] = {}
    sched_params = None  # (schedule, S, M) off any stage_step span
    data_wait_ns = 0
    coll_count = 0
    coll_wire = 0
    coll_ratios: List[float] = []
    # Podracer RL spans → acting / inference-wait / learning /
    # weight-sync (plus the learner's replay wait)
    rl_ns = {"acting": 0, "inference_wait": 0, "learning": 0,
             "weight_sync": 0, "replay_wait": 0}
    rl_env_steps = 0
    rl_seen = False
    # the stall watch's episodes (category ``proc``), by journal:
    # "frozen" / "starved" / "held:<thread>/<phase>" -> [count, seconds]
    stalls: Dict[str, Dict[str, List[float]]] = {}
    t_lo: Optional[int] = None
    t_hi: Optional[int] = None

    for label, events in journals.items():
        for seq, t0, dur, cat, name, args in events:
            t_lo = t0 if t_lo is None else min(t_lo, t0)
            t_hi = (t0 + dur) if t_hi is None else max(t_hi, t0 + dur)
            if cat == "pipeline":
                a = args or {}
                key = (a.get("stage"), a.get("step"))
                entry = per.setdefault(
                    key, {"wall_s": 0.0, "compute_s": 0.0,
                          "comms_s": 0.0})
                if name == "stage_step":
                    entry["wall_s"] = float(a.get("wall_s",
                                                  dur / 1e9))
                    entry["compute_s"] = float(a.get("compute_s", 0.0))
                    if a.get("schedule") is not None:
                        sched_params = (a.get("schedule"), a.get("S"),
                                        a.get("m"))
                elif name in _COMMS_OPS:
                    entry["comms_s"] += dur / 1e9
            elif cat == "prefetch" and name == "consumer_wait":
                data_wait_ns += dur
            elif cat == "collective":
                coll_count += 1
                a = args or {}
                coll_wire += int(a.get("wire", 0))
                if "ratio" in (a or {}):
                    coll_ratios.append(float(a["ratio"]))
            elif cat == "proc":
                a = args or {}
                what = (f"held:{a.get('thread')}/{a.get('phase')}"
                        if name == "thread.held" else a.get("kind"))
                entry = stalls.setdefault(label, {}).setdefault(
                    what, [0, 0.0])
                entry[0] += 1
                entry[1] = round(entry[1] + dur / 1e9, 6)
            elif cat == "rl":
                rl_seen = True
                a = args or {}
                if name == "rollout":
                    rl_ns["acting"] += dur
                    rl_env_steps += int(a.get("env_steps", 0))
                elif name == "infer_batch":
                    rl_ns["inference_wait"] += dur
                elif name == "learn_step":
                    rl_ns["learning"] += dur
                    # Anakin has no rollout spans: the fused step IS
                    # the rollout, so its env steps ride learn_step
                    if a.get("arch") == "anakin":
                        rl_env_steps += int(a.get("env_steps", 0))
                elif name == "weight_push":
                    rl_ns["weight_sync"] += dur
                elif name == "replay_wait":
                    rl_ns["replay_wait"] += dur

    steps = {k: v for k, v in per.items() if v["wall_s"] > 0}
    wall = sum(v["wall_s"] for v in steps.values())
    compute = sum(v["compute_s"] for v in steps.values())
    comms = sum(v["comms_s"] for v in steps.values())
    window_s = ((t_hi - t_lo) / 1e9 if t_hi is not None else 0.0)
    data_wait_s = data_wait_ns / 1e9

    # per-stage rollup (bubble = 1 - compute/wall, the live formula)
    per_stage: Dict[Any, Dict[str, float]] = {}
    for (stage, _step), v in steps.items():
        agg = per_stage.setdefault(
            stage, {"steps": 0, "wall_s": 0.0, "compute_s": 0.0,
                    "comms_s": 0.0})
        agg["steps"] += 1
        agg["wall_s"] += v["wall_s"]
        agg["compute_s"] += v["compute_s"]
        agg["comms_s"] += v["comms_s"]
    for agg in per_stage.values():
        agg["bubble"] = (max(0.0, 1.0 - agg["compute_s"]
                             / agg["wall_s"])
                         if agg["wall_s"] > 0 else 0.0)

    measured_bubble = (sum(a["bubble"] for a in per_stage.values())
                       / len(per_stage)) if per_stage else None

    theoretical = None
    if sched_params and sched_params[1] and sched_params[2]:
        try:
            from ray_tpu.train.pipeline import schedule as sched_mod
            theoretical = sched_mod.bubble_fraction(
                int(sched_params[1]), int(sched_params[2]),
                sched_params[0])
        except Exception:  # noqa: BLE001 — old dump, unknown schedule
            theoretical = None

    frac = {}
    if wall > 0:
        c = compute / wall
        m = comms / wall
        d = min(1.0, data_wait_s / window_s) if window_s > 0 else 0.0
        frac = {"compute": round(c, 4), "comms": round(m, 4),
                "data_wait": round(d, 4),
                "bubble": round(max(0.0, 1.0 - c), 4),
                "idle": round(max(0.0, 1.0 - c - m), 4)}

    rl_report = None
    if rl_seen:
        total_ns = sum(rl_ns[k] for k in
                       ("acting", "inference_wait", "learning",
                        "weight_sync"))
        rl_report = {k + "_s": round(v / 1e9, 6)
                     for k, v in rl_ns.items()}
        rl_report["env_steps"] = rl_env_steps
        if window_s > 0 and rl_env_steps:
            rl_report["env_steps_per_sec"] = round(
                rl_env_steps / window_s, 1)
        if total_ns > 0:
            rl_report["fractions"] = {
                k: round(rl_ns[k] / total_ns, 4)
                for k in ("acting", "inference_wait", "learning",
                          "weight_sync")}

    return {
        "steps": len({k[1] for k in steps}),
        "stages": len(per_stage),
        "window_s": round(window_s, 6),
        "fractions": frac,
        "per_stage": {str(k): {kk: (round(vv, 6)
                                    if isinstance(vv, float) else vv)
                               for kk, vv in v.items()}
                      for k, v in sorted(per_stage.items(),
                                         key=lambda kv: str(kv[0]))},
        "measured_bubble": (round(measured_bubble, 4)
                            if measured_bubble is not None else None),
        "theoretical_bubble": (round(theoretical, 4)
                               if theoretical is not None else None),
        "data_wait_s": round(data_wait_s, 6),
        "collectives": {"count": coll_count, "wire_bytes": coll_wire,
                        "mean_compression_ratio": (
                            round(sum(coll_ratios) / len(coll_ratios),
                                  3) if coll_ratios else None)},
        "rl": rl_report,
        "stalls": stalls,
    }


# --- submit-path phase attribution (PR 18) ---------------------------
# core/task_phase.py brackets 1-in-N submissions into a contiguous
# spec-build → result-return chain of ``task_phase`` events; this fold
# turns them into the per-phase µs budget ROADMAP item 2 is judged
# against. ``coverage`` is the union of the sampled chains' spans over
# the window — the fraction of submit+drain wall time the table
# accounts for (acceptance bar: ≥ 0.85 on the 20k-task harness).

def task_path_attribution(
        journals: Optional[Dict[str, List[tuple]]] = None,
        window_ns: Optional[tuple] = None) -> Dict[str, Any]:
    """Fold ``task_phase`` events into {phase: {count, total_us,
    mean_us, p50_us, p99_us}} plus chain-level coverage. ``window_ns``
    is an optional (lo, hi) pair in the driver clock domain (the bench
    harness passes its measured submit+drain window); without it the
    span of the phase events themselves is used."""
    if journals is None:
        from ray_tpu.util import flight_recorder
        journals = flight_recorder.merged_journals()

    from ray_tpu.core.task_phase import PHASES
    per: Dict[str, List[int]] = {}
    intervals: List[tuple] = []
    for label, events in journals.items():
        for seq, t0, dur, cat, name, args in events:
            if cat != "task_phase":
                continue
            per.setdefault(name, []).append(dur)
            intervals.append((t0, t0 + dur))

    if window_ns is not None:
        lo, hi = window_ns
    elif intervals:
        lo = min(iv[0] for iv in intervals)
        hi = max(iv[1] for iv in intervals)
    else:
        lo = hi = 0

    # union of chain spans, clipped to the window
    covered = 0
    cur_lo = cur_hi = None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_hi is None or s > cur_hi:
            if cur_hi is not None:
                covered += cur_hi - cur_lo
            cur_lo, cur_hi = s, e
        else:
            cur_hi = max(cur_hi, e)
    if cur_hi is not None:
        covered += cur_hi - cur_lo
    window = hi - lo
    coverage = covered / window if window > 0 else None

    def _q(durs: List[int], q: float) -> Optional[float]:
        if not durs:
            return None
        i = min(len(durs) - 1, int(q * len(durs)))
        return durs[i] / 1e3

    phases: Dict[str, Dict[str, Any]] = {}
    order = [p for p in PHASES if p in per] + sorted(
        p for p in per if p not in PHASES)
    for name in order:
        durs = sorted(per[name])
        total = sum(durs)
        phases[name] = {
            "count": len(durs),
            "total_us": round(total / 1e3, 1),
            "mean_us": round(total / len(durs) / 1e3, 2),
            "p50_us": round(_q(durs, 0.50), 2),
            "p99_us": round(_q(durs, 0.99), 2),
        }

    tasks = len(per.get("result-return", ()))
    chain_total = sum(v["total_us"] for v in phases.values())
    # e2e percentiles off the live histogram when available — tolerant
    # of empty/None snapshots (util/metrics.py returns None, never
    # raises, on an unobserved series)
    e2e = {}
    try:
        from ray_tpu.core.task_manager import TASK_E2E_SECONDS
        for q in (0.5, 0.99):
            value = TASK_E2E_SECONDS.percentile(q)
            if value is not None:
                e2e[f"p{int(q * 100)}_ms"] = round(value * 1e3, 3)
    except Exception:  # graftlint: disable=GL004
        pass  # offline dumps have no runtime/registry to read from

    return {
        "phases": phases,
        "tasks_sampled": tasks,
        "mean_chain_us": (round(chain_total / tasks, 1)
                          if tasks else None),
        "window_s": round(window / 1e9, 6),
        "coverage": (round(coverage, 4)
                     if coverage is not None else None),
        "task_e2e": e2e or None,
    }


def render_task_path(report: Dict[str, Any]) -> str:
    lines = ["submit-path phase budget (flight recorder, sampled)"]
    lines.append(
        f"  tasks sampled: {report['tasks_sampled']}  "
        f"window: {report['window_s'] * 1e3:.1f}ms  "
        + (f"coverage: {report['coverage'] * 100:.1f}%"
           if report["coverage"] is not None else "coverage: n/a"))
    lines.append("  %-16s %8s %10s %10s %10s %12s"
                 % ("phase", "count", "mean_us", "p50_us", "p99_us",
                    "total_ms"))
    for name, row in report["phases"].items():
        lines.append("  %-16s %8d %10.2f %10.2f %10.2f %12.2f"
                     % (name, row["count"], row["mean_us"],
                        row["p50_us"], row["p99_us"],
                        row["total_us"] / 1e3))
    if report["mean_chain_us"] is not None:
        lines.append(f"  mean sampled chain: "
                     f"{report['mean_chain_us']:.1f}us/task")
    e2e = report.get("task_e2e")
    if e2e:
        lines.append("  task e2e: " + "  ".join(
            f"{k}={v}" for k, v in e2e.items()))
    return "\n".join(lines)


def render(report: Dict[str, Any]) -> str:
    lines = ["step-time attribution (flight recorder)"]
    lines.append(f"  pipeline stages: {report['stages']}  "
                 f"steps: {report['steps']}  "
                 f"window: {report['window_s'] * 1e3:.1f}ms")
    frac = report.get("fractions") or {}
    if frac:
        lines.append(
            "  compute %5.1f%%  comms %5.1f%%  data-wait %5.1f%%  "
            "bubble %5.1f%%  idle %5.1f%%" % (
                frac["compute"] * 100, frac["comms"] * 100,
                frac["data_wait"] * 100, frac["bubble"] * 100,
                frac["idle"] * 100))
    mb, tb = report["measured_bubble"], report["theoretical_bubble"]
    if mb is not None:
        line = f"  measured bubble: {mb:.3f}"
        if tb is not None:
            line += f"  theoretical: {tb:.3f}  gap: {mb - tb:+.3f}"
        lines.append(line)
    for stage, agg in report["per_stage"].items():
        lines.append(
            f"  stage {stage}: steps={agg['steps']} "
            f"wall={agg['wall_s'] * 1e3:.1f}ms "
            f"compute={agg['compute_s'] * 1e3:.1f}ms "
            f"comms={agg['comms_s'] * 1e3:.1f}ms "
            f"bubble={agg['bubble']:.3f}")
    coll = report["collectives"]
    if coll["count"]:
        lines.append(
            f"  collectives: {coll['count']} hops, "
            f"{coll['wire_bytes']} wire bytes, "
            f"ratio={coll['mean_compression_ratio']}")
    if report["data_wait_s"]:
        lines.append(
            f"  data wait: {report['data_wait_s'] * 1e3:.1f}ms")
    rl = report.get("rl")
    if rl:
        rf = rl.get("fractions") or {}
        if rf:
            lines.append(
                "  rl: acting %5.1f%%  inference-wait %5.1f%%  "
                "learning %5.1f%%  weight-sync %5.1f%%" % (
                    rf["acting"] * 100, rf["inference_wait"] * 100,
                    rf["learning"] * 100, rf["weight_sync"] * 100))
        line = (f"  rl: env steps {rl['env_steps']}  "
                f"replay wait {rl['replay_wait_s'] * 1e3:.1f}ms")
        if "env_steps_per_sec" in rl:
            line += f"  ({rl['env_steps_per_sec']:.0f} steps/s)"
        lines.append(line)
    for label, kinds in sorted((report.get("stalls") or {}).items()):
        lines.append(f"  stalls of {label}: " + "  ".join(
            f"{what} x{n} {seconds * 1e3:.0f}ms"
            for what, (n, seconds) in sorted(kinds.items())))
    return "\n".join(lines)


def _load_journals(path: str) -> Dict[str, List[tuple]]:
    with open(path) as f:
        payload = json.load(f)
    journals = payload.get("journals", payload)
    return {label: [tuple(ev) for ev in events]
            for label, events in journals.items()}


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    task_path = "--task-path" in argv
    argv = [a for a in argv if a != "--task-path"]
    if not argv:
        print("usage: python -m ray_tpu.devtools.whereis "
              "[--task-path] <journal.json>\n(write one with "
              "ray_tpu.flight_journal('journal.json'))",
              file=sys.stderr)
        return 2
    journals = _load_journals(argv[0])
    if task_path:
        print(render_task_path(task_path_attribution(journals)))
    else:
        print(render(attribution(journals)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
