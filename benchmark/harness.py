"""What every runner shares: the cell as BENCHMARK.json and the data
files describe it, the modules its files name (runner, model family,
readers), the percentile and open-loop arithmetic, and the result line.

Nothing here touches a JAX backend: the process that runs a cell never
holds the chip (the worker or the replica the runtime spawns does).
"""

from __future__ import annotations

import importlib
import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
# jax.random.PRNGKey takes what 32 signed bits hold; the driver's
# seeds are a little larger
SEED_MODULUS = 2**31 - 1


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, a phase failed)."""


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, rehearse: bool = False) -> Dict[str, Any]:
    """One entry of BENCHMARK.json's workloads with its configuration
    and traffic files, and the metrics this cell reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = dict(cells[workload])
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    if rehearse:
        config.update(config.get("rehearse", {}))
    cell["config_file"] = config
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")

    def mine(metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    cell["end_to_end"] = mine(bench["end_to_end"])
    cell["per_layer"] = mine(bench["per_layer"])
    return cell


def runner_for(kind: str):
    """The runner of a traffic file's ``kind``, found by name."""
    return importlib.import_module(f"benchmark.runners.{kind}")


def program_for(name: str):
    """The module of a model family, found by a configuration file's
    ``program`` (what it gives: benchmark/programs/__init__.py)."""
    return importlib.import_module(f"benchmark.programs.{name}")


def reader_for(name: str):
    return importlib.import_module(f"benchmark.readers.{name}").read


def per_layer_values(cell: Dict[str, Any], observed: Dict[str, Any]
                     ) -> Dict[str, Dict[str, Any]]:
    """Each of the cell's per-layer metrics through its reader. A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out: Dict[str, Dict[str, Any]] = {}
    for metric in cell["per_layer"]:
        spec = load_json("layer_metrics", metric["name"] + ".json")
        value = reader_for(spec["reader"])(observed,
                                           **spec.get("args", {}))
        if value is not None and math.isfinite(value):
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def end_to_end_values(cell: Dict[str, Any], measured: Dict[str, float]
                      ) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics out of what the runner measured;
    one that the runner did not produce is an error, not a gap."""
    out = {}
    for metric in cell["end_to_end"]:
        if metric["name"] not in measured:
            raise BenchError(f"runner measured no {metric['name']}")
        out[metric["name"]] = {"value": measured[metric["name"]],
                               "unit": metric["unit"]}
    return out


# ---------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------

def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest rank: the smallest sample with at least q of the
    samples at or below it. No interpolation, so a tail is a latency
    some request really had."""
    if not samples:
        raise BenchError("percentile of no samples")
    ordered = sorted(samples)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def open_loop_latencies(records: Sequence[Dict[str, Any]]
                        ) -> Dict[str, Any]:
    """Client-side numbers of one open-loop window.

    ``records``: one per request sent, with ``due`` (when the schedule
    wanted it sent), ``sent``, ``token_times`` (arrival of each
    streamed token), ``finished`` and ``error``; seconds on the load
    generator's clock. Time to first token runs from ``due``, so a
    stall that delays a send is charged to the system, not hidden. A
    failed request, or one that never got a token, counts as the worst
    time any request of the window had.
    """
    ttft: List[Optional[float]] = []
    gaps: List[float] = []
    lags: List[float] = []
    for r in records:
        lags.append(r["sent"] - r["due"])
        times = r["token_times"]
        if r.get("error") or not times:
            ttft.append(None)
            continue
        ttft.append(times[0] - r["due"])
        gaps.extend(b - a for a, b in zip(times, times[1:]))
    known = [t for t in ttft if t is not None]
    worst = max(known) if known else float("inf")
    return {
        "ttft_s": [worst if t is None else t for t in ttft],
        "gaps_s": gaps,
        "lag_mean_s": sum(lags) / len(lags) if lags else 0.0,
        "lag_worst_s": max(lags) if lags else 0.0,
    }


def tokens_inside(records: Sequence[Dict[str, Any]], window_s: float
                  ) -> int:
    """Streamed tokens that reached the client before the window
    closed, whether or not their request had finished by then."""
    return sum(1 for r in records for t in r["token_times"]
               if t <= window_s)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None,
                compared: Optional[Dict[str, List[Any]]] = None) -> str:
    """``compared``: every number that ``correct`` compared, ``name ->
    [number, limit]`` (the limit None where the number is only shown);
    it comes last in the line, so that a run that is not correct says
    in its last line by which of them."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if compared:
        line["compared"] = compared
    return json.dumps(line)


def compared_lines(compared: Dict[str, List[Any]]) -> str:
    """The same for the end of standard error: a line a number."""
    return "".join(f"benchmark: compared {name}: {value} limit {limit}\n"
                   for name, (value, limit) in compared.items())


def device_and_breakdown(report: Dict[str, Any],
                         traced: Optional[Dict[str, Any]]):
    """The line's ``device`` from a worker's device_report() and, for
    a traced run, the trace's busy time, window and ``breakdown``."""
    device = {"platform": report["platform"],
              "kind": report["device_kind"],
              "count": len(report["device_ids"]),
              "memory_peak_bytes": report.get("peak_bytes_in_use")}
    if not traced:
        return device, None
    device.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
    return device, {"device_ops": traced["device_ops"],
                    "idle_gaps": traced["idle_gaps"]}


def missing_kernels(found: Sequence[str], wanted: Sequence[str]
                    ) -> List[str]:
    """Which of the ``wanted`` Pallas kernels a lowered program's
    kernel list (``name(operand types)`` entries) does not hold."""
    return [w for w in wanted
            if not any(f.startswith(w + "(") for f in found)]


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]
