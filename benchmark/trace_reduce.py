"""From a profiler trace (.xplane.pb) to numbers: device busy time and
window, time per operation name, and the longest idle gaps with what
the host was doing in them. Read with jax.profiler.ProfileData,
nothing else.

What counts as a device operation: on a TPU plane (``/device:TPU:n``)
every event of the line ``XLA Ops``; in a trace recorded on the CPU
(the small one under benchmark/tests/) every host event that carries
an ``hlo_op`` stat. Names are the trace's own.

The window is the host span ``bench_window`` where the traced code
set one (jax.profiler.TraceAnnotation), else from the first device
operation's start to the last one's end.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

WINDOW_SPAN = "bench_window"
_OPS_LINE = "XLA Ops"
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "all-to-all", "collective-permute")
# gaps shorter than this are the device's own turn-around between ops
_MIN_GAP_S = 20e-6
_ATTRIBUTED_GAPS = 400

Interval = Tuple[float, float]


def find_trace(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: List[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def short_name(name: str) -> str:
    """An XLA op's event name is its whole HLO line, ``%flash_fwd.5 =
    (bf16[1,32,512,128]{3,2,1,0:T(8,128)...}, ...) custom-call(...)``:
    keep the instruction's name and its result type without layouts,
    so that a Pallas kernel is found by its name and two programs'
    ``fusion.117`` stay apart."""
    if " = " not in name:
        return name[:96]
    head, rest = name.split(" = ", 1)
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            end = i
            break
    kind = re.sub(r"\{[^{}]*\}", "", rest[:end])
    return f"{head.lstrip('%')} {kind}"[:96]


def self_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, str]]:
    """(self seconds, name) of each event of one line: its duration
    less that of the events nested inside it (a ``while`` holds its
    body's ops on the same line)."""
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []     # (end, index into out)
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][0] -= min(end, stack[-1][0]) - start
        out.append([end - start, name])
        stack.append((end, len(out) - 1))
    return [(max(0.0, s), name) for s, name in out]


def _stats(event) -> Dict[str, Any]:
    return {k: v for k, v in event.stats}


def read_planes(path: str):
    """(device operations per device plane, host events per thread,
    the window span or None). Times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: Dict[str, List[Tuple[float, float, str]]] = {}
    window: Optional[Interval] = None
    planes = list(data.planes)
    # only a trace recorded on the CPU keeps its operations among the
    # host's events; reading every host event's stats is slow
    cpu_trace = not any(p.name.startswith("/device:TPU") for p in planes)
    for plane in planes:
        on_tpu = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if on_tpu:
                    if line.name == _OPS_LINE:
                        devices.setdefault(plane.name, []).append(
                            (start, end, short_name(ev.name)))
                    continue
                if not plane.name.startswith("/host:"):
                    continue
                if ev.name == WINDOW_SPAN:
                    window = (start, end)
                elif cpu_trace and "hlo_op" in _stats(ev):
                    devices.setdefault("/host:CPU", []).append(
                        (start, end, short_name(ev.name)))
                elif ev.duration_ns > 0:
                    host.setdefault(line.name, []).append(
                        (start, end, ev.name))
    return devices, host, window


_LAUNCH_MARKS = ("PjitFunction", "ExecuteSharded", "PjRtCApiLoadedExecutable",
                 "TpuExecute", "jit_", "pjit")


def _launcher(host) -> Optional[str]:
    """The host thread that hands programs to the device: the one
    with the most events that look like a launch."""
    counts = {thread: sum(1 for _, _, name in events
                          if any(m in name for m in _LAUNCH_MARKS))
              for thread, (_, events) in host.items()}
    best = max(counts, key=counts.get, default=None)
    return best if best is not None and counts[best] > 0 else None


def _what_host_did(gap: Interval, host, launcher: Optional[str]) -> str:
    """What the launching thread was in during the gap: the innermost
    (shortest) of its events that cover at least half of the gap.
    Without a launching thread, the best-fitting event of any."""
    lo, hi = gap
    length = hi - lo
    threads = [launcher] if launcher else list(host)
    best, best_len = "(launching thread in no traced call)", float("inf")
    for thread in threads:
        starts, events = host[thread]
        # events that start before the gap's middle, newest first
        i = bisect.bisect_right(starts, lo + length / 2)
        for start, end, name in reversed(events[max(0, i - 3000):i]):
            if min(end, hi) - max(start, lo) < length / 2:
                continue
            if end - start < best_len:
                best, best_len = name, end - start
    return best


def reduce_trace(path: str) -> Dict[str, Any]:
    """busy_s and op times are means over the device planes (the chips
    of one SPMD program do the same work); gaps are chip 0's. An op's
    seconds are its self time, so a loop does not count its body
    twice."""
    devices, host, window = read_planes(path)
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "ops": {},
                "device_ops": [], "idle_gaps": [], "collective_s": 0.0}
    if window is None:
        window = (min(e[0] for evs in devices.values() for e in evs),
                  max(e[1] for evs in devices.values() for e in evs))
    n = len(devices)
    busy = 0.0
    collective = 0.0
    ops: Dict[str, Dict[str, float]] = {}
    for events in devices.values():
        inside = [(max(a, window[0]), min(b, window[1]), name)
                  for a, b, name in events
                  if b > window[0] and a < window[1]]
        busy += total(merge([(a, b) for a, b, _ in inside])) / n
        collective += total(merge(
            [(a, b) for a, b, name in inside
             if any(c in name for c in _COLLECTIVES)])) / n
        for seconds, name in self_times(inside):
            entry = ops.setdefault(name, {"seconds": 0.0, "count": 0.0})
            entry["seconds"] += seconds / n
            entry["count"] += 1.0 / n
    first = devices[sorted(devices)[0]]
    merged = merge(clip([(a, b) for a, b, _ in first], window))
    edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= _MIN_GAP_S]
    gaps.sort(key=lambda g: g[0] - g[1])
    indexed = {}
    for thread, events in host.items():
        events.sort()
        indexed[thread] = ([e[0] for e in events], events)
    by_cause: Dict[str, float] = {}
    launcher = _launcher(indexed)
    for gap in gaps[:_ATTRIBUTED_GAPS]:
        cause = _what_host_did(gap, indexed, launcher)
        by_cause[cause] = by_cause.get(cause, 0.0) + gap[1] - gap[0]
    top = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])
    return {
        "devices": n,
        "busy_s": busy,
        "window_s": window[1] - window[0],
        "collective_s": collective,
        "ops": ops,
        "device_ops": [[name, v["seconds"]] for name, v in top[:10]],
        "idle_gaps": [[name, s] for name, s in sorted(
            by_cause.items(), key=lambda kv: -kv[1])[:10]],
    }


def op_seconds(reduced: Dict[str, Any], needle: str) -> Tuple[float, float]:
    """(seconds, calls) of every operation whose name holds ``needle``."""
    hits = [v for name, v in reduced["ops"].items() if needle in name]
    return (sum(v["seconds"] for v in hits), sum(v["count"] for v in hits))
