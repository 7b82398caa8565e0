"""Flight recorder: per-process lock-free ring-buffer event journal.

Capability parity with the reference's timeline/profiling layer
(PAPER.md survey L3: the dashboard answers "where did my step time
go"), extended with the crash-journal idiom from aviation: every
process keeps the last N events in a preallocated ring so a death or
stall can be reconstructed after the fact.

Three layers:

1. **Recorder** (every process) — a fixed-capacity list of slots
   claimed by an ``itertools.count`` ticket (``next()`` on a count is
   a single C call, atomic under the GIL) and written with one tuple
   store (a list-index assignment, also atomic). No locks anywhere on
   the record path, so it is safe from the ``rtpu-io-loop`` thread
   (graftlint GL013 enforces that loop-reachable code emits through
   THIS api, never the RPC-capable ``tracing.span``). When the
   recorder is disabled the hot-path cost is two loads and a compare::

       rec = flight_recorder.RECORDER
       if rec is not None:
           rec.record("io", "dispatch", t0_ns, dur_ns)

2. **Collector** (driver) — workers run a daemon flusher thread that
   periodically pushes journal increments over the worker→driver
   control channel (``flight_push``), preceded by a ping-pong clock
   sync (``flight_sync``): the worker samples its clock before and
   after reading the driver's, and ``offset = t_driver - midpoint``
   aligns its ``perf_counter_ns`` domain (arbitrary per-process epoch)
   onto the driver's. The driver keeps the last-N events per process —
   which doubles as the post-mortem source when a process dies without
   a chance to say goodbye.

3. **Export** — ``chrome_events()`` merges every journal (driver's own
   plus collected worker journals), applies the per-process offsets,
   and renders Chrome-trace/Perfetto ``X``/``i`` events on per-process
   tracks; ``ray_tpu.timeline()`` and the dashboard's ``/api/timeline``
   include them automatically. ``merged_journals()`` feeds the
   ``devtools.whereis`` step-time attribution report.

Event slot layout (plain tuple; one allocation per record)::

    (seq, t0_ns, dur_ns, category, name, args_or_None)

Categories used by the built-in instrumentation: ``io`` (IO-loop
dispatch / stream chunks), ``object`` (put/get/transfer), ``pipeline``
(stage instructions, tagged phase=warmup/steady/drain), ``shuffle``
(map/reduce waves), ``prefetch`` (producer/consumer waits),
``collective`` (allreduce &co with compression ratio), ``serve``
(engine steps, their phases as ``span``s, request queue waits), ``rl``
(podracer spans: rollout / infer_batch / replay_wait / learn_step /
weight_push).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_CAPACITY = 4096
# events kept per remote process in the driver-side collector
STORE_CAPACITY = 16384
# journal lines embedded in post-mortem error reports
TAIL_EVENTS = 40

_skew_ns: Optional[int] = None


def _test_skew_ns() -> int:
    """Test-only injected clock skew (``RTPU_FLIGHT_TEST_SKEW_NS``):
    a raw ns value, or ``random:<amp>`` for a per-process deterministic
    skew in ±amp (seeded by pid, so forked workers diverge). Applied
    inside ``clock_ns`` itself so the ping-pong sync must OBSERVE and
    CORRECT it — the clock-alignment test is meaningless otherwise."""
    global _skew_ns
    if _skew_ns is None:
        raw = os.environ.get("RTPU_FLIGHT_TEST_SKEW_NS", "")
        if raw.startswith("random:"):
            import random
            amp = int(float(raw.split(":", 1)[1]))
            _skew_ns = random.Random(os.getpid()).randint(-amp, amp)
        elif raw:
            _skew_ns = int(float(raw))
        else:
            _skew_ns = 0
    return _skew_ns


def clock_ns() -> int:
    """This process's journal clock: monotonic, arbitrary epoch."""
    return time.perf_counter_ns() + _test_skew_ns()


class Recorder:
    """Lock-free bounded journal. Writers from any thread; a snapshot
    may observe a torn ring mid-wrap (a slot overwritten between claim
    and scan) — acceptable: the journal is best-effort observability,
    never a consistency anchor."""

    __slots__ = ("capacity", "label", "_slots", "_seq")

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 label: str = ""):
        self.capacity = max(16, int(capacity))
        self.label = label or f"pid:{os.getpid()}"
        self._slots: List[Optional[tuple]] = [None] * self.capacity
        self._seq = itertools.count()

    # record() is THE hot path: claim a ticket (atomic), store a tuple
    # (atomic). No locks, no RPC — safe on the rtpu-io-loop thread.
    def record(self, cat: str, name: str, t0_ns: int, dur_ns: int,
               args: Optional[dict] = None) -> None:
        seq = next(self._seq)
        self._slots[seq % self.capacity] = (
            seq, t0_ns, dur_ns, cat, name, args)

    def instant(self, cat: str, name: str,
                args: Optional[dict] = None) -> None:
        self.record(cat, name, clock_ns(), 0, args)

    def clock(self) -> int:
        return clock_ns()

    def snapshot(self, since_seq: int = -1) -> List[tuple]:
        """Events with seq > since_seq, oldest first. Copies the slot
        list first so concurrent writers can't resize reality
        mid-scan."""
        slots = list(self._slots)
        events = [s for s in slots if s is not None and s[0] > since_seq]
        events.sort()
        return events

    def tail(self, n: int = TAIL_EVENTS) -> List[tuple]:
        return self.snapshot()[-n:]


# The module-level gate. Hot paths read this once and None-check it;
# rebinding is atomic under the GIL so enable/disable race nothing.
RECORDER: Optional[Recorder] = None


def enabled() -> bool:
    return RECORDER is not None


def enable(label: str = "", capacity: Optional[int] = None) -> Recorder:
    global RECORDER
    if capacity is None:
        from ray_tpu.core.config import get_config
        capacity = get_config().flight_recorder_capacity
    RECORDER = Recorder(capacity=capacity, label=label)
    _get_anchor()  # pin the wall/perf anchor while both clocks are live
    return RECORDER


def disable() -> None:
    global RECORDER
    RECORDER = None


def record(cat: str, name: str, t0_ns: int, dur_ns: int,
           args: Optional[dict] = None) -> None:
    """Convenience gate for cold paths; hot loops should inline the
    ``RECORDER`` None-check instead of paying a function call."""
    rec = RECORDER
    if rec is not None:
        rec.record(cat, name, t0_ns, dur_ns, args)


def instant(cat: str, name: str, args: Optional[dict] = None) -> None:
    rec = RECORDER
    if rec is not None:
        rec.record(cat, name, clock_ns(), 0, args)


def phase_begin(cat: str, name: str) -> Optional[int]:
    """Open an explicit span: returns the start ns (None when the
    recorder is off — phase_end treats None as a no-op). The matching
    ``phase_end`` MUST run on every code path out of the function;
    wrap the body in try/finally, or graftlint GL020 flags the early
    return/raise that would silently drop the span."""
    rec = RECORDER
    return rec.clock() if rec is not None else None


def phase_end(cat: str, name: str, t0: Optional[int],
              args: Optional[dict] = None) -> None:
    """Close a span opened by ``phase_begin``."""
    rec = RECORDER
    if rec is not None and t0 is not None:
        rec.record(cat, name, t0, clock_ns() - t0, args)


_trace_annotation: Any = None      # jax.profiler.TraceAnnotation, or False


class span:
    """``with span(cat, name, **args):`` puts one interval on both
    clocks an engineer reads: the ``jax.profiler`` trace (as a
    ``TraceAnnotation`` on the calling thread's host line, so a device
    trace's idle gaps can be named by it) and, when the recorder is
    on, this journal under ``cat`` with ``args``. With neither a
    profiler session nor a recorder it costs the annotation's
    construction. jax is looked up on first use; without it the span
    is journal-only."""

    __slots__ = ("_cat", "_name", "_args", "_annotation", "_t0")

    def __init__(self, cat: str, name: str, **args):
        global _trace_annotation
        if _trace_annotation is None:
            try:
                from jax.profiler import TraceAnnotation
                _trace_annotation = TraceAnnotation
            except ImportError:
                _trace_annotation = False
        self._cat, self._name, self._args = cat, name, args
        self._annotation = (_trace_annotation(name, **args)
                            if _trace_annotation else None)
        self._t0: Optional[int] = None

    def note(self, **args) -> None:
        """Journal args known only once the interval is under way (the
        profiler's annotation keeps those it was built with)."""
        self._args.update(args)

    def __enter__(self) -> "span":
        rec = RECORDER
        self._t0 = rec.clock() if rec is not None else None
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc_info) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)
        rec = RECORDER
        if rec is not None and self._t0 is not None:
            rec.record(self._cat, self._name, self._t0,
                       rec.clock() - self._t0, self._args or None)


# --- wall-clock anchoring -----------------------------------------------
# perf_counter_ns has an arbitrary per-process epoch. The driver pins
# one (wall, perf) pair; every aligned journal timestamp is rendered as
# wall_anchor + (t_ns - perf_anchor), putting flight events on the same
# wall-clock microsecond scale the task-event timeline already uses.

_anchor: Optional[Tuple[float, int]] = None


def _get_anchor() -> Tuple[float, int]:
    global _anchor
    if _anchor is None:
        _anchor = (time.time(), clock_ns())
    return _anchor


# --- driver-side collector ----------------------------------------------

class FlightStore:
    """Driver-held journals pushed by worker flushers. Bounded per
    process; survives the process that pushed it — the post-mortem
    source for actor deaths."""

    def __init__(self):
        self.lock = threading.Lock()
        self._procs: Dict[str, dict] = {}

    def push(self, label: str, events: List[tuple],
             offset_ns: int) -> None:
        # Brief and lock-only: this runs in the GCS dispatch path,
        # which may be the head's IO-loop thread.
        with self.lock:
            entry = self._procs.get(label)
            if entry is None:
                entry = {"events": deque(maxlen=STORE_CAPACITY),
                         "offset": 0, "last_seq": -1}
                self._procs[label] = entry
            entry["offset"] = int(offset_ns)
            for ev in events:
                if ev[0] > entry["last_seq"]:
                    entry["events"].append(tuple(ev))
                    entry["last_seq"] = ev[0]

    def journals(self) -> List[Tuple[str, int, List[tuple]]]:
        """(label, offset_ns, events) per pushed process."""
        with self.lock:
            return [(label, entry["offset"], list(entry["events"]))
                    for label, entry in sorted(self._procs.items())]

    def tail(self, label_substr: str,
             n: int = TAIL_EVENTS) -> Optional[List[str]]:
        """Formatted last-n events of the journal whose label contains
        ``label_substr`` — the supervisor's post-mortem lookup."""
        with self.lock:
            for label, entry in self._procs.items():
                if label_substr in label:
                    events = list(entry["events"])[-n:]
                    break
            else:
                return None
        return format_events(events)


_STORE: Optional[FlightStore] = None


def get_store() -> FlightStore:
    global _STORE
    if _STORE is None:
        _STORE = FlightStore()
    return _STORE


def store_push(label: str, events: List[tuple], offset_ns: int) -> None:
    get_store().push(label, events, offset_ns)


# --- process wiring ------------------------------------------------------

def init_driver() -> None:
    """Reset collector state and (when configured) enable the driver's
    own recorder. Called from Runtime.__init__; env flags are mirrored
    so workers forked later inherit the same configuration."""
    global _STORE, _anchor
    from ray_tpu.core.config import get_config
    cfg = get_config()
    _STORE = FlightStore()
    _anchor = None
    stop_flusher()
    if cfg.flight_recorder_enabled:
        os.environ["RTPU_FLIGHT_RECORDER_ENABLED"] = "1"
        os.environ["RTPU_FLIGHT_RECORDER_CAPACITY"] = str(
            cfg.flight_recorder_capacity)
        os.environ["RTPU_FLIGHT_FLUSH_INTERVAL_S"] = str(
            cfg.flight_flush_interval_s)
        enable(label=f"driver:{os.getpid()}",
               capacity=cfg.flight_recorder_capacity)
    else:
        os.environ.pop("RTPU_FLIGHT_RECORDER_ENABLED", None)
        disable()


def init_worker(rt, worker_id) -> None:
    """Enable the recorder and start the flusher thread in a worker
    process (no-op unless the driver enabled recording — the flag rides
    the inherited environment)."""
    from ray_tpu.core.config import get_config
    cfg = get_config()
    if not cfg.flight_recorder_enabled:
        return
    label = f"worker:{worker_id.hex()[:12]}:pid:{os.getpid()}"
    rec = enable(label=label, capacity=cfg.flight_recorder_capacity)
    start_flusher(rt, rec, interval_s=cfg.flight_flush_interval_s)


class _Flusher(threading.Thread):
    """Worker-side daemon: every interval, ping-pong the driver clock
    then push the journal increment. Runs gcs_call from a non-main
    thread — safe: replies are delivered by the worker's main recv
    loop (the same channel metrics forwarding uses)."""

    def __init__(self, rt, recorder: Recorder, interval_s: float):
        super().__init__(name="flight-flush", daemon=True)
        self._rt = rt
        self._recorder = recorder
        self._interval = max(0.02, float(interval_s))
        self._last_seq = -1
        self._stop = threading.Event()

    def flush_once(self) -> None:
        t0 = clock_ns()
        t_driver = self._rt.gcs_call("flight_sync")
        t1 = clock_ns()
        # driver_clock ≈ worker_clock + offset, assuming the symmetric-
        # delay midpoint is when the driver sampled its clock.
        offset = int(t_driver) - (t0 + t1) // 2
        events = self._recorder.snapshot(since_seq=self._last_seq)
        if events:
            self._last_seq = events[-1][0]
        self._rt.gcs_call("flight_push", self._recorder.label, events,
                          offset)

    def run(self) -> None:
        from ray_tpu.util.backoff import Backoff

        # Failed pushes back off with jitter (util/backoff.py) instead
        # of re-hammering a struggling control channel every interval.
        backoff = Backoff(initial_s=self._interval,
                          max_s=8 * self._interval)
        failures = 0
        delay = self._interval
        while not self._stop.wait(delay):
            try:
                self.flush_once()
                failures = 0
                backoff.reset()
                delay = self._interval
            except Exception:  # noqa: BLE001 — slow env setup, or the
                failures += 1  # channel is gone at shutdown
                if failures >= 3:
                    return
                delay = backoff.next_delay()

    def stop(self) -> None:
        self._stop.set()
        try:
            self.flush_once()  # final increment, best effort
        except Exception:  # graftlint: disable=GL004
            pass  # shutdown race: the control channel may be gone


_flusher: Optional[_Flusher] = None


def start_flusher(rt, recorder: Recorder, interval_s: float) -> None:
    global _flusher
    _flusher = _Flusher(rt, recorder, interval_s)
    _flusher.start()


def stop_flusher() -> None:
    global _flusher
    if _flusher is not None:
        _flusher.stop()
        _flusher = None


def flush_now() -> None:
    """Push the local journal increment immediately (worker-side; used
    right before surfacing an error so the driver's copy is current)."""
    if _flusher is not None:
        try:
            _flusher.flush_once()
        except Exception:  # graftlint: disable=GL004
            pass  # observability must never mask the original error


# --- merge + export ------------------------------------------------------

def merged_journals() -> Dict[str, List[tuple]]:
    """label -> clock-aligned events (driver perf_counter_ns domain),
    including the driver's own journal at offset 0."""
    out: Dict[str, List[tuple]] = {}
    store = _STORE
    if store is not None:
        for label, offset, events in store.journals():
            out[label] = [(seq, t0 + offset, dur, cat, name, args)
                          for seq, t0, dur, cat, name, args in events]
    rec = RECORDER
    if rec is not None:
        out[rec.label] = rec.snapshot()
    return out


def _role_for_label(label: str) -> str:
    """Human track name for a journal label: ``driver:4242`` → driver,
    ``worker:ab12cd34ef56:pid:77`` → worker-ab12cd34."""
    if label.startswith("driver"):
        return "driver"
    if label.startswith("worker:"):
        return "worker-" + label.split(":")[1][:8]
    return label.split(":")[0] or label


def chrome_events() -> List[Dict[str, Any]]:
    """Merged journals as Chrome-trace/Perfetto events: one ``pid``
    track per process, one ``tid`` row per category, complete ``X``
    slices for spans and ``i`` instants for point events. Each track
    leads with ``process_name``/``thread_name`` metadata (``ph: M``) so
    Perfetto labels rows by role (driver / worker-N / io-loop) instead
    of bare journal labels."""
    wall_anchor, perf_anchor = _get_anchor()
    out: List[Dict[str, Any]] = []
    for label, events in merged_journals().items():
        pid = f"flight:{label}"
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "tid": 0,
                    "args": {"name": _role_for_label(label),
                             "label": label}})
        for cat in sorted({ev[3] for ev in events}):
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": cat, "args": {"name": cat}})
        for seq, t0, dur, cat, name, args in events:
            ts_us = (wall_anchor + (t0 - perf_anchor) / 1e9) * 1e6
            ev: Dict[str, Any] = {
                "name": name, "cat": f"flight:{cat}", "ts": ts_us,
                "pid": pid, "tid": cat,
                "args": dict(args) if args else {"seq": seq},
            }
            if dur > 0:
                ev["ph"] = "X"
                ev["dur"] = dur / 1e3
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            out.append(ev)
    return out


def dump_journals(filename: Optional[str] = None) -> Dict[str, Any]:
    """Write the merged (clock-aligned) journals as JSON for offline
    analysis — the input format of ``python -m ray_tpu.devtools.whereis``."""
    import json
    payload = {
        "anchor": list(_get_anchor()),
        "journals": {label: [list(ev) for ev in events]
                     for label, events in merged_journals().items()},
    }
    if filename:
        with open(filename, "w") as f:
            json.dump(payload, f)
    return payload


# --- post-mortem ---------------------------------------------------------

def format_events(events: List[tuple]) -> List[str]:
    """Human lines for an error report, newest last, timestamps
    relative to the newest event."""
    if not events:
        return []
    t_end = max(ev[1] + ev[2] for ev in events)
    lines = []
    for seq, t0, dur, cat, name, args in events:
        rel_ms = (t0 - t_end) / 1e6
        line = f"[{rel_ms:+10.3f}ms] {cat}:{name}"
        if dur > 0:
            line += f" dur={dur / 1e6:.3f}ms"
        if args:
            line += f" {args}"
        lines.append(line)
    return lines


def local_tail(n: int = TAIL_EVENTS) -> Optional[List[str]]:
    """Formatted tail of THIS process's journal, or None when the
    recorder is off. Attached to exceptions at raise time (the tuple
    rides the pickled exception's __dict__ back to the driver)."""
    rec = RECORDER
    if rec is None:
        return None
    return format_events(rec.tail(n))


def attach_tail(exc: BaseException, n: int = TAIL_EVENTS) -> None:
    """Stamp the local journal tail onto ``exc`` (picklable: plain
    strings in __dict__) and push the increment to the driver so the
    supervisor's copy includes the final moments."""
    tail = local_tail(n)
    if tail is not None:
        exc._flight_tail = tail  # type: ignore[attr-defined]
    flush_now()


def tail_text(exc_or_lines, limit: int = TAIL_EVENTS) -> str:
    """Render a journal tail (from an exception's ``_flight_tail`` or a
    raw line list) as an indented block for error messages. Empty
    string when there is nothing to show."""
    lines = (getattr(exc_or_lines, "_flight_tail", None)
             if isinstance(exc_or_lines, BaseException) else exc_or_lines)
    if not lines:
        return ""
    lines = lines[-limit:]
    return ("\n  flight recorder (last %d events):\n    " % len(lines)
            + "\n    ".join(lines))


def store_tail_text(label_substr: str, n: int = TAIL_EVENTS) -> str:
    """Post-mortem text from the driver-side collector for a process
    that died (matched by label substring, e.g. a worker id prefix)."""
    store = _STORE
    if store is None:
        return ""
    lines = store.tail(label_substr, n)
    return tail_text(lines) if lines else ""
