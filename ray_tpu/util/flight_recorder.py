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
weight_push), ``proc`` (the stall watch's ``process.stall`` and
``thread.held``).

4. **Stall watch** (every process, always on) — one daemon thread,
   ``rtpu-stall-watch``, that says when the process did not run
   (``frozen``: nothing in it ran; ``starved``: some thread ran and the
   watch could not) and when a thread that owns a loop sat in one place
   (``held``; the loop registers a *probe*). See ``StallWatch``.
"""

from __future__ import annotations

import itertools
import logging
import os
import sys
import threading
import time
import traceback
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.util import metrics as _metrics

logger = logging.getLogger(__name__)

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


# --- stall watch ---------------------------------------------------------
# One daemon thread a process. It sleeps a tick and, on waking, says
# two things the spans cannot: that the process did not run (its own
# wake came late), and that a thread which owns a loop sat in one place
# (a probe named the same ``since`` for too long). It takes no lock
# that a hot path takes, and a probe is read, never written.
#
# What held the interpreter DURING a stop only a thread that needs no
# interpreter lock could say, and ``faulthandler.dump_traceback_later``
# is one; it is left out on purpose. Its C thread reads the other
# threads' frames while they run (after a frozen process thaws they all
# do), and on this interpreter (3.12) that is a segmentation fault in
# under a thousand firings against busy threads; on the chip it killed
# the Jamba cell's check worker in 5 runs of 10 (PR 63, PERF.md). A
# ``starved`` episode carries instead where every thread stands right
# after it, taken under the lock like any stack here.

STALL_TICK_S = 0.020        # the watch's sleep
STALL_LATE_S = 0.100        # a wake this late is an episode
STALL_HELD_S = 0.250        # a probe naming one ``since`` this long is held
STALL_AFTER_S = 1.0         # a starved one this long: where threads stand
STALL_RING = 32             # episodes kept in the process
STALL_STACK_FRAMES = 12     # frames of a stack in stats() and the journal
_TICK_NS, _LATE_NS = int(STALL_TICK_S * 1e9), int(STALL_LATE_S * 1e9)

PROCESS_STALL_SECONDS = _metrics.Counter(
    "ray_tpu_process_stall_seconds_total",
    "Seconds in which the process did not run: its stall watch woke "
    "that much later than its tick, by the process's role",
    tag_keys=("process",))
PROCESS_STALLS = _metrics.Counter(
    "ray_tpu_process_stalls_total",
    "Episodes in which the process did not run, by role and kind: "
    "frozen (its CPU clock stood still: the machine, the sandbox, a "
    "stop signal) or starved (some thread ran and the watch could not: "
    "the interpreter lock held through one long call, or no core)",
    tag_keys=("process", "kind"))
THREAD_HELD_SECONDS = _metrics.Counter(
    "ray_tpu_thread_held_seconds_total",
    "Seconds a thread that owns a loop sat in one place past the "
    "watch's threshold while the process ran, by role and thread",
    tag_keys=("process", "thread"))
THREAD_HELD = _metrics.Counter(
    "ray_tpu_thread_held_total",
    "Episodes of a thread held in one place, by role, thread and the "
    "phase it sat in",
    tag_keys=("process", "thread", "phase"))


class _Probe:
    """A loop's word to the watch: ``read()`` gives ``(what, since)``
    while the loop's thread is in some place since ``since`` (any value
    that changes when the place does) and None while it idles;
    ``ident()`` gives that thread's ident."""

    __slots__ = ("thread", "read", "ident", "threshold_ns", "on_held",
                 "since", "seen_ns", "episode", "skip")

    def __init__(self, thread: str, read: Callable[[], Optional[tuple]],
                 ident: Callable[[], Optional[int]], threshold_s: float,
                 on_held: Optional[Callable[[dict], None]]):
        self.thread, self.read, self.ident = thread, read, ident
        self.threshold_ns = int(threshold_s * 1e9)
        self.on_held = on_held
        self.since: Any = None
        self.seen_ns = 0            # the watch's clock when it first saw it
        self.episode: Optional[dict] = None
        self.skip = False           # this ``since`` has no thread to show


class StallWatch:
    """The process's stall watch. ``tick()`` is one turn of its thread:
    sleep, then look. The clocks and the sleep can be given, so that a
    test decides what passed.

    An episode is one dict: ``name`` (``process.stall`` / ``thread.held``),
    ``kind`` (``frozen`` / ``starved`` / ``held``), ``process`` (the
    role), ``pid``, ``t0_ns`` (the journal's clock), ``epoch_s``
    (``time.time()`` at its start), ``seconds``; a process episode adds
    ``cpu_s`` (the process's CPU seconds over the late sleep),
    ``cpu_usual_s`` (what a tick on time had taken just before) and,
    where it is ``starved`` for a second or more, ``after`` (thread name
    -> its three innermost frames at the wake: the one that held the
    interpreter has just come out of its call); a held one ``thread``,
    ``phase``, ``stack`` (the frames of the thread when the threshold
    passed, outermost first) and ``open`` while it lasts."""

    def __init__(self, role: str = "driver",
                 clock: Callable[[], int] = clock_ns,
                 cpu_clock: Callable[[], float] = time.process_time,
                 wall: Callable[[], float] = time.time,
                 sleep: Callable[[float], Any] = time.sleep):
        self.role = role
        self.pid = os.getpid()
        self._clock, self._cpu_clock = clock, cpu_clock
        self._wall, self._sleep = wall, sleep
        # replaced whole by add/remove, so a tick walks it with no lock
        self._probes: Tuple[_Probe, ...] = ()
        self._probes_lock = threading.Lock()    # its writers' alone
        self._ring: deque = deque(maxlen=STALL_RING)
        self._counts = _metrics.LocalBuffer()
        self._unsent = False    # counts wait for ship() or a carrier
        self._closed = False    # this tick closed an episode
        self._cpu = cpu_clock()
        self._cpu_usual = 0.0
        self._thread: Optional[threading.Thread] = None

    # -- the thread ------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self.pid = os.getpid()
        self._cpu = self._cpu_clock()
        self._thread = threading.Thread(
            target=self._run, name="rtpu-stall-watch", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while True:     # for the process's life: a daemon thread
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — the watch must outlive
                logger.exception("stall watch: tick failed")  # its faults

    def tick(self) -> None:
        t_sleep = self._clock()
        self._sleep(STALL_TICK_S)
        now = self._clock()
        cpu = self._cpu_clock()
        grown, self._cpu = cpu - self._cpu, cpu
        late_ns = now - t_sleep - _TICK_NS
        if late_ns >= _LATE_NS:
            self._process_stall(now - late_ns, late_ns, grown)
            for probe in self._probes:
                # the time the process did not run is no thread's stay
                probe.seen_ns += late_ns
        else:
            self._cpu_usual = grown
        for probe in self._probes:
            self._look(probe, now)
        if self._unsent and not stall_carriers:
            self.ship()
        if self._closed:
            # what closing took (a log line, the ship) is no tick's
            self._closed = False
            self._cpu = self._cpu_clock()

    # -- the process did not run -----------------------------------------

    def _process_stall(self, t0_ns: int, late_ns: int, cpu_s: float) -> None:
        seconds = late_ns / 1e9
        # what the process's threads took beyond a usual tick's share
        ran = cpu_s - self._cpu_usual
        episode = self._episode(
            "process.stall", "frozen" if ran < seconds / 10 else "starved",
            t0_ns, seconds)
        episode.update(cpu_s=cpu_s, cpu_usual_s=self._cpu_usual)
        if episode["kind"] == "starved" and seconds >= STALL_AFTER_S:
            episode["after"] = _where_threads_stand()
        # the watch's thread alone appends
        self._ring.append(episode)  # graftlint: disable=GL001
        self._counts.inc(PROCESS_STALL_SECONDS, seconds,
                         {"process": self.role})
        self._counts.inc(PROCESS_STALLS, 1.0,
                         {"process": self.role, "kind": episode["kind"]})
        self._close(episode)
        logger.warning(
            "stall watch: %s pid %d did not run for %.3f s from %.3f "
            "(%s: its threads took %.3f CPU s, %.3f in a usual tick)%s",
            self.role, self.pid, seconds, episode["epoch_s"],
            episode["kind"], cpu_s, self._cpu_usual,
            "".join(f"\n  {name}: {where}" for name, where
                    in episode.get("after", {}).items()))

    # -- a thread sat in one place ---------------------------------------

    def add_probe(self, thread: str, read, ident,
                  threshold_s: float = STALL_HELD_S,
                  on_held=None) -> _Probe:
        probe = _Probe(thread, read, ident, threshold_s, on_held)
        with self._probes_lock:
            self._probes += (probe,)
        return probe

    def remove_probe(self, probe: _Probe) -> None:
        with self._probes_lock:
            self._probes = tuple(p for p in self._probes
                                 if p is not probe)

    def _look(self, probe: _Probe, now: int) -> None:
        try:
            read = probe.read()
        except Exception:  # noqa: BLE001 — a probe's fault is not the
            read = None    # watch's: it reads as idle
        what, since = read if read is not None else (None, None)
        if since is None or since != probe.since:
            if probe.episode is not None:
                self._close_held(probe, now)
            probe.since, probe.seen_ns, probe.skip = since, now, False
            return
        stayed = now - probe.seen_ns
        if probe.episode is not None:
            probe.episode["seconds"] = stayed / 1e9
        elif stayed >= probe.threshold_ns and not probe.skip:
            self._open_held(probe, what, stayed)

    def _open_held(self, probe: _Probe, what: str, stayed: int) -> None:
        ident = probe.ident()
        frame = sys._current_frames().get(ident)
        if frame is None:
            probe.skip = True   # its thread is gone: nobody is held
            return
        episode = self._episode("thread.held", "held", probe.seen_ns,
                                stayed / 1e9)
        episode.update(thread=probe.thread, phase=what,
                       stack=traceback.format_stack(frame), open=True)
        del frame
        probe.episode = episode
        # the watch's thread alone appends
        self._ring.append(episode)  # graftlint: disable=GL001
        if probe.on_held is not None:
            try:
                probe.on_held(episode)
            except Exception:  # noqa: BLE001
                logger.exception("stall watch: on_held of %s failed",
                                 probe.thread)

    def _close_held(self, probe: _Probe, now: int) -> None:
        episode, probe.episode = probe.episode, None
        seconds = episode["seconds"] = (now - probe.seen_ns) / 1e9
        del episode["open"]
        self._counts.inc(THREAD_HELD_SECONDS, seconds,
                         {"process": self.role, "thread": probe.thread})
        self._counts.inc(THREAD_HELD, 1.0,
                         {"process": self.role, "thread": probe.thread,
                          "phase": episode["phase"]})
        self._close(episode)
        logger.warning(
            "stall watch: %s pid %d thread %s sat in %s for %.3f s from "
            "%.3f while the process ran; its stack when %.3f s had "
            "passed:\n%s", self.role, self.pid, probe.thread,
            episode["phase"], seconds, episode["epoch_s"],
            probe.threshold_ns / 1e9,
            "".join(episode["stack"][-STALL_STACK_FRAMES:]))

    # -- an episode's way out --------------------------------------------

    def _episode(self, name: str, kind: str, t0_ns: int,
                 seconds: float) -> dict:
        return {"name": name, "kind": kind, "process": self.role,
                "pid": self.pid, "t0_ns": t0_ns,
                "epoch_s": self._wall() - (self._clock() - t0_ns) / 1e9,
                "seconds": seconds}

    def _close(self, episode: dict) -> None:
        """A finished episode into the journal; its counts wait for
        ``ship`` or for a carrier's flush."""
        self._unsent = self._closed = True
        rec = RECORDER
        if rec is not None:
            rec.record("proc", episode["name"], episode["t0_ns"],
                       int(episode["seconds"] * 1e9),
                       _journal_args(episode))

    def ship(self) -> None:
        """The counts of the episodes since the last ship as ONE
        ``record_batch`` (from a worker one control-plane call, which
        may take the channel's timeout where the channel is not up)."""
        self._unsent = False
        try:
            self._counts.flush()
        except Exception:  # graftlint: disable=GL004
            pass  # observability is best-effort

    def take_counts(self) -> List[tuple]:
        """The unsent counts as ``record_batch`` items, for a carrier
        that ships them with its own."""
        self._unsent = False
        return self._counts.drain()

    def stalls(self, frames: Optional[int] = None) -> List[dict]:
        out = []
        for episode in list(self._ring):
            episode = dict(episode)
            if frames is not None and "stack" in episode:
                episode["stack"] = episode["stack"][-frames:]
            out.append(episode)
        return out


def _where_threads_stand() -> Dict[str, str]:
    """Thread name -> its three innermost frames, innermost first; the
    caller's own thread left out."""
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    out = {}
    for ident, frame in sys._current_frames().items():
        if ident == me:
            continue
        frames = []
        while frame is not None and len(frames) < 3:
            code = frame.f_code
            frames.append(
                f"{code.co_filename}:{frame.f_lineno} {code.co_name}")
            frame = frame.f_back
        out[names.get(ident, str(ident))] = " <- ".join(frames)
    return out


def _journal_args(episode: dict) -> dict:
    args = {k: v for k, v in episode.items()
            if k not in ("name", "t0_ns", "stack")}
    if "stack" in episode:
        args["stack"] = "".join(episode["stack"][-STALL_STACK_FRAMES:])
    return args


# Buffers that ship the watch's counts with their own flush (the
# engine's _MetricsBuffer: /v1/stats' flush then carries them, and the
# watch's thread of a replica makes no control-plane call). While there
# is none the watch ships an episode's counts itself.
stall_carriers: "weakref.WeakSet" = weakref.WeakSet()

_WATCH: Optional[StallWatch] = None
_watch_lock = threading.Lock()


def stall_watch() -> StallWatch:
    """The process's one watch; made on first use, started by
    ``start_stall_watch``."""
    global _WATCH
    watch = _WATCH
    if watch is None:
        with _watch_lock:
            if _WATCH is None:
                _WATCH = StallWatch()
            watch = _WATCH
    return watch


def start_stall_watch(role: Optional[str] = None) -> StallWatch:
    """Start the process's watch (once: a second call names the role
    anew). ``role``: ``driver`` (what a process is until it says
    otherwise), ``node``, ``worker``."""
    watch = stall_watch()
    if role is not None:
        watch.role = role
    watch.start()
    return watch


def rename_worker(role: str) -> None:
    """A worker has become a ``replica`` or a ``train_worker``: its
    later episodes say so. Any other process keeps its role (a replica
    in serve's local mode lives in the driver)."""
    watch = stall_watch()
    if watch.role == "worker":
        watch.role = role


def add_probe(thread: str, read, ident, threshold_s: float = STALL_HELD_S,
              on_held=None) -> _Probe:
    return stall_watch().add_probe(thread, read, ident, threshold_s,
                                   on_held)


def remove_probe(probe: _Probe) -> None:
    stall_watch().remove_probe(probe)


def stalls(frames: Optional[int] = None) -> List[dict]:
    """The process's last ``STALL_RING`` episodes, oldest first; a held
    thread's is there from the moment the threshold passed (``open``)."""
    return stall_watch().stalls(frames)


def take_stall_counts() -> List[tuple]:
    """What a carrier's flush takes with it: nothing, at the cost of
    two loads, while no episode closed since the last."""
    watch = _WATCH
    return watch.take_counts() if watch is not None and watch._unsent \
        else []
