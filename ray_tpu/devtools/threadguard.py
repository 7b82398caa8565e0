"""threadguard: opt-in runtime thread-affinity enforcement for the
IO-loop core.

The static half of the contract lives in graftlint's GL009-GL012
(``ray_tpu/devtools/lint/rules/threadguard.py``); this module is the
runtime half, in the locktrace mold:

* ``@loop_only`` — asserts at call time that the method runs on its
  owning IO loop's thread, with a diagnostic naming the expected and
  actual threads. With ``RAY_TPU_THREADGUARD`` unset the decorator
  returns the function *unchanged* — zero overhead, plain functions.
* ``@loop_owned("attr", ...)`` — class decorator declaring which
  attributes are loop-thread-only. Purely declarative: it feeds the
  static GL011 rule and documentation; no runtime wrapping.
* ``LoopStallWatchdog`` — a dispatch that exceeds
  ``RAY_TPU_THREADGUARD_STALL_S`` (default 1.0s) is reported with the
  loop thread's stack, so GL009 escapes get caught live. It is a probe
  of the process's stall watch (``util/flight_recorder.py``), which is
  the one thread that polls and the one place that samples a stack.
  Wired up by ``IOLoop`` itself when threadguard is enabled; it only
  logs and records, never raises.

Enable with::

    RAY_TPU_THREADGUARD=1 python my_driver.py
    RAY_TPU_THREADGUARD=1 RAY_TPU_THREADGUARD_STALL_S=0.25 pytest ...

Like everything in devtools, importing this module must stay cheap:
no jax, no runtime imports (the flight recorder is stdlib and metrics).
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from typing import Callable, List, Optional

from ray_tpu.util import flight_recorder

logger = logging.getLogger(__name__)

_ENV_FLAG = "RAY_TPU_THREADGUARD"
_STALL_ENV = "RAY_TPU_THREADGUARD_STALL_S"
_STALL_DEFAULT_S = 1.0

_reports: List[dict] = []
_reports_lock = threading.Lock()


def enabled() -> bool:
    return os.environ.get(_ENV_FLAG, "").strip().lower() in (
        "1", "true", "yes", "on")


def stall_default_s() -> float:
    try:
        return float(os.environ.get(_STALL_ENV, _STALL_DEFAULT_S))
    except ValueError:
        return _STALL_DEFAULT_S


class LoopAffinityError(AssertionError):
    """A @loop_only method was called off its owning loop's thread."""


def _resolve_loop(obj, loop_attr: Optional[str]):
    """Find the owning IOLoop (duck-typed: has on_loop_thread) on
    ``obj``: an explicit dotted ``loop_attr`` path, ``obj`` itself,
    or a conventional attribute (_loop/_io/loop/io). Returns None when
    unresolvable — the guard then passes through rather than guessing."""
    if loop_attr:
        target = obj
        for part in loop_attr.split("."):
            target = getattr(target, part, None)
            if target is None:
                return None
        if callable(getattr(target, "on_loop_thread", None)):
            return target
        return None
    if callable(getattr(obj, "on_loop_thread", None)):
        return obj
    for name in ("_loop", "_io", "loop", "io"):
        cand = getattr(obj, name, None)
        if cand is not None and \
                callable(getattr(cand, "on_loop_thread", None)):
            return cand
    return None


def loop_only(fn: Optional[Callable] = None, *,
              loop_attr: Optional[str] = None):
    """Mark a method as loop-thread-only.

    Always sets ``_tg_loop_only`` (consumed by the static GL009-GL011
    seeding); when ``RAY_TPU_THREADGUARD`` is enabled at decoration
    time, also wraps the method to raise ``LoopAffinityError`` when
    called from any other thread. ``loop_attr`` is a dotted attribute
    path to the owning loop for classes that don't follow the
    _loop/_io convention (e.g. ``loop_attr="conn._loop"``)."""

    def deco(f: Callable) -> Callable:
        f._tg_loop_only = True
        if not enabled():
            return f

        @functools.wraps(f)
        def wrapper(self, *args, **kwargs):
            loop = _resolve_loop(self, loop_attr)
            if loop is not None and not loop.on_loop_thread():
                expected = getattr(loop, "_thread", None)
                raise LoopAffinityError(
                    f"threadguard: {type(self).__name__}."
                    f"{f.__name__}() is @loop_only but was called on "
                    f"thread {threading.current_thread().name!r} "
                    f"(ident={threading.get_ident()}); owning loop "
                    f"thread is "
                    f"{getattr(expected, 'name', '<unknown>')!r} "
                    f"(ident={getattr(expected, 'ident', '?')}). "
                    "Route the call through call_soon/call_later.")
            return f(self, *args, **kwargs)

        wrapper._tg_loop_only = True
        return wrapper

    if fn is not None:
        return deco(fn)
    return deco


def loop_owned(*names: str):
    """Class decorator declaring loop-thread-only attributes. Static
    marker for graftlint GL011 (and for readers); merges with any
    declaration on base classes. No runtime wrapping — enforcement of
    attribute affinity is static-only."""

    def deco(cls):
        inherited = set()
        for base in cls.__mro__[1:]:
            inherited |= set(getattr(base, "_tg_loop_owned", ()))
        cls._tg_loop_owned = frozenset(inherited | set(names))
        return cls

    return deco


class LoopStallWatchdog:
    """Reports a loop thread's stack when one dispatch runs too long.

    The loop publishes busy-ness via ``enter()``/``exit_busy()`` around
    each batch of work (callbacks, handlers, timers). That busy window
    is a probe of the process's stall watch: when one has lasted
    ``stall_s`` the watch hands over the loop thread's stack and a
    report is appended (one per stall episode). It never raises into
    the loop."""

    def __init__(self, thread: threading.Thread,
                 stall_s: Optional[float] = None):
        self._thread = thread
        self._stall_s = stall_s if stall_s is not None \
            else stall_default_s()
        self._busy_since: Optional[float] = None
        self._probe = flight_recorder.add_probe(
            "io_loop", self._busy, lambda: thread.ident,
            threshold_s=self._stall_s, on_held=self._report)
        flight_recorder.start_stall_watch()

    # called from the loop thread only
    def enter(self) -> None:
        self._busy_since = time.monotonic()

    def exit_busy(self) -> None:
        self._busy_since = None

    def stop(self) -> None:
        flight_recorder.remove_probe(self._probe)

    # called from the stall watch's thread only
    def _busy(self) -> Optional[tuple]:
        t0 = self._busy_since
        return None if t0 is None else ("dispatch", t0)

    def _report(self, episode: dict) -> None:
        stack = "".join(episode["stack"])
        report = {
            "thread": self._thread.name,
            "ident": self._thread.ident,
            "stalled_s": episode["seconds"],
            "stack": stack,
        }
        with _reports_lock:
            _reports.append(report)
        logger.warning(
            "threadguard: IO loop thread %r busy for %.3fs "
            "(> %.3fs stall threshold); current stack:\n%s",
            self._thread.name, episode["seconds"], self._stall_s, stack)


def stall_reports() -> List[dict]:
    """Snapshot of watchdog stall reports recorded so far."""
    with _reports_lock:
        return list(_reports)


def reset() -> None:
    """Clear recorded stall reports (test helper)."""
    with _reports_lock:
        del _reports[:]
