"""A device trace of the served engine, for the ``--trace 1`` run.

The replica's process holds the chip, so only it can trace it, and the
program has no hook for that. The traced run therefore deploys this
subclass of the program's LLMServer, which adds one route and changes
nothing else: ``GET /v1/bench/trace?seconds=<s>`` traces the replica's
process for that long with jax.profiler and says where the trace lies.
The runner reduces it (benchmark/trace_reduce.py) after the window, in
its own process: read in the replica, the reduction held the
interpreter for seconds, stalled the engine's loop and made the
controller's health check kill the replica. The untraced run, which
gives the end-to-end metrics, deploys the program's own
build_openai_app.
"""

from __future__ import annotations

import json
import shutil
import tempfile
import threading
import time
import urllib.request
from typing import Any, Dict, Optional

from ray_tpu import serve
from ray_tpu.serve.llm import LLMConfig, LLMServer


class TracedLLMServer(LLMServer):
    def __call__(self, request: Dict[str, Any]) -> Any:
        if request.get("__path__", "").endswith("/bench/trace"):
            return self._trace(float(request.get("seconds", 3.0)))
        return super().__call__(request)

    def _trace(self, seconds: float) -> Dict[str, Any]:
        import jax

        from benchmark import trace_reduce
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        # without the Python tracer: its hook on every call slowed the
        # engine's steps by 7% while it ran, and stop_trace held the
        # interpreter 0.6-0.9 s to collect what it had recorded. The
        # engine's own spans and the runtime's (TraceMe) name the gaps.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return {"logdir": logdir}


def traced_app(config: LLMConfig):
    """What build_llm_deployment binds, with the subclass."""
    return serve.deployment(
        TracedLLMServer, name=config.model_id,
        num_replicas=config.num_replicas,
        max_ongoing_requests=config.max_ongoing_requests,
        ray_actor_options=config.ray_actor_options(),
        request_router="pow2").bind(config, None)


class PendingTrace:
    """Asks the replica for a trace ``after_s`` from now without
    blocking the caller; ``collect`` waits for it and reduces it."""

    def __init__(self, base: str, after_s: float, seconds: float):
        self._url = f"{base}/bench/trace?seconds={seconds}"
        self._after_s = after_s
        self._result: Optional[Dict[str, Any]] = None
        self._error: Optional[str] = None
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self) -> None:
        time.sleep(self._after_s)
        try:
            with urllib.request.urlopen(self._url, timeout=600) as r:
                self._result = json.loads(r.read())
        except Exception as exc:  # noqa: BLE001 - reported by collect()
            self._error = repr(exc)

    def collect(self) -> Dict[str, Any]:
        self._thread.join(900)
        if self._result is None:
            raise RuntimeError(f"no trace came back: {self._error}")
        from benchmark import trace_reduce
        logdir = self._result["logdir"]
        try:
            return trace_reduce.reduce_trace(trace_reduce.find_trace(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
