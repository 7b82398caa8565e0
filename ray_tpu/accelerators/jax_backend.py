"""What this process's JAX backend is and what it compiled.

The one definition of "on a TPU" for kernel selection and sizing
(``on_tpu``), plus the facts a worker reports so a caller can tell
which device served it: platform, device kind and ids, peak device
memory, seconds spent compiling, and the Pallas kernels a lowered
program holds. Importing this module does not initialize a backend;
every function that does says so.
"""

from __future__ import annotations

import os
import re
import threading
from typing import Any, Dict, List

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_lock = threading.Lock()
_compile_seconds = 0.0
_listening = False

# `stablehlo.custom_call @tpu_custom_call(...) {..., kernel_name = "x",
# ...} : (operand types) -> result types` in lowered StableHLO text
_KERNEL_RE = re.compile(
    r'@tpu_custom_call\(.*?kernel_name = "([^"]+)".*?\}\s*:\s*\(([^)]*)\)')


def on_tpu() -> bool:
    """True iff this process's default JAX backend is a TPU
    (initializes the backend)."""
    import jax
    return jax.default_backend() == "tpu"


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    global _compile_seconds
    if event in _COMPILE_EVENTS:
        with _compile_lock:
            _compile_seconds += duration


def track_compile_time() -> None:
    """Start summing trace + lower + backend-compile seconds for this
    process (idempotent; a persistent-cache hit counts only its
    retrieval, so a warm second run reads lower)."""
    global _listening
    import jax.monitoring
    with _compile_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds() -> float:
    with _compile_lock:
        return _compile_seconds


def device_report() -> Dict[str, Any]:
    """This process's platform, device kind, local device ids, the
    chips the runtime made visible to it (TPU_VISIBLE_CHIPS; None = the
    whole host), peak bytes in use (max over local devices; None where
    the backend keeps no memory stats) and compile seconds so far
    (initializes the backend)."""
    import jax
    devices = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_ids": [d.id for d in devices],
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "pid": os.getpid(),
        "peak_bytes_in_use": max(peaks) if peaks else None,
        "compile_seconds": round(compile_seconds(), 3),
    }


def pallas_kernels(lowered_text: str) -> List[str]:
    """``name(operand types)`` for every Pallas TPU kernel in a lowered
    program's text (``jitted.lower(...).as_text()``), sorted, one entry
    per distinct kernel and operand shapes."""
    return sorted({f"{name}({operands})" for name, operands
                   in _KERNEL_RE.findall(lowered_text)})
