"""The selective-scan kernel's calls in a served cell's trace: one
``selective_scan_<L>`` event per Mamba layer of each prefill, L the
prompt's bucket. How much of a bucket was the prompt's own the trace
does not say; the engine counts real and padded prefill positions
(``ray_tpu_engine_prefill_tokens_total{kind}``), and the calls' floor is
owed for the real share of their positions: every run offers the same
set of prompt lengths, so the window's share is the traced calls' too.
The kernel works in chunks of 64 positions, so it does a little more
than the floor is owed for: the share reads low by that, never high."""

import re

from benchmark import harness, ops_jamba
from benchmark.readers.series import delta

_REAL = 'ray_tpu_engine_prefill_tokens_total{kind="real"}'
_PAD = 'ray_tpu_engine_prefill_tokens_total{kind="pad"}'

_CALL = re.compile(r"selective_scan_(\d+)")


def seconds_and_least(observed, device_kind: str):
    """(device seconds of the scan kernel's calls in the traced window,
    the least the chip could take for those calls by their bytes), or
    None where the trace holds no such call: a program without the
    kernel, a rehearsal on the CPU."""
    trace = observed.get("trace")
    if not trace:
        return None
    config = observed["cell"]["config_file"]
    peaks = harness.peaks_for(device_kind)
    real_share = 1.0
    if observed.get("series_after") is not None:
        real, pad = delta(observed, _REAL), delta(observed, _PAD)
        if real > 0:
            real_share = real / (real + pad)
    spent = least = 0.0
    for name, op in trace["ops"].items():
        found = _CALL.search(name)
        if not found:
            continue
        spent += op["seconds"]
        least += op["count"] * ops_jamba.memory_seconds(
            ops_jamba.scan_call(config, real_share * int(found.group(1))),
            peaks)
    return (spent, least) if spent > 0 else None
