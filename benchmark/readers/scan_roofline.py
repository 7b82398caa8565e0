"""Least time of the selective-scan kernel's calls (the bytes the
algorithm needs for the prompts' own positions, from
benchmark/ops_jamba.py and the engine's count of real and padded
prefill positions, at the memory's peak) over their device time in the
trace. The kernel is bound by the vector unit, for which peaks.json has
no peak, so the floor is the memory's: the share reads how far the
kernel is from memory-bound, and cannot pass 100% while the kernel
reads its inputs and writes its outputs."""

from benchmark.readers.scan_kernels import seconds_and_least


def read(observed, device_kind: str):
    got = seconds_and_least(observed, device_kind)
    return None if got is None else 100.0 * got[1] / got[0]
