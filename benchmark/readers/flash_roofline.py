"""Least time of the flash kernels' calls (operations and bytes from
benchmark/ops.py at the peaks) over their device time in the trace."""

from benchmark.readers.flash_kernels import seconds_and_least


def read(observed):
    got = seconds_and_least(observed)
    if got is None or got[0] <= 0:
        return None
    return 100.0 * got[1] / got[0]
