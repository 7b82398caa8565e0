"""The flash kernels' device time over the device's busy time."""

from benchmark.readers.flash_kernels import seconds_and_least


def read(observed):
    got = seconds_and_least(observed)
    trace = observed.get("trace")
    if got is None or not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * got[0] / trace["busy_s"]
