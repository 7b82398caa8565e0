"""The selective-scan kernel's device time over the device's busy
time in the traced window."""

from benchmark.readers.scan_kernels import seconds_and_least


def read(observed, device_kind: str):
    got = seconds_and_least(observed, device_kind)
    trace = observed.get("trace")
    if got is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * got[0] / trace["busy_s"]
