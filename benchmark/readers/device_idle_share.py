"""1 less the union of device-operation intervals over the traced
window, from the profiler's trace."""


def read(observed):
    trace = observed.get("trace")
    if not trace or trace.get("window_s", 0) <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
