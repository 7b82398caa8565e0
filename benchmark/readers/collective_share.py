"""Device time of collective operations (all-gather, reduce-scatter,
all-reduce, by the names the trace prints) on one chip over the traced
window."""


def read(observed):
    trace = observed.get("trace")
    if not trace or trace.get("window_s", 0) <= 0:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
