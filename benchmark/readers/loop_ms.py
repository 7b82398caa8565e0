"""A statistic of the train loop's own clock, in milliseconds:
``field`` is "waits" (around next(batches)) or "steps" (around the
compiled step ending in block_until_ready); ``stat`` mean or median."""

from benchmark import harness


def read(observed, field: str, stat: str):
    values = (observed.get("loop") or {}).get(field)
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values) * 1e3
    return harness.percentile(values, 0.5) * 1e3
