"""A percentile (nearest rank) of the load generator's own samples of
the window, in milliseconds: ``field`` is "ttft_s" (from the due time
to the first streamed token) or "gaps_s" (between streamed tokens)."""

from benchmark import harness


def read(observed, field: str, q: float):
    samples = (observed.get("client") or {}).get(field)
    if not samples:
        return None
    return harness.percentile(samples, q) * 1e3
