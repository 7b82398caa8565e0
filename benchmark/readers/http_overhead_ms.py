"""Mean time to first token at the client (from the due time) less
the engine's own mean (from admission to the first token): what
proxy, router, replica and the wait for a send add."""

from benchmark.readers.series import hist_mean


def read(observed):
    client = (observed.get("client") or {}).get("ttft_s")
    if not client or observed.get("series_after") is None:
        return None
    engine = hist_mean(observed, "ray_tpu_engine_ttft_seconds")
    if engine is None:
        return None
    return (sum(client) / len(client) - engine) * 1e3
