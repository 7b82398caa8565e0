"""Tokens the engine generated over the steps it took, both phases:
every step emits one token per active slot, so this is the mean batch
occupancy of the window."""

from benchmark.readers.series import delta

_STEPS = 'ray_tpu_engine_step_seconds_count{phase="%s"}'


def read(observed):
    if observed.get("series_after") is None:
        return None
    steps = sum(delta(observed, _STEPS % p) for p in ("prefill", "decode"))
    if steps <= 0:
        return None
    return delta(observed, "ray_tpu_engine_tokens_generated_total") / steps
