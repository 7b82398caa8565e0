"""What the window's prefills had to compute over what the chip could
have computed in the time their steps took ON THE STEPPER'S CLOCK, for
a family with latent attention: the operations that the REAL tokens of
the window's prompts need (benchmark/ops_kimi.py: two a parameter that
every token meets outside the routed experts and the head, the head a
prompt, causal attention at the true widths; the routed experts left
out, so a lower bound) at the matrix unit's peak, over the SUM of the
steps tagged ``prefill``. Such a step is the admission's prefill and
the decode step behind it, host time and all, so the share holds the
bucket's padding, the lanes the attention kernel is padded to, the
pairs the grouped product walks for nothing and the stall of the live
slots; it cannot pass 100%.

The tokens are ``prefill_tokens_total{kind="real"}``, the prompts the
samples of ``admit_launch_seconds`` (one an admission). None where a
series is absent or did not grow (a program without the family, an
untraced run)."""

from benchmark import harness, ops_kimi
from benchmark.readers.series import delta

_REAL = 'ray_tpu_engine_prefill_tokens_total{kind="real"}'
_ADMITTED = 'ray_tpu_engine_admit_launch_seconds_count{overlapped="%d"}'
_PREFILL_S = 'ray_tpu_engine_step_seconds_sum{phase="prefill"}'


def read(observed, device_kind: str):
    if observed.get("series_after") is None:
        return None
    tokens = delta(observed, _REAL)
    prompts = sum(delta(observed, _ADMITTED % i) for i in (0, 1))
    seconds = delta(observed, _PREFILL_S)
    if tokens <= 0 or prompts <= 0 or seconds <= 0:
        return None
    ops = ops_kimi.prefill_floor_ops(observed["cell"]["config_file"],
                                     tokens, prompts)
    return 100.0 * ops / (seconds * harness.peaks_for(
        device_kind)["bf16_flops_per_s"])
