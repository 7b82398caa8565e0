"""The least a decode step of the window could take over what one took
ON THE STEPPER'S CLOCK, for a family whose cache is rows of a latent:
the bytes ANY implementation has to read a step (benchmark/ops_kimi.py:
attention, shared experts, routers, dense layers, norms and head once;
the held experts that a live row HIT, from the engine's
``expert_slots_total``; the latent rows read, from
``decode_kv_rows_total``: the blocks the kernel covers, a little over
what a row-exact kernel needs and never over what this program reads,
each at the published row's bytes, once), at the memory's peak, over
the mean of the steps tagged ``decode``. That mean is a step's WALL
time, so this is an Engine metric, and it cannot pass 100% while the
program reads what it must: a program that skips idle experts or reads
a latent row once moves it.

Both counters cover every dense decode step and each says how many
steps it covers (hit + idle is routed layers x held experts a step,
read + skipped is slots x max_seq), so the means a step need no step
count. None where a series is absent or did not grow (a program
without the family, an untraced run)."""

from benchmark import harness, ops_kimi
from benchmark.readers.series import delta, hist_mean

_HIT = 'ray_tpu_engine_expert_slots_total{state="hit"}'
_IDLE = 'ray_tpu_engine_expert_slots_total{state="idle"}'
_READ = 'ray_tpu_engine_decode_kv_rows_total{kind="read"}'
_SKIPPED = 'ray_tpu_engine_decode_kv_rows_total{kind="skipped"}'


def read(observed, device_kind: str):
    if observed.get("series_after") is None:
        return None
    hit, idle = delta(observed, _HIT), delta(observed, _IDLE)
    rows, skipped = delta(observed, _READ), delta(observed, _SKIPPED)
    step_s = hist_mean(observed, "ray_tpu_engine_step_seconds",
                       '{phase="decode"}')
    if hit + idle <= 0 or rows + skipped <= 0 or not step_s:
        return None
    config = observed["cell"]["config_file"]
    sizes = config["serving"]
    layers = ops_kimi.layer_counts(config)["moe"]
    steps_experts = (hit + idle) / (layers * config["n_routed_experts"])
    steps_rows = (rows + skipped) / (sizes["max_batch"] * sizes["max_seq"])
    floor = ops_kimi.decode_floor_bytes(
        config, hit / steps_experts, rows / steps_rows)
    least_s = sum(floor.values()) / harness.peaks_for(
        device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
