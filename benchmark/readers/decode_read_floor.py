"""The least a decode step of the window could take over what one took
ON THE STEPPER'S CLOCK: the bytes ANY implementation has to read a step
(benchmark/ops_lfm2.py: mixers, dense layers, norms, routers and head
once; the experts that a live row HIT, from the engine's
``expert_slots_total``; the K/V rows read, from
``decode_kv_rows_total``), at the memory's peak, over the mean of the
steps tagged ``decode``. That mean is a step's WALL time: the device's
time and the host's beside it (the read-back, the gaps between two
launches), so this is an Engine metric, which a host-side change moves
as a kernel's does, and it cannot pass 100% while the program reads
what it must. The decode program's own device time is not to be had
from a reduced trace (PERF.md 7-26b).

Both counters cover every dense decode step, the ones that also admit a
prompt among them, and each says how many steps it covers (hit + idle is
routed layers x experts a step, read + skipped is slots x max_seq), so
the means a step need no step count. None where a series is absent or
did not grow (a program without the family, an untraced run)."""

from benchmark import harness, ops_lfm2
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
    layers = ops_lfm2.layer_counts(config)["moe"]
    steps_experts = (hit + idle) / (layers * config["num_experts"])
    steps_rows = (rows + skipped) / (sizes["max_batch"] * sizes["max_seq"])
    floor = ops_lfm2.decode_floor_bytes(
        config, hit / steps_experts, rows / steps_rows)
    least_s = sum(floor.values()) / harness.peaks_for(
        device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
