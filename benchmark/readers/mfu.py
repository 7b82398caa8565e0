"""Model FLOP/s utilization of a training cell: the tokens of a step
over the median step time of the loop's clock, times the operations a
token needs (benchmark/ops.py; recomputation not counted), over chips
times the peak (benchmark/peaks.json). From the median step and not
from the window's rate, which in a traced run also holds the seconds
the profiler takes to stop."""

from benchmark import harness, ops


def read(observed):
    peaks, loop = observed.get("peaks"), observed.get("loop")
    if not peaks or not loop or not loop.get("steps"):
        return None
    config = observed["cell"]["config_file"]
    sizes = config["training"]
    tokens_a_second = (sizes["batch"] * sizes["seq"]
                       / harness.percentile(loop["steps"], 0.5))
    per_token = ops.train_flops_per_token(config, sizes["seq"])
    return (100.0 * tokens_a_second * per_token
            / (observed["chips"] * peaks["bf16_flops_per_s"]))
