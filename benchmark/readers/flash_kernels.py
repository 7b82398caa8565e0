"""The three flash-attention kernels in a training cell's trace."""

from benchmark import ops, trace_reduce

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def seconds_and_least(observed):
    """(device seconds of the kernels, the least the chip could take
    for the calls the trace shows, what bounds them), per chip."""
    trace, peaks = observed.get("trace"), observed.get("peaks")
    if not trace or not peaks:
        return None
    config = observed["cell"]["config_file"]
    sizes = config["training"]
    shard_batch = sizes["batch"] // sizes["fsdp"]
    spent = least = 0.0
    bounds = set()
    for kernel in KERNELS:
        seconds, calls = trace_reduce.op_seconds(trace, kernel)
        if calls <= 0:
            return None
        call = ops.flash_call(kernel, shard_batch,
                              config["num_attention_heads"], sizes["seq"],
                              ops.head_dim(config))
        floor = ops.least_seconds(call, peaks)
        spent += seconds
        least += calls * floor["seconds"]
        bounds.add(floor["bound"])
    return spent, least, sorted(bounds)
