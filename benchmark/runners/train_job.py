"""Runner of the traffic kind ``train_job``: JaxTrainer -> one worker
that owns the cell's chips -> the jitted adamw step on the loss of the
configuration's program module (benchmark/programs/) over an fsdp
mesh, fed by ray_tpu.data.

This process starts the runtime and the trainer and never initializes
a JAX backend; ``_loop`` runs in the worker, which holds the chips,
and everything that needs the device (the reference check, the clock
around the steps, the profiler trace and its reduction) happens there.

The step programs are chip_smoke.train_programs' (copied: a later PR
may change that file), with the optimizer and sizes of the cell's
configuration.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict

from benchmark import harness, reference_check, traffic


def _programs(family, mesh, opt):
    """``family`` is a program module's training(). -> init(key) ->
    (params, opt_state), the shardings of that state under the
    family's rules, the loss, and the adamw step on it."""
    import jax
    import optax

    from ray_tpu.parallel.sharding import infer_sharding

    def init(key):
        params = family["init"](key)
        return params, opt.init(params)

    shardings = infer_sharding(
        jax.eval_shape(init, jax.random.PRNGKey(0)), mesh,
        family["sharding_rules"])

    def loss_fn(params, tokens, targets):
        return family["loss"](params, tokens, targets, mesh)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return init, shardings, loss_fn, train_step


def _loop(config: Dict[str, Any]) -> None:
    """The worker's side. Reports one summary through train.report."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import ray_tpu.train as train
    from benchmark import reference_check, trace_reduce
    from ray_tpu.accelerators import jax_backend
    from ray_tpu.ops import attention as att
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    jax_backend.track_compile_time()
    sizes, seconds = config["sizes"], config["seconds"]
    family = harness.program_for(config["program"]).training(
        config["config_file"], sizes, config["rehearse"])
    mesh = make_mesh(MeshSpec(fsdp=sizes["fsdp"]))
    init, shardings, loss_fn, train_step = _programs(
        family, mesh, optax.adamw(sizes["learning_rate"]))
    # under jit straight into the target sharding, in the served type
    params, opt_state = jax.jit(init, out_shardings=shardings)(
        jax.random.PRNGKey(config["seed"]))
    batches = train.get_dataset_shard("train").iter_device_batches(
        batch_size=sizes["batch"], dtypes=jnp.int32,
        sharding=NamedSharding(mesh, P(("data", "fsdp"))))
    first = next(batches)
    # the program's loss against the plain reference, same weights
    check = reference_check.check_training(
        params, first["tokens"], first["targets"], family["model"],
        config["config_file"]["reference"], jax.jit(loss_fn))
    lowered = jax.jit(train_step, donate_argnums=(0, 1)).lower(
        params, opt_state, first["tokens"], first["targets"])
    kernels = jax_backend.pallas_kernels(lowered.as_text())
    step = lowered.compile()
    memory = step.memory_analysis()
    losses = []
    for b in (first, next(batches)):       # warm-up: two whole steps
        params, opt_state, loss = step(params, opt_state, b["tokens"],
                                       b["targets"])
        losses.append(float(loss))
    compile_s0 = jax_backend.compile_seconds()
    trace_at, trace_steps = config["trace_at_step"], config["trace_steps"]
    logdir = tempfile.mkdtemp(prefix="bench_trace_") \
        if config["trace"] else None
    window_span = None
    waits, steps = [], []
    exhausted = False

    def end_trace():
        nonlocal window_span
        window_span.__exit__(None, None, None)
        window_span = None
        jax.profiler.stop_trace()

    w0 = time.monotonic()
    setup_s = w0 - config["t_start"]
    while True:
        i = len(steps)
        if logdir and i == trace_at:
            jax.profiler.start_trace(logdir)
            window_span = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            window_span.__enter__()
        t_a = time.monotonic()
        b = next(batches, None)
        t_b = time.monotonic()
        if b is None:       # the job's max_steps ran out: not a result
            exhausted = True
            if window_span is not None:
                end_trace()
            break
        params, opt_state, loss = step(params, opt_state, b["tokens"],
                                       b["targets"])
        loss.block_until_ready()
        t_c = time.monotonic()
        waits.append(t_b - t_a)
        steps.append(t_c - t_b)
        losses.append(float(loss))
        if window_span is not None and i == trace_at + trace_steps - 1:
            end_trace()
        # the window closes with the step that passes its length:
        # all of the work over all of the time
        if t_c - w0 >= seconds and window_span is None:
            break
    window_s = time.monotonic() - w0
    compiled = jax_backend.compile_seconds() - compile_s0
    traced = None
    if logdir:
        try:
            traced = trace_reduce.reduce_trace(
                trace_reduce.find_trace(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    train.report({
        "summary": True, "kernels": kernels, "losses": losses,
        "flash_fallbacks": list(att.kernel_fallbacks),
        "device": jax_backend.device_report(),
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "check": check, "setup_s": setup_s, "window_s": window_s,
        "steps": steps, "waits": waits,
        "compiled_in_window_s": compiled, "trace": traced,
        "data_exhausted": exhausted,
        "compiled_gb_per_chip": {
            "arguments": memory.argument_size_in_bytes / 2**30,
            "temporaries": memory.temp_size_in_bytes / 2**30}})


def run(cell: Dict[str, Any], args, t_start: float) -> Dict[str, Any]:
    import ray_tpu
    import ray_tpu.data as rd
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, job = cell["config_file"], cell["traffic_file"]
    sizes = config["training"]
    seed = args.seed % harness.SEED_MODULUS
    program = harness.program_for(config["program"])
    vocab_size = program.vocab_size(config, args.rehearse)
    chips = cell["chips"]
    log = lambda *a: print("[train_job]", *a, flush=True)  # noqa: E731
    rows = sizes["batch"] * job["max_steps"]
    ray_tpu.init(**({"num_tpus": chips} if args.rehearse else {}))
    try:
        ds = rd.range(rows, parallelism=job["blocks"]).map_batches(
            traffic.token_rows(args.seed, sizes["seq"], vocab_size))
        result = JaxTrainer(
            _loop,
            train_loop_config={
                "program": config["program"], "config_file": config,
                "rehearse": args.rehearse, "sizes": sizes, "seed": seed,
                "seconds": args.seconds, "t_start": t_start,
                "trace": bool(args.trace),
                "trace_at_step": job["trace_at_step"],
                "trace_steps": job["trace_steps"]},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpu_chips_per_worker=chips),
            run_config=RunConfig(name=f"bench_{cell['name']}"),
            datasets={"train": ds}).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise harness.BenchError(f"trainer returned an error: "
                                 f"{result.error!r}")
    s = result.metrics_history[-1]
    if not s.get("summary"):
        raise harness.BenchError(f"no summary from the loop: {s}")
    where = s["device"]
    if not args.rehearse and where["platform"] != "tpu":
        raise harness.BenchError(f"the worker computes on {where}")
    tokens_per_step = sizes["batch"] * sizes["seq"]
    n = len(s["steps"])
    measured = {"setup_s": s["setup_s"],
                "train_tok_s": n * tokens_per_step / s["window_s"]}
    missing = [] if args.rehearse else harness.missing_kernels(
        s["kernels"], program.kernels("train_step"))
    finite = all(x == x and abs(x) != float("inf") for x in s["losses"])
    # every number that ``correct`` compares beside what it may be, for
    # the result's last line (``loss_diff`` is the check's, beside
    # reference_check.LOSS_TOL: at most; the others: equal)
    conjuncts = {
        "check": [int(bool(s["check"]["ok"])), 1],
        "losses_finite": [int(finite), 1],
        "kernels_missing": [len(missing), 0],
        "fallbacks": [len(s["flash_fallbacks"]), 0],
        "compiled_in_window_s": [s["compiled_in_window_s"], 0.0],
        "data_exhausted": [int(bool(s["data_exhausted"])), 0],
        "devices": [len(where["device_ids"]), chips]}
    correct = all(value == wanted for value, wanted in conjuncts.values())
    compared = {"loss_diff": [s["check"]["diff"], reference_check.LOSS_TOL],
                **conjuncts}
    log("check:", json.dumps(s["check"]), "losses:",
        [round(x, 4) for x in s["losses"][:3]], "...",
        round(s["losses"][-1], 4))
    log("also:", json.dumps({
        "steps": n, "window_s": s["window_s"],
        "train_tok_s": measured["train_tok_s"],
        "step_ms_median": harness.percentile(s["steps"], 0.5) * 1e3,
        "step_ms_max": max(s["steps"]) * 1e3,
        "data_wait_ms_mean": sum(s["waits"]) / n * 1e3,
        "mesh": s["mesh"], "compiled_gb_per_chip": s["compiled_gb_per_chip"],
        "kernels": s["kernels"]}))
    if not correct:
        log("NOT CORRECT:", json.dumps({
            "check": s["check"], "finite": finite, "missing": missing,
            "fallbacks": s["flash_fallbacks"],
            "compiled_in_window_s": s["compiled_in_window_s"],
            "data_exhausted": s["data_exhausted"],
            "devices": where["device_ids"]}))
    if args.dump:
        import os
        os.makedirs(args.dump, exist_ok=True)
        with open(os.path.join(args.dump, f"{cell['name']}.train.json"),
                  "w") as f:
            json.dump(s, f, default=str)
    traced = s["trace"]
    observed = {"loop": s, "trace": traced, "cell": cell, "chips": chips,
                "peaks": None if args.rehearse
                else harness.peaks_for(where["device_kind"])}
    device, breakdown = harness.device_and_breakdown(where, traced)
    return {"correct": correct and not args.rehearse, "attempted": n,
            "failed": sum(1 for x in s["losses"]
                          if x != x or abs(x) == float("inf")),
            "measured": measured, "observed": observed, "device": device,
            "breakdown": breakdown, "compared": compared}
