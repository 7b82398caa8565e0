"""Headline benchmark: Llama train-step throughput on the local chip(s).

Prints ONE JSON line:
  {"metric": ..., "value": tokens/sec/chip, "unit": ..., "vs_baseline": ...}

vs_baseline = achieved MFU / 0.35 (BASELINE.json north star: Llama-2-7B
fine-tune at >=35% MFU; on the single-chip CI device we run the largest
Llama-architecture model that trains comfortably in HBM and report MFU
against the same bar).

The parent process never initializes a JAX backend (a process that has
holds the chip): it runs `python bench.py --inner`, the benchmark body,
in one child under a watchdog. Without a TPU the run fails; there is
no CPU fallback.
"""

import json
import os
import subprocess
import sys
import time


def _cpu_env() -> dict:
    """A copy of the env forcing a clean CPU JAX backend. Default 1
    device; RTPU_BENCH_CPU_DEVICES>1 builds a forced multi-device host
    so the gradient-sync toggles exercise real collectives off-TPU."""
    from __graft_entry__ import cpu_mesh_env
    return cpu_mesh_env(int(os.environ.get("RTPU_BENCH_CPU_DEVICES",
                                           "1")))


def _sync_toggles() -> tuple:
    """(grad_compression, zero1) from the env — the round-7 gradient-
    sync levers, recorded verbatim in the BENCH json."""
    comp = os.environ.get("RTPU_BENCH_GRAD_COMPRESSION", "").strip()
    comp = comp if comp in ("int8", "fp8") else None
    zero1 = os.environ.get("RTPU_BENCH_ZERO1", "") not in ("", "0")
    return comp, zero1


def _pipeline_toggles():
    """(stages, microbatches, schedule) from the env, or None when the
    pipeline row is off (stages <= 1). Flags: --pipeline-stages N
    --microbatches M --schedule 1f1b|gpipe."""
    stages = int(os.environ.get("RTPU_BENCH_PIPELINE_STAGES", "0") or 0)
    if stages <= 1:
        return None
    microbatches = int(os.environ.get("RTPU_BENCH_MICROBATCHES", "4"))
    schedule = os.environ.get("RTPU_BENCH_SCHEDULE", "1f1b")
    return stages, microbatches, schedule


def _bench_pipeline(stages, microbatches, schedule):
    """Pipeline-parallel row: a small layered MLP driven through
    PipelineRunner (shm activation channels), reporting the measured
    per-stage bubble against the schedule's theoretical
    (s-1)/(m+s-1) plus end-to-end rows/s. Runs inside the --inner
    child so the backend env is already settled."""
    import numpy as np

    import ray_tpu
    from ray_tpu.train.pipeline import LayeredModel, PipelineRunner

    def model_fns():
        # closures: stage actors deserialize these by value, no
        # dependency on the bench module being importable remotely
        import jax.numpy as jnp

        def apply_layer(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def loss_fn(out, tgt):
            return jnp.mean((out - tgt) ** 2)

        return apply_layer, loss_fn

    dim = int(os.environ.get("RTPU_BENCH_PIPELINE_DIM", "64"))
    steps = int(os.environ.get("RTPU_BENCH_PIPELINE_STEPS", "5"))
    rng = np.random.RandomState(0)
    layers = [{"w": rng.randn(dim, dim).astype(np.float32) * 0.3,
               "b": np.zeros(dim, dtype=np.float32)}
              for _ in range(max(2 * stages, 2))]
    batch = 8 * microbatches
    x = rng.randn(batch, dim).astype(np.float32)
    y = rng.randn(batch, dim).astype(np.float32)

    ray_tpu.init(num_cpus=max(4, stages + 1),
                 system_config={"task_max_retries": 0})
    apply_layer, loss_fn = model_fns()
    runner = PipelineRunner(
        LayeredModel(layers, apply_layer, loss_fn),
        num_stages=stages, num_microbatches=microbatches,
        schedule=schedule, recv_timeout_s=60.0)
    try:
        runner.step(x, y)  # warm: stage-side jit + channel setup
        bubbles = []
        t0 = time.perf_counter()
        for _ in range(steps):
            bubbles.append(runner.step(x, y)["bubble"])
        dt = time.perf_counter() - t0
        return {
            "pipeline_stages": stages,
            "microbatches": microbatches,
            "schedule": schedule,
            "bubble_ratio": round(sum(bubbles) / len(bubbles), 4),
            "theoretical_bubble": round(runner.theoretical_bubble, 4),
            "tokens_per_sec": round(batch * steps / dt, 1),
        }
    finally:
        runner.shutdown()
        ray_tpu.shutdown()


def _attach_pipeline_row(result: dict) -> None:
    """Append the pipeline bench row to the JSON dict when the
    --pipeline-stages toggle is on (never fails the headline bench)."""
    pipe = _pipeline_toggles()
    if pipe is None:
        return
    try:
        result["pipeline"] = _bench_pipeline(*pipe)
    except Exception as e:  # noqa: BLE001 — optional row
        sys.stderr.write(f"[bench] pipeline row failed: {e!r}\n")
        result["pipeline"] = {
            "pipeline_stages": pipe[0], "microbatches": pipe[1],
            "schedule": pipe[2], "error": str(e)[:300]}


def _bench_data_pipeline():
    """Data-plane bench (runs in the --data-pipeline-inner child):

    1. streaming-shuffle throughput — rows/s and GB/s through the
       pipelined map->reduce path, plus the streaming proof stats
       (first output landed before the last map; bounded in-flight);
    2. trainer-feed efficiency — the SAME jitted train step driven by
       device-resident synthetic batches vs by the real pipeline
       (read -> map_batches -> iter_device_batches double-buffering).
       real_vs_synthetic ~ 1.0 means the data plane never starves the
       step loop.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    result = {"metric": "data_pipeline"}
    try:
        # ---- leg 1: streaming shuffle ------------------------------
        rows = int(os.environ.get("RTPU_BENCH_DATA_ROWS", "100000"))
        vec = int(os.environ.get("RTPU_BENCH_DATA_VEC", "64"))
        ds = rd.range_tensor(rows, shape=(vec,),
                             parallelism=32).random_shuffle(seed=0)
        t0 = time.perf_counter()
        out_rows = sum(b.metadata.num_rows or 0
                       for b in ds.iter_internal_ref_bundles())
        dt = time.perf_counter() - t0
        ss = list(ds._last_executor.shuffle_states.values())[0]
        # bytes that crossed the shuffle: every block enters a map and
        # leaves a reduce, so count both directions
        moved = ss.bytes_map_in + ss.bytes_reduce_out
        result["shuffle"] = {
            "rows": out_rows,
            "row_bytes": vec * 8,
            "seconds": round(dt, 3),
            "rows_per_sec": round(out_rows / dt, 1),
            "gb_per_sec": round(moved / dt / 1e9, 4),
            "first_output_maps_done": ss.first_output_maps_done,
            "n_maps": ss.n_maps,
            "peak_in_flight_blocks": ss.peak_in_flight_blocks,
            "in_flight_window": ss.window,
        }

        # ---- leg 2: real-pipeline trainer vs synthetic batches -----
        cfg = LlamaConfig.tiny()
        batch = int(os.environ.get("RTPU_BENCH_DATA_BATCH", "8"))
        seq = 64
        steps = int(os.environ.get("RTPU_BENCH_DATA_STEPS", "20"))
        opt = optax.adamw(3e-4)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        opt_state = opt.init(params)

        @jax.jit
        def train_step(p, s, tokens, targets):
            loss, grads = jax.value_and_grad(
                lambda q: llama_loss(q, tokens, targets, cfg))(p)
            updates, s = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss

        def tokenize(b):
            t = ((b["data"] * 31 + np.arange(seq)) % cfg.vocab_size)
            return {"tokens": t.astype(np.int32),
                    "targets": np.roll(t, -1, axis=1).astype(np.int32)}

        n_rows = batch * (steps + 2)
        pipe_ds = rd.range_tensor(n_rows, shape=(seq,),
                                  parallelism=8).map_batches(tokenize)

        tok = jnp.zeros((batch, seq), jnp.int32)
        p, s, loss = train_step(params, opt_state, tok, tok)
        float(loss)  # compile + flush barrier

        t0 = time.perf_counter()
        for _ in range(steps):
            p, s, loss = train_step(p, s, tok, tok)
        float(loss)
        dt_syn = time.perf_counter() - t0

        it = pipe_ds.iter_device_batches(batch_size=batch, prefetch=4,
                                         dtypes=jnp.int32)
        first = next(it)  # pipeline warmup batch, outside the window
        p, s, loss = train_step(p, s, first["tokens"], first["targets"])
        float(loss)
        n_real = 0
        t0 = time.perf_counter()
        for b in it:
            p, s, loss = train_step(p, s, b["tokens"], b["targets"])
            n_real += 1
        float(loss)
        dt_real = time.perf_counter() - t0

        syn_tps = batch * seq * steps / dt_syn
        real_tps = batch * seq * n_real / dt_real
        result["trainer"] = {
            "model_params": cfg.num_params(),
            "batch": batch, "seq": seq, "steps": steps,
            "synthetic_tokens_per_sec": round(syn_tps, 1),
            "real_tokens_per_sec": round(real_tps, 1),
            "real_vs_synthetic": round(real_tps / syn_tps, 4),
            "prefetch_wait_seconds": round(it.wait_seconds_total, 4),
        }
        result["device"] = str(getattr(jax.devices()[0], "device_kind",
                                       "cpu"))
    finally:
        ray_tpu.shutdown()
    return result


def data_pipeline_main():
    """`bench.py --data-pipeline`: run the data-plane bench in a child,
    write BENCH_data.json next to this script, echo the JSON line."""
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_data.json")
    timeout_s = int(os.environ.get("RTPU_BENCH_DATA_TIMEOUT_S", "600"))
    ok, parsed, diag = _run_child(["--data-pipeline-inner"],
                                  os.environ.copy(), timeout_s)
    if not ok or parsed is None:
        sys.stderr.write(
            f"[bench] data pipeline failed ({diag}); retrying on a "
            "clean CPU env\n")
        ok, parsed, diag = _run_child(["--data-pipeline-inner"],
                                      _cpu_env(), timeout_s)
        if ok and parsed is not None:
            parsed["degraded"] = "cpu-fallback"
    if not ok or parsed is None:
        parsed = {"metric": "data_pipeline", "error": diag}
    with open(out_path, "w") as f:
        json.dump(parsed, f, indent=2)
        f.write("\n")
    print(json.dumps(parsed))


def _bench_rl_inner():
    """`bench.py --rl-inner` (child): one podracer arch, JSON line out.
    Arch picked by RTPU_BENCH_RL_ARCH (anakin | sebulba)."""
    arch = os.environ.get("RTPU_BENCH_RL_ARCH", "anakin")
    warmup = int(os.environ.get("RTPU_BENCH_RL_WARMUP", "2"))
    if arch == "anakin":
        import jax
        from ray_tpu.rl.podracer import Anakin, AnakinConfig
        updates = int(os.environ.get("RTPU_BENCH_RL_UPDATES", "20"))
        cfg = AnakinConfig(num_envs_per_device=16, rollout_len=16,
                           hidden=(64, 64))
        trainer = Anakin(cfg)
        trainer.train(warmup)  # compile + first-touch outside the clock
        out = trainer.train(updates)
        return {
            "arch": "anakin",
            "num_devices": out["num_devices"],
            "num_updates": updates,
            "env_steps": updates * out["num_devices"]
            * cfg.num_envs_per_device * cfg.rollout_len,
            "env_steps_per_sec": round(out["env_steps_per_sec"], 1),
            "backend": jax.default_backend(),
        }
    # sebulba: the full actor–learner constellation on the local node
    import ray_tpu
    from ray_tpu.rl.podracer import Sebulba, SebulbaConfig
    from ray_tpu.rl.podracer.inference import MAX_BATCH_SIZE
    learner_steps = int(os.environ.get("RTPU_BENCH_RL_UPDATES", "12"))
    ray_tpu.init(system_config={"task_max_retries": 0})
    try:
        cfg = SebulbaConfig(num_actors=2, num_envs_per_actor=4,
                            rollout_len=16, hidden=(64, 64),
                            fragments_per_step=2,
                            weight_push_interval=1, max_staleness=50)
        trainer = Sebulba(cfg)
        try:
            out = trainer.train(learner_steps, step_timeout_s=120.0)
        finally:
            trainer.shutdown()
    finally:
        from ray_tpu import serve
        serve.shutdown()
        ray_tpu.shutdown()
    learner = out["learner"]
    max_rows = cfg.num_actors * cfg.num_envs_per_actor
    return {
        "arch": "sebulba",
        "num_actors": cfg.num_actors,
        "learner_updates": learner["num_updates"],
        "env_steps": out["env_steps_sampled"],
        "env_steps_per_sec": round(out["env_steps_per_sec"], 1),
        "inference_batch_rows_mean": round(out["mean_batch_rows"], 2),
        "inference_batch_occupancy": round(
            out["mean_batch_rows"] / min(max_rows, MAX_BATCH_SIZE), 4),
        "weight_pushes": learner["weight_pushes"],
        "weight_push_ms": round(learner["last_push_ms"], 3),
        "version_lag_mean": round(learner["version_lag_mean"], 2),
        "version_lag_max": learner["version_lag_max"],
        "stale_dropped": learner["stale_dropped"],
        "replay": out["replay"],
    }


def rl_main():
    """`bench.py --rl [anakin|sebulba|both]`: run the podracer RL
    benches in children, write BENCH_rl.json, echo the JSON line."""
    arch = os.environ.get("RTPU_BENCH_RL_ARCH", "both")
    timeout_s = int(os.environ.get("RTPU_BENCH_RL_TIMEOUT_S", "420"))
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_rl.json")
    from __graft_entry__ import cpu_mesh_env
    result = {"metric": "podracer_rl"}
    archs = ["anakin", "sebulba"] if arch == "both" else [arch]
    for a in archs:
        if a == "anakin":
            # Anakin wants a multi-device shard view: force a 4-device
            # host platform in the child (same trick as the sweeps)
            env = cpu_mesh_env(int(os.environ.get(
                "RTPU_BENCH_RL_DEVICES", "4")))
        else:
            env = _cpu_env()
        env["RTPU_BENCH_RL_ARCH"] = a
        ok, parsed, diag = _run_child(["--rl-inner"], env, timeout_s)
        result[a] = parsed if (ok and parsed is not None) \
            else {"error": diag}
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result))


def _run_child(args, env, timeout_s):
    """Run a child, return (ok, parsed_json_or_None, diagnostic_str)."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + args,
            env=env, timeout=timeout_s, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return False, None, f"timeout after {timeout_s}s"
    if proc.stderr:
        sys.stderr.write(proc.stderr[-4000:])
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
            except ValueError:
                continue  # stray '{'-prefixed noise; keep scanning up
            ok = proc.returncode == 0
            diag = "" if ok else (
                f"rc={proc.returncode} after printing JSON: "
                + (proc.stderr or "")[-300:].strip())
            return ok, parsed, diag
    tail = (proc.stdout or "")[-500:] + (proc.stderr or "")[-500:]
    return False, None, f"rc={proc.returncode}: {tail.strip()[-600:]}"


def main():
    run_s = int(os.environ.get("RTPU_BENCH_TIMEOUT_S", "600"))
    env = os.environ.copy()
    # the child's sweep budget must fit inside the watchdog (margin
    # for startup + one config overrun)
    env.setdefault("RTPU_BENCH_SWEEP_BUDGET_S", str(max(120, run_s - 180)))
    ok, parsed, diag = _run_child(["--inner"], env, run_s)
    if not ok or parsed is None:
        sys.exit(f"[bench] failed: {diag}")
    print(json.dumps(parsed))


# Peak bf16 FLOP/s per chip, keyed by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s).
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device) -> float:
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind "
            f"{device.device_kind!r}; add it to PEAK_FLOPS with its "
            "source") from None


def _bench_config(cfg, batch, seq, steps, devices,
                  grad_compression=None, zero1=False):
    """One measured config -> metrics dict, or raises (e.g. OOM)."""
    import jax
    import numpy as np
    import optax

    from ray_tpu.models.llama import llama_init, llama_loss

    n_chips = len(devices)
    batch = batch * n_chips
    params = llama_init(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4, weight_decay=0.01)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0,
                                 cfg.vocab_size)
    use_shard_map = grad_compression is not None or zero1
    if use_shard_map:
        train_step, opt_state = _shard_map_step(
            cfg, opt, params, devices, grad_compression, zero1)
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("data",))
        data_sharding = NamedSharding(mesh, P("data"))
        tokens = jax.device_put(tokens, data_sharding)
        targets = jax.device_put(targets, data_sharding)
        params = jax.device_put(params, NamedSharding(mesh, P()))
    else:
        opt_state = opt.init(params)
        if n_chips > 1:
            # Shard the batch over a data-axis mesh, so dividing
            # throughput by n_chips below is honest on multi-chip hosts
            # (an unsharded step would run on device 0 only).
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec as P)
            mesh = Mesh(np.asarray(devices), ("data",))
            data_sharding = NamedSharding(mesh, P("data"))
            repl = NamedSharding(mesh, P())
            tokens = jax.device_put(tokens, data_sharding)
            targets = jax.device_put(targets, data_sharding)
            params = jax.device_put(params, repl)
            opt_state = jax.device_put(opt_state, repl)

        @jax.jit
        def train_step(params, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(
                lambda p: llama_loss(p, tokens, targets, cfg))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

    # Compile + warmup.
    params, opt_state, loss = train_step(params, opt_state, tokens,
                                         targets)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, tokens,
                                             targets)
    jax.block_until_ready((params, opt_state, loss))
    dt = time.perf_counter() - t0
    final_loss = float(loss)

    dev = devices[0]
    tokens_per_sec = batch * seq * steps / dt
    tokens_per_sec_per_chip = tokens_per_sec / n_chips
    mfu = (tokens_per_sec_per_chip * cfg.flops_per_token()
           / peak_flops(dev))
    return {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4),
        "mfu": round(mfu, 4),
        "model_params": cfg.num_params(),
        "batch": batch, "seq": seq,
        "ce_chunk_tokens": cfg.ce_chunk_tokens,
        "device": str(getattr(dev, "device_kind", dev)),
        "final_loss": round(final_loss, 4),
        "grad_compression": grad_compression,
        "zero1": bool(zero1),
    }


def _shard_map_step(cfg, opt, params, devices, grad_compression, zero1):
    """Explicit-collective DDP/ZeRO-1 train step over a data mesh.

    The plain bench path lets GSPMD insert the gradient sync; these
    toggles need the collectives spelled out: quantized_psum /
    quantized_reduce_scatter from ray_tpu.parallel.collective for the
    wire-compression lever, and an explicitly sharded optimizer update
    (reduce-scatter grads → adam on this device's 1/world flat shard of
    params + moments → all-gather params) for ZeRO-1.
    Returns (jitted step fn, initial optimizer state placed on the
    mesh: flat and P("data")-sharded when zero1, replicated otherwise).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.llama import llama_loss
    from ray_tpu.parallel.collective import (quantized_pmean,
                                             quantized_reduce_scatter)

    world = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    block = 256

    def local_grads(p, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda q: llama_loss(q, tokens, targets, cfg))(p)
        return jax.lax.pmean(loss, "data"), grads

    if not zero1:
        # replicated update, compressed gradient transport
        opt_state = jax.device_put(opt.init(params),
                                   NamedSharding(mesh, P()))

        def step(p, state, tokens, targets):
            loss, grads = local_grads(p, tokens, targets)
            grads = jax.tree_util.tree_map(
                lambda g: quantized_pmean(g, "data",
                                          dtype=grad_compression),
                grads)
            updates, state = opt.update(grads, state, p)
            p = optax.apply_updates(p, updates)
            return p, state, loss

        specs = (P(), P(), P("data"), P("data"))
        out_specs = (P(), P(), P())
        return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=specs,
                                     out_specs=out_specs,
                                     check_vma=False)), opt_state

    # ZeRO-1: flat param vector padded to a (world * block) multiple so
    # both psum_scatter and the quantized variant split it evenly; the
    # adam moments live as flat P("data")-sharded arrays — each device
    # materializes only its 1/world shard.
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = [l.shape for l in leaves]
    sizes = [int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes]
    n = int(sum(sizes))
    padded_n = -(-n // (world * block)) * (world * block)
    shard_n = padded_n // world

    def flatten_tree(tree):
        ls = jax.tree_util.tree_leaves(tree)
        vec = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in ls])
        return jnp.pad(vec, (0, padded_n - n))

    def unflatten_vec(vec):
        out = []
        off = 0
        for shape, size, leaf in zip(shapes, sizes, leaves):
            out.append(vec[off:off + size].reshape(shape)
                       .astype(leaf.dtype))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    opt_state = opt.init(jnp.zeros((padded_n,), jnp.float32))
    state_specs = jax.tree_util.tree_map(
        lambda x: P("data") if getattr(x, "ndim", 0) >= 1 else P(),
        opt_state)
    opt_state = jax.device_put(
        opt_state,
        jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), state_specs,
            is_leaf=lambda x: isinstance(x, P)))

    def step(p, state, tokens, targets):
        loss, grads = local_grads(p, tokens, targets)
        gvec = flatten_tree(grads)
        if grad_compression is not None:
            gshard = quantized_reduce_scatter(
                gvec, "data", dtype=grad_compression) / world
        else:
            gshard = jax.lax.psum_scatter(gvec, "data",
                                          scatter_dimension=0,
                                          tiled=True) / world
        pvec = flatten_tree(p)
        idx = jax.lax.axis_index("data")
        pshard = jax.lax.dynamic_slice_in_dim(pvec, idx * shard_n,
                                              shard_n)
        updates, state = opt.update(gshard, state, pshard)
        new_shard = optax.apply_updates(pshard, updates)
        new_vec = jax.lax.all_gather(new_shard, "data", tiled=True)
        return unflatten_vec(new_vec), state, loss

    specs = (P(), state_specs, P("data"), P("data"))
    out_specs = (P(), state_specs, P())
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=specs,
                                 out_specs=out_specs,
                                 check_vma=False)), opt_state


def inner():
    import jax
    import jax.numpy as jnp

    from ray_tpu.accelerators.jax_backend import on_tpu
    from ray_tpu.models.llama import LlamaConfig

    if not on_tpu():
        raise RuntimeError(
            f"bench.py measures a TPU; jax's backend here is "
            f"{jax.default_backend()!r}")
    devices = jax.devices()
    grad_compression, zero1 = _sync_toggles()

    def model(dim, layers, heads, hidden, ce_chunk):
        # Llama-architecture configs sized so the MXU dominates while
        # params + fp32 Adam moments + remat activations fit one 16 GB
        # chip. Wider models ran measurably higher MFU in the round-4
        # on-chip sweep (PERF.md): dim 2560/L12 (1.1B) 0.4896 at b10,
        # dim 2048/L12 (748M) 0.4751, dim 1536/L12 (440M) 0.4444.
        return LlamaConfig(
            vocab_size=32000, dim=dim, n_layers=layers, n_heads=heads,
            n_kv_heads=heads, hidden_dim=hidden, max_seq_len=2048,
            dtype=jnp.bfloat16, attention="flash", remat=True,
            ce_chunk_tokens=ce_chunk)

    # Config sweep, best-measured first (each entry: model shape +
    # batch). Chunked cross-entropy frees the [B, S, V] fp32 logits so
    # the larger shapes fit. Keep the best MFU inside the time budget;
    # the dim-1536 entries are the round-1/round-4 proven fallbacks.
    # Sweep progress goes to stderr (stdout carries ONLY the final
    # JSON line for the driver).
    # Measured on one v5e chip at PR 51, when remat began to keep a
    # block's narrow residuals (llama.REMAT_SAVED). The batches that
    # had sat at the memory's edge under full recomputation no longer
    # fit; such an entry is skipped, and its shape has a smaller batch.
    sweep = [
        ((2560, 12, 20, 6912, 4096), 10),  # 1.1B, no longer fits (0.4896)
        ((2560, 12, 20, 6912, 4096), 8),   # 1.1B, no longer fits (0.4856)
        ((2560, 12, 20, 6912, 4096), 4),   # 1.1B, measured 0.5094
        ((2048, 12, 16, 5632, 8192), 16),  # 748M, no longer fits (0.4751)
        ((2048, 12, 16, 5632, 8192), 8),   # 748M, measured 0.5289
        ((1536, 12, 12, 4096, 4096), 16),  # 440M, measured 0.4911 (0.4444)
        ((1536, 12, 12, 4096, 4096), 8),   # 440M, measured 0.5063
        ((1536, 12, 12, 4096, 0), 8),      # 440M, dense loss: 0.5119
    ]
    budget_s = float(os.environ.get("RTPU_BENCH_SWEEP_BUDGET_S", "420"))
    t_start = time.perf_counter()
    best = None
    last_config_s = 0.0
    for shape, batch in sweep:
        # Pre-config budget check: never START a config that (judging
        # by the previous one) would run past the budget — finishing
        # mid-config under the parent's SIGKILL loses best-so-far.
        elapsed = time.perf_counter() - t_start
        if best is not None and (
                elapsed + 1.2 * last_config_s > budget_s):
            sys.stderr.write("[bench] sweep budget reached\n")
            break
        t_cfg = time.perf_counter()
        try:
            result = _bench_config(model(*shape), batch, 2048, 5, devices,
                                   grad_compression=grad_compression,
                                   zero1=zero1)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            sys.stderr.write(f"[bench] shape={shape} batch={batch} "
                             "does not fit the chip's memory\n")
            continue
        last_config_s = time.perf_counter() - t_cfg
        sys.stderr.write(
            f"[bench] shape={shape} batch={batch} "
            f"mfu={result['mfu']}\n")
        if best is None or result["mfu"] > best["mfu"]:
            best = result
    if best is None:
        raise RuntimeError("no entry of the sweep fits the chip's memory")
    if os.environ.get("RTPU_BENCH_INT8"):
        _bench_int8_row()
    _attach_pipeline_row(best)
    print(json.dumps(best))


def _bench_int8_row():
    """Optional on-chip int8-vs-bf16 weight-matmul row (stderr only;
    enable with RTPU_BENCH_INT8=1). Llama-7B FFN shape at decode batch
    32 — the weight-bandwidth-bound case the kernel targets."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.quant_matmul import int8_matmul, quantize_int8

    d, h, b = 4096, 11008, 32
    w = jax.random.normal(jax.random.PRNGKey(0), (d, h), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, d), jnp.bfloat16)
    w8, s = quantize_int8(w)
    f_bf = jax.jit(lambda x: jnp.sum(x @ w))
    f_q8 = jax.jit(lambda x: jnp.sum(int8_matmul(x, w8, s)))
    out = {}
    for name, fn in (("bf16", f_bf), ("int8", f_q8)):
        float(fn(x))  # compile
        t0 = time.perf_counter()
        acc = 0.0
        for _ in range(20):
            acc += float(fn(x))
        out[name] = (time.perf_counter() - t0) / 20
    sys.stderr.write(
        f"[bench] int8 ffn-matmul [{b}x{d}]@[{d}x{h}]: "
        f"bf16 {out['bf16']*1e3:.3f}ms int8 {out['int8']*1e3:.3f}ms "
        f"speedup {out['bf16']/out['int8']:.2f}x\n")


if __name__ == "__main__":
    # Toggle flags become env vars so the --inner child inherits them:
    #   python bench.py --grad-compression int8 --zero1
    #   python bench.py --pipeline-stages 3 --microbatches 8 \
    #       --schedule 1f1b
    _argv = sys.argv[1:]
    for _i, _a in enumerate(_argv):
        if _a.startswith("--grad-compression="):
            os.environ["RTPU_BENCH_GRAD_COMPRESSION"] = \
                _a.split("=", 1)[1]
        elif _a == "--grad-compression" and _i + 1 < len(_argv):
            os.environ["RTPU_BENCH_GRAD_COMPRESSION"] = _argv[_i + 1]
        elif _a == "--zero1":
            os.environ["RTPU_BENCH_ZERO1"] = "1"
        elif _a.startswith("--pipeline-stages="):
            os.environ["RTPU_BENCH_PIPELINE_STAGES"] = \
                _a.split("=", 1)[1]
        elif _a == "--pipeline-stages" and _i + 1 < len(_argv):
            os.environ["RTPU_BENCH_PIPELINE_STAGES"] = _argv[_i + 1]
        elif _a.startswith("--microbatches="):
            os.environ["RTPU_BENCH_MICROBATCHES"] = _a.split("=", 1)[1]
        elif _a == "--microbatches" and _i + 1 < len(_argv):
            os.environ["RTPU_BENCH_MICROBATCHES"] = _argv[_i + 1]
        elif _a.startswith("--schedule="):
            os.environ["RTPU_BENCH_SCHEDULE"] = _a.split("=", 1)[1]
        elif _a == "--schedule" and _i + 1 < len(_argv):
            os.environ["RTPU_BENCH_SCHEDULE"] = _argv[_i + 1]
        elif _a.startswith("--rl="):
            os.environ["RTPU_BENCH_RL_ARCH"] = _a.split("=", 1)[1]
        elif _a == "--rl":
            nxt = _argv[_i + 1] if _i + 1 < len(_argv) else ""
            os.environ["RTPU_BENCH_RL_ARCH"] = (
                nxt if nxt in ("anakin", "sebulba", "both") else "both")
    if "--rl-inner" in sys.argv:
        print(json.dumps(_bench_rl_inner()))
    elif "--rl" in sys.argv or any(
            _a.startswith("--rl=") for _a in _argv):
        rl_main()
    elif "--data-pipeline-inner" in sys.argv:
        print(json.dumps(_bench_data_pipeline()))
    elif "--data-pipeline" in sys.argv or \
            os.environ.get("RTPU_BENCH_DATA_PIPELINE"):
        data_pipeline_main()
    elif "--inner" in sys.argv:
        inner()
    else:
        main()
