"""Round-7 gradient-sync tests: ring allreduce (vs the binomial tree,
bitwise), int8/fp8 block-quantized transport with error feedback,
ZeRO-1 sharded optimizer parity + memory, collective byte counters at
/metrics, and the jit-side quantized collectives on a forced 8-device
CPU backend (run in a subprocess so this process's JAX state stays
untouched — see the `multidevice` marker in pytest.ini)."""

import os
import subprocess
import sys
import textwrap
import urllib.request

import numpy as np
import pytest

import ray_tpu

WORLD = 4


def _spawn_group(n, group="qgrp"):
    @ray_tpu.remote(num_cpus=0)
    class SyncWorker:
        def __init__(self, rank, world):
            from ray_tpu.parallel import collective
            self.rank, self.world = rank, world
            self.group = group
            collective.init_collective_group(world, rank, group)

        def ring_vs_tree(self):
            from ray_tpu.parallel import collective
            # integer-valued floats: fp32 addition is exact, so any
            # mismatch is an algorithm bug, not roundoff
            x = np.arange(self.rank, self.rank + 5000, dtype=np.float32)
            ring = collective.allreduce(x, "sum", self.group,
                                        algorithm="ring")
            tree = collective.allreduce(x, "sum", self.group,
                                        algorithm="tree")
            mean = collective.allreduce(x, "mean", self.group,
                                        algorithm="ring")
            return (bool((ring == tree).all()),
                    bool(np.allclose(mean, tree / self.world)),
                    ring[:4].tolist())

        def quantized_error(self, compression):
            from ray_tpu.parallel import collective
            rng = np.random.default_rng(self.rank)
            g = rng.standard_normal(4097).astype(np.float32)
            exact = collective.allreduce(g, "sum", self.group)
            quant = collective.allreduce(g, "sum", self.group,
                                         compression=compression)
            rel = float(np.abs(quant - exact).max()
                        / np.abs(exact).max())
            return rel, quant[:4].tolist()

        def ef_convergence(self, rounds):
            """Repeatedly allreduce the SAME tensor; the time-averaged
            result converges to the truth only with error feedback —
            naive quantization repeats the same biased rounding every
            round."""
            from ray_tpu.parallel import collective
            rng = np.random.default_rng(self.rank)
            g = rng.standard_normal(2048).astype(np.float32)
            truth = collective.allreduce(g, "mean", self.group)
            naive = np.zeros_like(g)
            ef = np.zeros_like(g)
            for _ in range(rounds):
                naive += collective.allreduce(g, "mean", self.group,
                                              compression="int8")
                ef += collective.allreduce(g, "mean", self.group,
                                           compression="int8",
                                           ef_key="efleaf")
            naive_bias = float(np.abs(naive / rounds - truth).max())
            ef_bias = float(np.abs(ef / rounds - truth).max())
            return naive_bias, ef_bias

        def zero1_vs_ddp(self, steps):
            """Same grads through Zero1Optimizer and DDPOptimizer must
            land on the same params; ZeRO-1's adam state is ~1/world of
            DDP's."""
            import jax
            import optax
            from ray_tpu.train.collective import (DDPOptimizer,
                                                  Zero1Optimizer)
            params = {
                "w": np.linspace(-1.0, 1.0, 1003,
                                 dtype=np.float32).reshape(17, 59),
                "b": np.zeros(59, dtype=np.float32),
            }
            z1 = Zero1Optimizer(optax.adam(0.05), params,
                                group_name=self.group)
            ddp = DDPOptimizer(optax.adam(0.05), params,
                               group_name=self.group)
            p_z1 = jax.tree_util.tree_map(np.array, params)
            p_ddp = jax.tree_util.tree_map(np.array, params)
            rng = np.random.default_rng(100 + self.rank)
            for _ in range(steps):
                grads = {
                    "w": rng.standard_normal((17, 59)).astype(np.float32),
                    "b": rng.standard_normal(59).astype(np.float32),
                }
                p_z1 = z1.step(p_z1, grads)
                p_ddp = ddp.step(p_ddp, grads)
            diff = max(
                float(np.abs(np.asarray(p_z1[k])
                             - np.asarray(p_ddp[k])).max())
                for k in params)
            return (diff, z1.optimizer_state_bytes(),
                    ddp.optimizer_state_bytes())

        def bytes_for(self, compression):
            from ray_tpu.parallel import collective
            g = np.ones(65536, dtype=np.float32)
            collective.allreduce(g, "sum", self.group,
                                 compression=compression)
            return True

        def roundtrip_flat(self):
            from ray_tpu.parallel import collective
            g = np.arange(1025, dtype=np.float32) * (self.rank + 1)
            truth = collective.allreduce(g, "mean", self.group)
            shard, off = collective.reduce_scatter_flat(
                g, "mean", self.group)
            full = collective.allgather_flat(shard, self.group)
            return (float(np.abs(full - truth).max()), int(off),
                    int(shard.size))

        def destroy(self):
            from ray_tpu.parallel import collective
            collective.destroy_collective_group(self.group)

    return [SyncWorker.remote(i, n) for i in range(n)]


def test_ring_allreduce_matches_tree_bitwise(ray_start_regular):
    workers = _spawn_group(WORLD)
    out = ray_tpu.get([w.ring_vs_tree.remote() for w in workers])
    assert all(bitwise for bitwise, _, _ in out)
    assert all(mean_ok for _, mean_ok, _ in out)
    # every rank returns the identical reduced tensor
    assert len({tuple(head) for _, _, head in out}) == 1
    ray_tpu.get([w.destroy.remote() for w in workers])


def test_odd_world_ring(ray_start_regular):
    workers = _spawn_group(3)
    out = ray_tpu.get([w.ring_vs_tree.remote() for w in workers])
    assert all(bitwise for bitwise, _, _ in out)
    ray_tpu.get([w.destroy.remote() for w in workers])


@pytest.mark.parametrize("compression,bound", [("int8", 0.02),
                                               ("fp8", 0.15)])
def test_quantized_allreduce_error_bounded(ray_start_regular,
                                           compression, bound):
    workers = _spawn_group(WORLD)
    out = ray_tpu.get(
        [w.quantized_error.remote(compression) for w in workers])
    for rel, _head in out:
        assert rel < bound, f"{compression} rel error {rel} > {bound}"
    # ranks decode the same wire bytes -> identical outputs
    assert len({tuple(head) for _, head in out}) == 1
    ray_tpu.get([w.destroy.remote() for w in workers])


@pytest.mark.watchdog(300)
def test_error_feedback_converges_where_naive_drifts(ray_start_regular):
    workers = _spawn_group(WORLD)
    out = ray_tpu.get([w.ef_convergence.remote(50) for w in workers])
    for naive_bias, ef_bias in out:
        # naive quantization repeats the same deterministic rounding ->
        # constant bias; EF compensates it away round over round
        assert ef_bias < naive_bias / 3
        assert ef_bias < 2e-3
    ray_tpu.get([w.destroy.remote() for w in workers])


@pytest.mark.watchdog(300)
def test_zero1_matches_ddp_and_shrinks_opt_state(ray_start_regular):
    workers = _spawn_group(WORLD)
    out = ray_tpu.get([w.zero1_vs_ddp.remote(5) for w in workers])
    n_params = 1003 + 59
    for diff, z1_bytes, ddp_bytes in out:
        assert diff < 1e-5, f"zero1 diverged from ddp by {diff}"
        # adam keeps mu+nu (f32): DDP holds them for every param,
        # ZeRO-1 only for this rank's 1/world flat shard
        assert ddp_bytes >= 2 * 4 * n_params
        ratio = ddp_bytes / max(z1_bytes, 1)
        assert WORLD * 0.7 < ratio < WORLD * 1.4, (
            f"opt-state shrink {ratio} not ~{WORLD}x")
    ray_tpu.get([w.destroy.remote() for w in workers])


def test_reduce_scatter_allgather_flat_roundtrip(ray_start_regular):
    workers = _spawn_group(WORLD)
    out = ray_tpu.get([w.roundtrip_flat.remote() for w in workers])
    offs = sorted((off, size) for _, off, size in out)
    assert offs[0][0] == 0
    assert sum(size for _, size in offs) == 1025
    for err, _, _ in out:
        assert err < 1e-6
    ray_tpu.get([w.destroy.remote() for w in workers])


def test_kv_wait_timeout_names_missing_rank(ray_start_regular):
    """A rank whose peer never shows up gets a timeout that says WHICH
    rank it was waiting for (satellite: backoff _kv_wait with a hard
    deadline and a named-rank error)."""
    from ray_tpu.exceptions import GetTimeoutError
    from ray_tpu.parallel import collective
    collective.init_collective_group(2, 0, "lonely")
    try:
        with pytest.raises(GetTimeoutError) as exc:
            collective.allreduce(np.ones(4, np.float32), "sum", "lonely",
                                 timeout=1.5)
        msg = str(exc.value)
        assert "rank 1" in msg
        assert "lonely" in msg
    finally:
        collective._groups.pop("lonely", None)


def test_ef_residual_reset_and_inspection(ray_start_regular):
    from ray_tpu.parallel import collective
    collective.init_collective_group(1, 0, "solo")
    try:
        g = np.linspace(-1, 1, 512).astype(np.float32)
        collective.allreduce(g, "sum", "solo", compression="int8",
                             ef_key="leaf")
        # world==1 short-circuits before quantization: no residual
        assert collective.error_feedback_residual("solo", "leaf") is None
        collective.reset_error_feedback("solo")
    finally:
        collective._groups.pop("solo", None)


@pytest.fixture
def metrics_runtime():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4, include_dashboard=True)
    yield rt
    ray_tpu.shutdown()


def _scrape_text(url):
    with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
        return resp.read().decode()


def _bytes_series_sum(body, dtype):
    total = 0.0
    for line in body.splitlines():
        if (line.startswith("ray_tpu_train_collective_bytes_total")
                and f'dtype="{dtype}"' in line
                and 'op="allreduce"' in line):
            total += float(line.rsplit(" ", 1)[1])
    return total


@pytest.mark.watchdog(300)
def test_collective_bytes_counter_and_compression_ratio(metrics_runtime):
    """The GL006-named transport metrics appear at /metrics, and the
    byte counters prove int8 moves >=3.5x fewer payload bytes than fp32
    for the same gradient tensor (acceptance criterion). Deltas, not
    absolutes: the driver-side registry outlives ray_tpu.shutdown(), so
    earlier tests' collectives are already in the counters."""
    workers = _spawn_group(WORLD, group="mgrp")
    base = _scrape_text(metrics_runtime.dashboard_url)
    ray_tpu.get([w.bytes_for.remote(None) for w in workers])
    mid = _scrape_text(metrics_runtime.dashboard_url)
    ray_tpu.get([w.bytes_for.remote("int8") for w in workers])
    ray_tpu.get([w.destroy.remote() for w in workers])
    body = _scrape_text(metrics_runtime.dashboard_url)

    fp32_bytes = (_bytes_series_sum(mid, "float32")
                  - _bytes_series_sum(base, "float32"))
    int8_bytes = (_bytes_series_sum(body, "int8")
                  - _bytes_series_sum(mid, "int8"))
    assert fp32_bytes > 0, f"no fp32 byte series in:\n{body[:2000]}"
    assert int8_bytes > 0
    assert fp32_bytes / int8_bytes >= 3.5, (
        f"int8 only moved {fp32_bytes / int8_bytes:.2f}x fewer bytes")
    # the ratio gauge is exported and agrees
    gauges = [
        float(line.rsplit(" ", 1)[1])
        for line in body.splitlines()
        if line.startswith("ray_tpu_train_collective_compression_ratio")
        and 'dtype="int8"' in line
    ]
    assert gauges and max(gauges) >= 3.5


@pytest.mark.watchdog(300)
def test_trainer_zero1_flags_plumbed(ray_start_regular, tmp_path):
    """ScalingConfig(grad_compression=..., zero1=...) reaches the
    TrainContext; make_optimizer picks Zero1Optimizer and synced
    updates keep ranks identical."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def train_loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        import ray_tpu.train as train
        from ray_tpu.train.collective import (Zero1Optimizer,
                                              make_optimizer)

        ctx = train.get_context()
        assert ctx.grad_compression == "int8"
        assert ctx.zero1 is True
        params = {"w": np.linspace(-1, 1, 600,
                                   dtype=np.float32).reshape(20, 30)}
        stepper = make_optimizer(optax.adam(0.05), params)
        assert isinstance(stepper, Zero1Optimizer)
        rng = np.random.default_rng(ctx.world_rank)
        for _ in range(3):
            grads = {"w": rng.standard_normal((20, 30))
                     .astype(np.float32)}
            params = stepper.step(params, grads)
        checksum = float(np.sum(np.asarray(params["w"])))
        train.report({"checksum": checksum,
                      "opt_bytes": stepper.optimizer_state_bytes()})

    trainer = JaxTrainer(
        train_loop,
        scaling_config=ScalingConfig(num_workers=2,
                                     grad_compression="int8",
                                     zero1=True),
        run_config=RunConfig(name="zero1_test",
                             storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    checksums = {
        reports[-1][0]["checksum"] for reports in result.all_reports}
    assert len(checksums) == 1, "ranks diverged under zero1"


_MULTIDEVICE_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from ray_tpu.parallel import collective as C

    assert jax.device_count() == 8, jax.devices()
    mesh = Mesh(np.array(jax.devices()), ("d",))
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((8, 1000)).astype(np.float32)
    truth = xs.sum(0)

    def run(fn, *args):
        specs = tuple(P("d") for _ in args)
        return np.asarray(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                        out_specs=P("d"),
                                        check_vma=False)(*args))

    out = run(lambda x: C.quantized_psum(x, "d", dtype="int8"), xs)
    rel = np.abs(out[0] - truth).max() / np.abs(truth).max()
    assert rel < 0.02, f"int8 psum rel {rel}"
    assert (out == out[0]).all(), "replicas disagree"

    out8 = run(lambda x: C.quantized_psum(x, "d", dtype="fp8"), xs)
    rel8 = np.abs(out8[0] - truth).max() / np.abs(truth).max()
    assert rel8 < 0.1, f"fp8 psum rel {rel8}"

    # the error-feedback pair returns the residual of THIS round
    def ef(x, e):
        return C.quantized_psum(x, "d", dtype="int8", error=e)[1]
    res = run(ef, xs, np.zeros_like(xs))
    assert res.shape == xs.shape
    assert np.abs(res).max() > 0  # quantization error is nonzero

    # quantized reduce-scatter: shards concatenate to the sum
    ys = rng.standard_normal((8, 4096)).astype(np.float32)
    t2 = ys.sum(0)
    sh = run(lambda y: C.quantized_reduce_scatter(
        y.reshape(-1), "d", dtype="int8"), ys).reshape(-1)
    rel2 = np.abs(sh - t2).max() / np.abs(t2).max()
    assert rel2 < 0.02, f"qrs rel {rel2}"
    print("MULTIDEVICE_OK")
""")


@pytest.mark.multidevice
@pytest.mark.watchdog(300)
def test_jit_quantized_collectives_eight_devices():
    """jit-side quantized_psum / quantized_reduce_scatter numerics on a
    forced 8-device CPU backend — in a SUBPROCESS (cpu_mesh_env(8)) so
    the tier-1 process's own JAX backend is never reconfigured."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from __graft_entry__ import cpu_mesh_env
    proc = subprocess.run(
        [sys.executable, "-c", _MULTIDEVICE_SCRIPT],
        env=cpu_mesh_env(8), capture_output=True, text=True,
        timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout[-2000:]
                                  + proc.stderr[-2000:])
    assert "MULTIDEVICE_OK" in proc.stdout
