"""Sharding, ring attention, Ulysses, pipeline tests on the 8-device
CPU mesh (SURVEY.md §7: testing without TPUs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import MeshSpec, make_mesh, mesh_axis_size
from ray_tpu.parallel.pipeline import pipeline
from ray_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
    ulysses_attention,
)

from ray_tpu.parallel.sharding import (
    ShardingConfig,
    ShardingRules,
    infer_sharding,
    shard_pytree,
)


def test_mesh_spec():
    spec = MeshSpec.for_devices(8, model=2)
    assert spec.data == 4 and spec.model == 2 and spec.size == 8
    mesh = make_mesh(spec)
    assert mesh_axis_size(mesh, "model") == 2
    assert mesh_axis_size(mesh, "data") == 4


def test_sharding_rules_match():
    rules = ShardingRules(rules=[
        (r"dense/kernel", P("fsdp", "model")),
        (r".*", P()),
    ])
    assert rules.spec_for("model/dense/kernel", 2) == P("fsdp", "model")
    assert rules.spec_for("model/bias", 1) == P()
    # Spec longer than ndim gets truncated.
    assert rules.spec_for("dense/kernel", 1) == P("fsdp")


def test_shard_pytree_places_shards(cpu_mesh8):
    mesh = make_mesh(MeshSpec(data=2, model=4), cpu_mesh8)
    tree = {"dense": {"kernel": jnp.ones((8, 16)), "bias": jnp.ones(16)}}
    rules = ShardingConfig(mode="tp").rules()
    # generic tp rules don't match "kernel"; use explicit rules
    rules = ShardingRules(rules=[(r"kernel", P(None, "model")),
                                 (r".*", P())])
    sharded = shard_pytree(tree, mesh, rules)
    assert sharded["dense"]["kernel"].sharding.spec == P(None, "model")


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh(MeshSpec(seq=4, data=2))
    B, S, H, D = 2, 64, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    ref = reference_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_matches_reference():
    mesh = make_mesh(MeshSpec(seq=4, data=2))
    B, S, H, D = 2, 64, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    ref = reference_attention(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_sharded_inputs():
    """Ring attention with inputs actually sharded over seq."""
    mesh = make_mesh(MeshSpec(seq=8))
    B, S, H, D = 1, 128, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    sharding = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))(qs, ks, vs)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential():
    mesh = make_mesh(MeshSpec(pipe=4, data=2))
    n_stages, d = 4, 32
    w = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.1
    params = {"w": w}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.PRNGKey(1), (16, d))
    ref = x
    for i in range(n_stages):
        ref = stage_fn({"w": w[i]}, ref)
    out = pipeline(stage_fn, params, x, mesh, num_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_rejects_bad_microbatch():
    mesh = make_mesh(MeshSpec(pipe=4, data=2))
    params = {"w": jnp.zeros((4, 8, 8))}
    x = jnp.zeros((10, 8))
    with pytest.raises(ValueError, match="divisible"):
        pipeline(lambda p, x: x, params, x, mesh, num_microbatches=4)


def test_pipeline_multi_round_and_grad():
    """More microbatches than stages (R=3 rounds of the sharded input
    stream) and gradient flow with remat."""
    mesh = make_mesh(MeshSpec(pipe=4, data=2))
    n_stages, d = 4, 16
    w = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.1
    params = {"w": w}

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.PRNGKey(1), (24, d))
    ref = x
    for i in range(n_stages):
        ref = stage_fn({"w": w[i]}, ref)
    out = pipeline(stage_fn, params, x, mesh, num_microbatches=12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    def loss(p, x):
        return jnp.sum(pipeline(stage_fn, p, x, mesh,
                                num_microbatches=12, remat=True) ** 2)

    def ref_loss(p, x):
        h = x
        for i in range(n_stages):
            h = stage_fn({"w": p["w"][i]}, h)
        return jnp.sum(h ** 2)

    g = jax.grad(loss)(params, x)
    g_ref = jax.grad(ref_loss)(params, x)
    np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(g_ref["w"]),
                               atol=2e-4, rtol=2e-4)


def test_pipeline_rejects_uneven_stage_split():
    mesh = make_mesh(MeshSpec(pipe=4, data=2))
    params = {"w": jnp.zeros((4, 8, 8))}
    x = jnp.zeros((12, 8))
    with pytest.raises(ValueError, match="pipe size"):
        pipeline(lambda p, x: x, params, x, mesh, num_microbatches=6)
