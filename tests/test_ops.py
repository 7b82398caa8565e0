"""Kernel correctness vs jnp references: the CPU fallback paths and the
Pallas kernels in interpreter mode (chip_smoke.py checks the compiled
kernels on a TPU; tests/test_tpu_compile.py compiles them for one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import _attention_reference, flash_attention
from ray_tpu.ops.rmsnorm import _rms_norm_reference, rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


def test_flash_attention_cpu_fallback():
    B, S, H, D = 2, 32, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    out = flash_attention(q, k, v, True)
    ref = _attention_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_flash_attention_grad_finite():
    B, S, H, D = 1, 16, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True))

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64,))
    np.testing.assert_allclose(
        np.asarray(rms_norm(x, w)),
        np.asarray(_rms_norm_reference(x, w, 1e-6)), atol=1e-6)


def test_rope_rotation_properties():
    cos, sin = rope_frequencies(16, 64)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 16))
    out = apply_rope(x, cos, sin)
    # Norm-preserving per pair.
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(out, axis=-1)),
        np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)
    # Position 0 is identity.
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)


def test_rope_with_positions():
    cos, sin = rope_frequencies(8, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 1, 8))
    pos = jnp.array([[0, 1, 2, 3], [4, 5, 6, 7]])
    out = apply_rope(x, cos, sin, positions=pos)
    # Batch 0 with default positions == explicit arange positions.
    default = apply_rope(x[:1], cos, sin)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(default[0]),
                               atol=1e-6)


def test_flash_kernels_interpret_vs_reference():
    # Run the actual Pallas kernels (forward + fused backward) in
    # interpreter mode on CPU and compare against the jnp reference.
    from ray_tpu.ops import attention as att

    prev = att._INTERPRET
    att._INTERPRET = True
    try:
        for sq, sk in ((256, 256), (256, 512)):
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            q = jax.random.normal(ks[0], (1, sq, 2, 128), jnp.float32)
            k = jax.random.normal(ks[1], (1, sk, 2, 128), jnp.float32)
            v = jax.random.normal(ks[2], (1, sk, 2, 128), jnp.float32)
            assert att._kernel_plan(q, k) is not None
            out = att.flash_attention(q, k, v, True)
            ref = att._attention_reference(q, k, v, True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-2)

            def loss_k(q, k, v):
                return jnp.sum(att.flash_attention(q, k, v, True) * 0.1)

            def loss_r(q, k, v):
                return jnp.sum(att._attention_reference(q, k, v, True) * 0.1)

            gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gk, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=5e-3)
    finally:
        att._INTERPRET = prev


def test_int8_matmul_kernel_interpret_vs_reference():
    # The weight-only int8 Pallas kernel in interpreter mode vs the
    # dequantized jnp reference (same path hardware uses).
    from ray_tpu.ops import quant_matmul as qm

    prev = qm._INTERPRET
    qm._INTERPRET = True
    try:
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (1024, 1024), jnp.float32) * 0.05
        x = jax.random.normal(key, (5, 1024), jnp.bfloat16)
        w8, scale = qm.quantize_int8(w)
        # quantization itself is sound
        np.testing.assert_allclose(
            np.asarray(w8.astype(jnp.float32) * scale[None, :]),
            np.asarray(w), atol=float(np.max(np.abs(np.asarray(w)))) / 100)
        got = qm.int8_matmul(x, w8, scale, block_n=512, block_k=512)
        ref = x.astype(jnp.float32) @ (w8.astype(jnp.float32)
                                       * scale[None, :])
        rel = (np.max(np.abs(np.asarray(got, np.float32) - np.asarray(ref)))
               / (np.max(np.abs(np.asarray(ref))) + 1e-9))
        assert rel < 2e-2, rel
        # odd batch row counts pad internally and slice back
        assert qm.int8_matmul(x[:1], w8, scale).shape == (1, 1024)
        with pytest.raises(ValueError, match="divide"):
            qm.int8_matmul(x, w8[:, :1000], scale[:1000])
    finally:
        qm._INTERPRET = prev


@pytest.mark.parametrize("rules", ["fsdp", "fsdp_tp"])
def test_kernels_under_a_mesh_match_references(cpu_mesh8, monkeypatch,
                                               rules):
    # flash_attention and rms_norm called with a mesh run per shard
    # under jax.shard_map (a Mosaic kernel cannot be partitioned by
    # GSPMD); here the real kernels in interpreter mode, inputs sharded
    # as each rule set leaves them: batch over (data, fsdp) and, with
    # tensor parallelism, heads over model.
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops import attention as att
    from ray_tpu.ops import rmsnorm as rn
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    monkeypatch.setattr(att, "_INTERPRET", True)
    monkeypatch.setattr(rn, "_INTERPRET", True)
    mesh = make_mesh(MeshSpec(data=2, fsdp=2, model=2), cpu_mesh8)
    heads = "model" if rules == "fsdp_tp" else None
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(kk, (4, 128, 2, 128), jnp.float32)
               for kk in ks[:3])
    sharded = [jax.device_put(a, NamedSharding(
        mesh, P(("data", "fsdp"), None, heads, None))) for a in (q, k, v)]

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * 0.1)

    kern = lambda q, k, v: att.flash_attention(q, k, v, True, mesh)  # noqa: E731
    ref = lambda q, k, v: att._attention_reference(q, k, v, True)  # noqa: E731
    np.testing.assert_allclose(np.asarray(jax.jit(kern)(*sharded)),
                               np.asarray(ref(q, k, v)), atol=1e-2)
    for a, b in zip(
            jax.jit(jax.grad(loss(kern), argnums=(0, 1, 2)))(*sharded),
            jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)

    x = jax.random.normal(ks[3], (4, 128, 256), jnp.float32)
    w = jax.random.normal(ks[4], (256,), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(("data", "fsdp"))))
    norm = lambda x, w: rn.rms_norm(x, w, 1e-5, mesh)  # noqa: E731
    norm_ref = lambda x, w: rn._rms_norm_reference(x, w, 1e-5)  # noqa: E731
    np.testing.assert_allclose(np.asarray(jax.jit(norm)(xs, w)),
                               np.asarray(norm_ref(x, w)), atol=1e-5)
    # the weight is replicated: its gradient sums over every shard
    for a, b in zip(
            jax.jit(jax.grad(lambda x, w: jnp.sum(norm(x, w) ** 2),
                             argnums=(0, 1)))(xs, w),
            jax.grad(lambda x, w: jnp.sum(norm_ref(x, w) ** 2),
                     argnums=(0, 1))(x, w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_kernels_under_a_mesh_reject_uncovered_layouts(cpu_mesh8):
    from ray_tpu.ops.rmsnorm import rms_norm as rms
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    q = jnp.zeros((4, 128, 2, 128))
    with pytest.raises(ValueError, match="sequence is sharded"):
        flash_attention(q, q, q, True,
                        make_mesh(MeshSpec(data=4, seq=2), cpu_mesh8))
    mesh = make_mesh(MeshSpec(data=8), cpu_mesh8)
    with pytest.raises(ValueError, match="does not divide"):
        flash_attention(q, q, q, True, mesh)
    with pytest.raises(ValueError, match="does not divide"):
        rms(jnp.zeros((4, 128, 256)), jnp.ones((256,)), 1e-5, mesh)


def test_flash_fallback_on_a_tpu_is_counted(monkeypatch):
    # on a TPU a shape the kernels do not cover (head_dim 16) still
    # computes, through the O(S^2) reference, and says so
    from ray_tpu.accelerators import jax_backend
    from ray_tpu.ops import attention as att

    monkeypatch.setattr(jax_backend, "on_tpu", lambda: True)
    monkeypatch.setattr(att, "kernel_fallbacks", [])
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 16))
    out = att.flash_attention(q, q, q, True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(att._attention_reference(q, q, q, True)),
        atol=1e-6)
    assert att.kernel_fallbacks == ["q[1, 128, 2, 16] k[128] float32"]
