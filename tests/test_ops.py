"""Kernel correctness vs jnp references: the CPU fallback paths and the
Pallas kernels in interpreter mode (chip_smoke.py checks the compiled
kernels on a TPU; tests/test_tpu_compile.py compiles them for one)."""

import types

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


@pytest.fixture
def flash_interpreted(monkeypatch):
    from ray_tpu.ops import attention as att
    monkeypatch.setattr(att, "_INTERPRET", True)
    return att


def _assert_flash_matches_reference(att, sq, sk, heads, causal):
    """The Pallas kernels (forward + both backward) in interpreter mode
    against the jnp reference: outputs and all three gradients."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, sq, heads, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, sk, heads, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, sk, heads, 128), jnp.float32)
    assert att._kernel_plan(q, k) is not None
    out = att.flash_attention(q, k, v, causal)
    ref = att._attention_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)

    def loss_k(q, k, v):
        return jnp.sum(att.flash_attention(q, k, v, causal) * 0.1)

    def loss_r(q, k, v):
        return jnp.sum(att._attention_reference(q, k, v, causal) * 0.1)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


# What the cells and the other callers run, one case each. The train
# cells' 2048 and the prefill buckets 128-1024 at the Mistral and Granite
# cells' 32 heads, Jamba's 20; keys past the queries with an offset of
# one and a half key tiles (256 -> 1024) and with tiles of 128 (a
# sequence no 256 divides); no mask (dit, vit); and, with the three sizes
# shrunk, a sequence of several major blocks: the statistics cross grid
# steps through the scratch, and the steps wholly above the diagonal
# clamp their block index and visit nothing.
_SEVERAL_MAJORS = {"_BLOCK": 128, "_TILE": 128, "_MAJOR": 256}


@pytest.mark.parametrize("sq, sk, heads, causal, sizes", [
    (256, 256, 2, True, None), (256, 512, 2, True, None),
    (128, 128, 32, True, None), (256, 256, 32, True, None),
    (512, 512, 32, True, None), (1024, 1024, 32, True, None),
    (2048, 2048, 32, True, None), (128, 128, 20, True, None),
    (512, 512, 20, True, None), (256, 1024, 1, True, None),
    (384, 640, 1, True, None), (384, 384, 1, True, None),
    (512, 512, 1, False, None), (256, 768, 2, False, None),
    (512, 768, 1, True, _SEVERAL_MAJORS),
    (512, 512, 1, False, _SEVERAL_MAJORS)])
def test_flash_kernels_interpret_vs_reference(flash_interpreted, monkeypatch,
                                              sq, sk, heads, causal, sizes):
    att = flash_interpreted
    for name, rows in (sizes or {}).items():
        monkeypatch.setattr(att, name, rows)
    plan = att._kernel_plan(jnp.zeros((1, sq, heads, 128)),
                            jnp.zeros((1, sk, heads, 128)))
    if sizes:
        assert sq // plan.major_q > 1 and sk // plan.major_k > 1
    if (sq, sk) == (256, 1024):
        assert (sk - sq) % plan.tile_k
    _assert_flash_matches_reference(att, sq, sk, heads, causal)


def _schedule(att, sq, sk, causal):
    plan = att._kernel_plan(jnp.zeros((1, sq, 1, 128)),
                            jnp.zeros((1, sk, 1, 128)))
    return plan, att.tile_schedule(sq, sk, causal, plan)


@pytest.mark.parametrize("sq, sk, causal, sizes, dead_steps", [
    (128, 128, True, None, False), (512, 512, True, None, False),
    (1024, 1024, True, None, False), (2048, 2048, True, None, False),
    (256, 1024, True, None, False), (384, 640, True, None, False),
    (4096, 4096, True, None, True), (512, 512, False, None, False),
    (256, 768, False, None, False),
    (512, 768, True, _SEVERAL_MAJORS, True),
    (512, 512, False, _SEVERAL_MAJORS, False)])
def test_flash_schedule_covers_the_triangle_once(flash_interpreted,
                                                 monkeypatch, sq, sk, causal,
                                                 sizes, dead_steps):
    """Each walk visits every visible (query, key) pair in exactly one
    tile, no tile that is wholly masked, and masks exactly the tiles
    the diagonal crosses; a grid step that visits nothing exists only
    under a mask once the walked operand takes several major blocks."""
    for name, rows in (sizes or {}).items():
        monkeypatch.setattr(flash_interpreted, name, rows)
    _, schedule = _schedule(flash_interpreted, sq, sk, causal)
    assert (schedule["dead_steps"] > 0) == dead_steps
    rows = np.arange(sq)[:, None]
    cols = np.arange(sk)[None, :]
    visible = (cols <= rows + (sk - sq)) if causal else np.ones(
        (sq, sk), bool)
    for walk in ("by_query", "by_key"):
        seen = np.zeros((sq, sk), int)
        for q0, k0, n_q, n_k, masked in schedule[walk]:
            tile = visible[q0:q0 + n_q, k0:k0 + n_k]
            assert tile.any(), (walk, q0, k0)
            assert masked == (not tile.all()), (walk, q0, k0)
            seen[q0:q0 + n_q, k0:k0 + n_k] += 1
        assert seen.max() == 1
        assert (seen[visible] == 1).all()


def test_flash_schedule_at_the_train_cells_shape(flash_interpreted):
    """At 2048 x 2048: no grid step above the diagonal (the grid it
    replaced predicated off 12 of a head's 32), a mask on the four
    tiles the diagonal crosses and on no other (it masked all 20 live
    ones), and the scores computed above the diagonal no more than the
    block's share of the sequence."""
    plan, schedule = _schedule(flash_interpreted, 2048, 2048, True)
    assert (plan.major_q, plan.major_k) == (2048, 2048)
    assert schedule["dead_steps"] == 0
    for walk in ("by_query", "by_key"):
        tiles = schedule[walk]
        assert sum(masked for *_, masked in tiles) == 2048 // plan.block_q
        computed = sum(n_q * n_k for _, _, n_q, n_k, _ in tiles)
        owed = 2048 * 2049 // 2
        assert computed <= owed * (1 + plan.block_q / 2048)
    # ten tiles of 512 x 512 where the grid it replaced ran twenty of
    # 256 x 512 for the same triangle
    assert len(schedule["by_query"]) == len(schedule["by_key"]) == 10


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


_SSD_SHAPE = (3, 32, 128, 8, 128)      # [M, B, H, P, N]: 32 slots a layer
_SSD_LIVE = {
    "all": np.ones(32, bool),
    # parked slots first, last and between live ones
    "some": np.asarray([0, 0, 1, 1, 0, 1, 1, 1] * 3 + [1, 0, 1, 1, 0, 1, 0, 0],
                       bool),
    "none": np.zeros(32, bool),
}


def _ssd_steps(update, ssm, layer, live, steps):
    """``steps`` consecutive updates of one layer on random inputs ->
    (the stack after them, every step's y [steps, B, H, P])."""
    _, b, h, p, n = ssm.shape

    def body(ssm, key):
        ks = jax.random.split(key, 5)
        return update(
            ssm, layer, live,
            jax.random.uniform(ks[0], (b, h), jnp.float32, 0.5, 1.0),
            jax.random.normal(ks[1], (b, h, p)),
            jax.random.normal(ks[2], (b, n)), jax.random.normal(ks[3], (b, n)),
            jax.random.normal(ks[4], (b, h, p)))

    return jax.lax.scan(body, ssm,
                        jax.random.split(jax.random.PRNGKey(1), steps))


@pytest.mark.parametrize("live,layer,block", [
    (live, layer, 32) for live in _SSD_LIVE for layer in (0, 1, 2)
] + [("some", 1, 128), ("some", 2, 16), ("all", 0, 128)])
def test_ssd_update_kernel_matches_the_jnp_form_over_64_steps(
        monkeypatch, live, layer, block):
    # the decode step's recurrence in one pass (interpret mode) against
    # the jax.numpy lines it replaced, both float32: 64 steps on end,
    # by how many heads a grid step owns (128: a slot is one block)
    from ray_tpu.ops import ssd_update as op

    monkeypatch.setattr(op, "_INTERPRET", True)
    monkeypatch.setattr(op, "_HEADS", block)
    assert op.head_block(*_SSD_SHAPE[2:]) == block
    ssm = jax.random.normal(jax.random.PRNGKey(0), _SSD_SHAPE, jnp.float32)
    lv = _SSD_LIVE[live]
    got, y = jax.jit(lambda s: _ssd_steps(
        op.ssd_update, s, layer, jnp.asarray(lv), 64))(ssm)
    want, y_ref = jax.jit(lambda s: _ssd_steps(
        op._update_reference, s, layer, jnp.asarray(lv), 64))(ssm)
    ssm, got, want = np.asarray(ssm), np.asarray(got), np.asarray(want)
    y, y_ref = np.asarray(y), np.asarray(y_ref)
    assert got.dtype == np.float32 and y.dtype == np.float32
    if lv.any():
        assert (np.abs(got[layer][lv] - want[layer][lv]).max()
                <= 1e-5 * np.abs(want[layer][lv]).max())
        assert (np.abs(y - y_ref).max(axis=(1, 2, 3))
                <= 1e-5 * np.abs(y_ref).max(axis=(1, 2, 3))).all()
    # a parked slot's state is the bytes that went in, its y zero, and
    # no other layer of the stack is touched
    assert (got[layer][~lv] == ssm[layer][~lv]).all()
    assert (y[:, ~lv] == 0).all()
    others = [m for m in range(ssm.shape[0]) if m != layer]
    assert (got[others] == ssm[others]).all()


@pytest.mark.parametrize("live", ["some", "none"])
def test_ssd_update_moves_the_donated_stack_where_it_lies(monkeypatch, live):
    from ray_tpu.ops import ssd_update as op

    monkeypatch.setattr(op, "_INTERPRET", True)
    ssm = jax.random.normal(jax.random.PRNGKey(0), _SSD_SHAPE, jnp.float32)
    where = ssm.unsafe_buffer_pointer()
    new, _ = jax.jit(lambda s: _ssd_steps(
        op.ssd_update, s, 1, jnp.asarray(_SSD_LIVE[live]), 2),
        donate_argnums=0)(ssm)
    assert ssm.is_deleted() and new.unsafe_buffer_pointer() == where


def test_ssd_update_takes_the_jnp_form_where_the_kernel_does_not_engage(
        monkeypatch):
    # off the TPU, and for a head or state size that is no multiple of
    # the tile, the reference runs; the answer is the same mathematics
    from ray_tpu.accelerators import jax_backend
    from ray_tpu.ops import ssd_update as op

    assert op.head_block(128, 64, 128) is None          # the CPU
    monkeypatch.setattr(jax_backend, "on_tpu", lambda: True)
    assert op.head_block(128, 64, 128) == op._HEADS
    for shape in ((8, 16, 16), (80, 64, 128), (128, 64, 96), (128, 12, 128)):
        assert op.head_block(*shape) is None
    ssm = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8, 16, 16))
    live = np.asarray([True, False, True])
    got, y = _ssd_steps(op.ssd_update, ssm, 1, jnp.asarray(live), 1)
    # the one step by hand, on the keys _ssd_steps drew
    ks = jax.random.split(jax.random.split(jax.random.PRNGKey(1), 1)[0], 5)
    da = jax.random.uniform(ks[0], (3, 8), jnp.float32, 0.5, 1.0)
    dtx, dx = (jax.random.normal(k, (3, 8, 16)) for k in (ks[1], ks[4]))
    b, c = (jax.random.normal(k, (3, 16)) for k in (ks[2], ks[3]))
    h = (da[:, :, None, None] * ssm[1]
         + dtx[..., None] * b[:, None, None, :])
    want_y = np.asarray(jnp.einsum("bhpn,bn->bhp", h, c) + dx)
    ssm, got, y, h = (np.asarray(a) for a in (ssm, got, y[0], h))
    np.testing.assert_allclose(got[1][live], h[live], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    assert (got[1][~live] == ssm[1][~live]).all() and (y[~live] == 0).all()
    assert (got[0] == ssm[0]).all()


# --- heads of 64 through the kernels written for 128 lanes ------------

@pytest.mark.parametrize("seq,heads", [(128, 4), (512, 2), (1024, 1)])
def test_flash_attention_with_heads_of_64_matches_the_reference(
        flash_interpreted, seq, heads):
    """A prefill's heads of 64, zero-padded to 128 lanes with the true
    head's scale: the forward kernel against the jnp reference, at a
    bucket of one block and of several tiles."""
    att = flash_interpreted
    q, k, v = (jax.random.normal(key, (1, seq, heads, 64), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(seq), 3))
    assert att._kernel_plan(q, k) is None      # not as they are
    out = jax.jit(lambda q, k, v: att.flash_attention(q, k, v, True))(
        q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(att._attention_reference(q, k, v, True)),
        atol=1e-2)


@pytest.mark.parametrize("kvh,n_rep,dtype", [
    (8, 4, jnp.bfloat16), (2, 1, jnp.float32), (4, 2, jnp.float32)])
def test_decode_attention_over_packed_rows_matches_the_reference(
        flash_interpreted, kvh, n_rep, dtype):
    """A cache of heads of 64 kept two KV heads a row of 128 lanes
    (``cache_row_shape``): the decode kernel reads it as it lies and
    gives what the plain form gives over the unpacked cache, for slots
    at the first row, inside a block and at the last row; without the
    kernel (no TPU, no interpret mode) the same through the reference."""
    att = flash_interpreted
    layers, slots, rows = 2, 3, 512
    assert att.cache_row_shape(kvh, 64) == (kvh // 2, 128)
    assert att.cache_row_shape(kvh, 128) == (kvh, 128)
    assert att.cache_row_shape(3, 64) == (3, 64)      # no pair to make
    assert att.cache_row_shape(4, 16) == (4, 16)
    keys = jax.random.split(jax.random.PRNGKey(kvh), 3)
    ck, cv = (jax.random.normal(key, (layers, slots, rows, kvh, 64),
                                jnp.float32).astype(dtype)
              for key in keys[:2])
    q = jax.random.normal(keys[2], (slots, kvh, n_rep, 64),
                          jnp.float32).astype(dtype)
    pos = jnp.array([0, 200, rows - 1])
    packed = (layers, slots, rows) + att.cache_row_shape(kvh, 64)
    # the kernel is asked about the cache as it is stored
    assert att.decode_block_rows(rows, kvh, 64) is None
    assert att.decode_block_rows(rows, *att.cache_row_shape(kvh, 64)) \
        == att.decode_block_rows(rows, kvh // 2, 128) is not None
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    for layer in range(layers):
        want = att._decode_attention_reference(q, ck, cv, layer, pos, dtype)
        got = jax.jit(att.decode_attention, static_argnums=5)(
            q, ck.reshape(packed), cv.reshape(packed), layer, pos, dtype)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
    att._INTERPRET = False      # the fixture's monkeypatch puts it back
    got = att.decode_attention(q, ck.reshape(packed), cv.reshape(packed), 1,
                               pos, dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-6)


# --- latent attention's two forms through the kernels as they are -----

@pytest.mark.parametrize("seq,heads,dk,dv", [
    (128, 4, 192, 128), (1024, 2, 192, 128), (256, 2, 24, 16)])
def test_flash_attention_with_values_narrower_than_keys(
        flash_interpreted, seq, heads, dk, dv):
    """The expanded form of latent attention: keys of 192 over values of
    128 and a scale of the caller's (``0.1447``, not ``192 ** -0.5``),
    all three zero-padded to 256 lanes; the forward kernel against the
    jnp reference told the same scale, the output as wide as the
    values."""
    att = flash_interpreted
    keys = jax.random.split(jax.random.PRNGKey(seq + dk), 3)
    q, k = (jax.random.normal(key, (1, seq, heads, dk), jnp.float32)
            for key in keys[:2])
    v = jax.random.normal(keys[2], (1, seq, heads, dv), jnp.float32)
    scale = dk ** -0.5 * 2.00474
    out = jax.jit(lambda q, k, v: att.flash_attention(
        q, k, v, True, sm_scale=scale))(q, k, v)
    assert out.shape == v.shape
    want = att._attention_reference(q, k, v, True, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-2)
    # the scale is in the result: the default one gives another
    plain = att._attention_reference(q, k, v, True)
    assert float(jnp.abs(plain - want).max()) > 0.05
    # without the kernel (no TPU, no interpret mode) the plain form
    att._INTERPRET = False      # the fixture's monkeypatch puts it back
    np.testing.assert_allclose(
        np.asarray(att.flash_attention(q, k, v, True, sm_scale=scale)),
        np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError, match="one device"):
        att.flash_attention(q, k, v, True, sm_scale=scale,
                            mesh=types.SimpleNamespace(size=2))


@pytest.mark.parametrize("heads,lanes,dtype", [
    (64, 640, jnp.bfloat16), (4, 128, jnp.float32), (16, 256, jnp.float32)])
def test_decode_attention_with_one_leaf_as_keys_and_values(
        flash_interpreted, heads, lanes, dtype):
    """The absorbed form of latent attention: ONE cache of latent rows
    handed over as the keys and as the values, one "KV head" that all
    the query heads share, a scale of the caller's. The kernel reads
    1024 rows in two blocks of 512 and gives what the plain form gives
    told the same, for slots at the first row, at a block's last row,
    at the next block's first and at the cache's last."""
    att = flash_interpreted
    layers, rows = 2, 1024
    pos = jnp.array([0, 511, 512, rows - 1])
    keys = jax.random.split(jax.random.PRNGKey(heads), 2)
    cache = jax.random.normal(keys[0], (layers, 4, rows, 1, lanes),
                              jnp.float32).astype(dtype)
    q = jax.random.normal(keys[1], (4, 1, heads, lanes),
                          jnp.float32).astype(dtype)
    scale = 0.144680 * (192 / lanes) ** 0.5
    assert att.decode_block_rows(rows, 1, lanes) == 512
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    for layer in range(layers):
        want = att._decode_attention_reference(q, cache, cache, layer, pos,
                                               dtype, scale)
        got = jax.jit(att.decode_attention, static_argnums=(5, 6))(
            q, cache, cache, layer, pos, dtype, scale)
        assert got.shape == q.shape and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
    # the scale is in the result
    plain = att._decode_attention_reference(q, cache, cache, 1, pos, dtype)
    assert float(jnp.abs(plain.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) > 0.05
    att._INTERPRET = False      # the fixture's monkeypatch puts it back
    got = att.decode_attention(q, cache, cache, 1, pos, dtype, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-6)
