"""Fused causal flash attention — streaming Pallas TPU kernels.

Forward: online-softmax accumulation over K/V tiles (FlashAttention
algorithm) with a (batch, head, q-block, k-block) grid — VMEM stays
bounded at any sequence length, the [S, S] score matrix never touches
HBM, and causally-masked K blocks are skipped (their compute is
predicated off and their DMAs elided by clamping the block index map to
the last valid block, so Mosaic's pipeline sees a repeated index and
re-uses the buffer).

Backward: fused dq and dk/dv kernels using the saved logsumexp and the
precomputed delta = rowsum(dO * O) — no score-matrix materialization in
the backward either, which is where the naive VJP loses (a
[B, H, S, S] f32 tensor per layer is HBM-bandwidth death at seq 2048+).

All matmuls run with bf16 inputs and f32 accumulation
(preferred_element_type) — the MXU's native mode; softmax statistics
stay f32.

Reference analog: the reference has no in-tree attention kernels (it
delegates to vLLM/torch, SURVEY.md §5.7); this is the TPU-native
equivalent the blueprint commits to.

Layout: [batch, seq, heads, head_dim] (GQA supported by repeating K/V
heads upstream in the model).

Decode (``decode_attention``): one query position a slot against the
serving cache as it is stored, [layers, slots, rows, kv_heads,
head_dim]. One kernel takes the stacked cache in HBM, the layer index
and the slots' positions as scalar prefetch, and fetches of each slot
only the blocks that hold a visible row; the XLA form it replaced
stays as the reference and the path of uncovered shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.accelerators import jax_backend
from ray_tpu.parallel.mesh import mesh_axes, mesh_axis_size

NEG_INF = -1e30

# Default tile sizes; shrunk to fit when seq is smaller. 128-multiples
# keep every matmul MXU-aligned. 256x512 measured ~4x faster than
# 512x512 on v5e (the [bq, bk] f32 score tile plus double-buffered
# operands stays within VMEM without spilling).
_BLOCK_Q = 256
_BLOCK_K = 512
# Run kernels in interpreter mode (CPU testing); toggled by tests.
_INTERPRET = False
# Shapes for which flash attention was asked for on a TPU and the
# kernels do not cover them, so the O(S^2) reference ran instead: one
# "q[b,sq,h,d] k[sk]" entry per trace. Callers that must not run the
# slow path (chip_smoke, engine.stats()) read it.
kernel_fallbacks: list = []
# jax.ad_checkpoint names of the two residuals that only the forward
# kernel produces. A caller's jax.checkpoint policy that lists them
# (models/llama.py REMAT_SAVED) keeps both, and the backward pass then
# does not run the forward kernel a second time; with no such policy
# the names do nothing.
SAVED_OUT = "attn_out"   # kernel layout [B, H, S, D], as _bwd wants it
SAVED_LSE = "attn_lse"   # [B, H, S, 1] float32


def _block_size(pref: int, dim: int) -> Optional[int]:
    """Largest 128-multiple block <= pref that tiles `dim` exactly."""
    for cand in (pref, 256, 128):
        if cand <= dim and dim % cand == 0:
            return cand
    return None


def _attention_reference(q, k, v, causal: bool):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), sk - sq)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)



# --- shared causal-geometry helpers (keep forward/backward in sync) ----

def _causal_live(qi, ki, block_q: int, block_k: int, offset: int):
    """Whether the (qi, ki) tile touches the causal lower triangle."""
    return (qi + 1) * block_q - 1 + offset >= ki * block_k


def _causal_mask(s, qi, ki, block_q: int, block_k: int, offset: int):
    """NEG_INF-mask score tile entries above the causal diagonal."""
    q_pos = qi * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _clamped_kv_index(causal: bool, block_q: int, block_k: int,
                      offset: int, nk: int):
    """KV block index map: past-diagonal fetches clamp to the last live
    block, so Mosaic sees a repeated index and elides the DMA."""
    def index(bi, hi, qi, ki):
        if causal:
            last = jnp.minimum(
                ((qi + 1) * block_q - 1 + offset) // block_k, nk - 1)
            ki = jnp.minimum(ki, last)
        return (bi, hi, ki, 0)
    return index


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal: bool, sm_scale: float, block_q: int,
                block_k: int, offset: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (_causal_live(qi, ki, block_q, block_k, offset) if causal
           else ki >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                                   # [bq, d] bf16
        k = k_ref[0, 0]                                   # [bk, d] bf16
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        m_prev = m_scr[...]                               # [bq, 128]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)                # broadcast
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])     # [bq, 1]
        p = jnp.exp(s - m_new[:, :1])                     # [bq, bk] f32
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha
        acc += jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, :1], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked row guard
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)          # [bq, 1]


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int):
    """q,k,v: [B, H, S, D] -> (o [B, H, Sq, D], lse [B, H, Sq, 1] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    offset = sk - sq
    nq, nk = sq // block_q, sk // block_k
    grid = (b, h, nq, nk)

    kv_index = _clamped_kv_index(causal, block_q, block_k, offset, nk)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=d ** -0.5,
        block_q=block_q, block_k=block_k, offset=offset)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            # trailing dim of 1 satisfies the (8, 128) tile rule via
            # the block-equals-array-dim escape hatch, without the 128x
            # lane padding the official kernel pays for its lse output
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),     # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_INTERPRET,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal: bool, sm_scale: float, block_q: int,
               block_k: int, offset: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (_causal_live(qi, ki, block_q, block_k, offset) if causal
           else ki >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        p = jnp.exp(s - lse_ref[0, 0])                     # [bq, bk]
        do = do_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                sm_scale: float, block_q: int, block_k: int, offset: int):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (_causal_live(qi, ki, block_q, block_k, offset) if causal
           else qi >= 0)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                                    # [bq, d]
        k = k_ref[0, 0]                                    # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        p = jnp.exp(s - lse_ref[0, 0])                     # [bq, bk]
        do = do_ref[0, 0]                                  # [bq, d]
        # dv += p^T @ do
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * sm_scale
        # dk += ds^T @ q
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal: bool, block_q: int,
                    block_k: int):
    """All tensors [B, H, S, D] (lse/delta [B, H, S]); returns dq/dk/dv."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[2]
    offset = sk - sq
    nq, nk = sq // block_q, sk // block_k
    sm_scale = d ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B,H,Sq,1]

    q_idx = lambda bi, hi, qi, ki: (bi, hi, qi, 0)

    kv_idx = _clamped_kv_index(causal, block_q, block_k, offset, nk)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_idx),
            pl.BlockSpec((1, 1, block_k, d), kv_idx),
            pl.BlockSpec((1, 1, block_k, d), kv_idx),
            pl.BlockSpec((1, 1, block_q, d), q_idx),
            pl.BlockSpec((1, 1, block_q, 1), q_idx),
            pl.BlockSpec((1, 1, block_q, 1), q_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_idx),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_INTERPRET,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: iterate q blocks innermost for each k block. For causal,
    # early (fully-masked) q blocks clamp forward to the first live one.
    def q_idx_b(bi, hi, ki, qi):
        if causal:
            first = jnp.maximum((ki * block_k - offset) // block_q, 0)
            qi = jnp.maximum(qi, first)
        return (bi, hi, qi, 0)

    kv_idx_b = lambda bi, hi, ki, qi: (bi, hi, ki, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_idx_b),
            pl.BlockSpec((1, 1, block_k, d), kv_idx_b),
            pl.BlockSpec((1, 1, block_k, d), kv_idx_b),
            pl.BlockSpec((1, 1, block_q, d), q_idx_b),
            pl.BlockSpec((1, 1, block_q, 1), q_idx_b),
            pl.BlockSpec((1, 1, block_q, 1), q_idx_b),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), kv_idx_b),
            pl.BlockSpec((1, 1, block_k, d), kv_idx_b),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_INTERPRET,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------

def _kernel_plan(q, k):
    """(block_q, block_k) if the kernels cover these shapes, else None."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if not (_INTERPRET or jax_backend.on_tpu()):
        return None
    bq = _block_size(_BLOCK_Q, sq)
    bk = _block_size(_BLOCK_K, sk)
    if d % 128 or bq is None or bk is None:
        return None
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, causal: bool):
    plan = _kernel_plan(q, k)
    if plan is None:
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"q{list(q.shape)} k[{k.shape[1]}] {q.dtype}")
        return _attention_reference(q, k, v, causal)
    # Kernel layout is [B, H, S, D] so the tiled (second-to-last, last)
    # dims are (seq, head_dim) — the MXU-friendly orientation. XLA fuses
    # the transposes into the surrounding projections.
    out, _ = _flash_forward(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal, *plan)
    return out.transpose(0, 2, 1, 3)


def _fwd(q, k, v, causal):
    plan = _kernel_plan(q, k)
    if plan is None:
        out = checkpoint_name(_flash(q, k, v, causal), SAVED_OUT)
        return out, (q, k, v, None, None)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _flash_forward(qt, kt, vt, causal, *plan)
    out = checkpoint_name(out, SAVED_OUT)
    lse = checkpoint_name(lse, SAVED_LSE)
    return out.transpose(0, 2, 1, 3), (q, k, v, out, lse)


def _bwd(causal, res, g):
    q, k, v, out, lse = res
    plan = _kernel_plan(q, k)
    if plan is None or out is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _attention_reference(q_, k_, v_, causal),
            q, k, v)
        return vjp(g)
    # `out` was saved in kernel layout [B, H, S, D] by _fwd.
    dq, dk, dv = _flash_backward(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), out, lse,
        g.transpose(0, 2, 1, 3), causal, *plan)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal: bool = True, mesh=None):
    """Fused causal attention: [B, S, H, D] x3 -> [B, S, H, D].

    K/V head count must equal Q head count (expand GQA groups first).
    With a ``mesh`` of more than one device the op runs per shard under
    ``jax.shard_map`` (a Mosaic kernel cannot be partitioned by GSPMD):
    batch over (data, fsdp), heads over model, sequence and head_dim
    whole. A sequence-sharded mesh needs ring/ulysses attention."""
    if mesh is None or mesh.size == 1:
        return _flash(q, k, v, causal)
    batch, n_batch = mesh_axes(mesh, "data", "fsdp")
    heads, n_heads = mesh_axes(mesh, "model")
    spec = P(batch, None, heads, None)
    if (mesh_axis_size(mesh, "seq") > 1 or q.shape[0] % n_batch
            or q.shape[2] % n_heads):
        raise ValueError(
            f"flash_attention under a mesh shards q/k/v as {spec} over "
            f"{dict(mesh.shape)}; shape {q.shape} does not divide, or "
            "the sequence is sharded (use attention=\"ring\" or "
            "\"ulysses\")")
    return jax.shard_map(
        lambda q_, k_, v_: _flash(q_, k_, v_, causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Decode attention: one query position a slot, over the cache as stored
# ---------------------------------------------------------------------------

def _decode_attention_reference(q, cache_k, cache_v, layer, pos, dtype):
    """The plain XLA form of ``decode_attention``: the layer sliced out
    of the stacked cache, every one of its S rows scored and the rows
    above ``pos`` masked. What the kernel is tested against, and the
    path of shapes and devices it does not cover."""
    k = jax.lax.dynamic_index_in_dim(cache_k, layer, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache_v, layer, keepdims=False)
    visible = jnp.arange(k.shape[1])[None, :] <= pos[:, None]    # [B, S]
    scores = jnp.einsum("bgrd,bsgd->bgrs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    scores = jnp.where(visible[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bgrs,bsgd->bgrd", weights, v)


def decode_block_rows(s: int, kvh: int, hd: int) -> Optional[int]:
    """Positions one block of the decode kernel holds for a cache of
    ``s`` rows, ``kvh`` KV heads of ``hd``: a slot at position ``p``
    has rows ``[0, (p // block + 1) * block)`` read and no other. None
    where the kernel does not cover the shape or the backend, and the
    reference reads all ``s`` rows. About 256 KiB of bf16 a block for 8
    KV heads (1024 cache rows of 128 lanes); never under 128 positions,
    so one KV head gets 512."""
    if not (_INTERPRET or jax_backend.on_tpu()):
        return None
    block = min(s, max(128, 512 // kvh))
    if hd % 128 or s % block or (block * kvh) % 128:
        return None
    return block


def _decode_kernel(layer_ref, pos_ref, q_ref, at_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, seen_ref, *, block: int, kvh: int,
                   sm_scale: float):
    """One grid step a slot. The slot's live blocks (those that hold a
    row <= pos) come from the stacked cache in HBM by DMA, two buffers
    deep, the next slot's first block started under this slot's last;
    ``seen_ref`` counts the blocks so far, whose parity is the buffer.
    A cache row is (position, KV head), heads fastest: all the query
    heads meet all of a block's rows in one product, and ``at_ref``
    ([heads, rows] int32) holds a row's position for the query heads
    of its KV head and a number past any position for the others."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    rows = block * kvh
    pos = pos_ref[b]
    n_live = pos // block + 1

    def copies(slot, j, buf):
        start = pl.multiple_of(j * rows, rows)
        return [pltpu.make_async_copy(
            hbm.at[layer, slot, pl.ds(start, rows)], vmem.at[buf],
            sems.at[i, buf])
            for i, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf)))]

    def start(slot, j, buf):
        for copy in copies(slot, j, buf):
            copy.start()

    @pl.when(b == 0)
    def _first():
        seen_ref[0] = 0
        start(0, 0, 0)

    seen = seen_ref[0]
    q = q_ref[0]                                          # [H, HD]
    at = at_ref[...]                                      # [H, rows]
    heads, hd = q.shape

    def body(j, carry):
        m, l, acc = carry
        buf = (seen + j) % 2

        @pl.when(j + 1 < n_live)
        def _next_block():
            start(b, j + 1, 1 - buf)

        @pl.when(jnp.logical_and(j + 1 == n_live, b + 1 < n_slots))
        def _next_slot():
            start(b + 1, 0, 1 - buf)

        for copy in copies(b, j, buf):
            copy.wait()
        k = k_buf[buf]                                    # [rows, HD]
        v = v_buf[buf]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(at <= pos - j * block, s, NEG_INF)  # [H, rows]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_live, body,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, hd), jnp.float32)))
    seen_ref[0] = seen + n_live
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _decode_pallas(q, cache_k, cache_v, layer, pos, dtype, block: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, n_rep, hd = q.shape
    n_layers, _, s = cache_k.shape[:3]
    n_heads = kvh * n_rep
    # whole sublane tiles of query heads; a padded head is a zero query
    # of the last KV head, computed and dropped
    heads = -(-n_heads // 16) * 16
    rows = block * kvh
    qh = jnp.pad(q.reshape(b, n_heads, hd),
                 ((0, 0), (0, heads - n_heads), (0, 0)))
    head_kv = np.minimum(np.arange(heads) // n_rep, kvh - 1)[:, None]
    row = np.arange(rows)[None, :]
    at = np.where(row % kvh == head_kv, row // kvh,
                  np.iinfo(np.int32).max).astype(np.int32)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, kvh=kvh,
                          sm_scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, hd), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((heads, rows), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, hd), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, hd), cache_k.dtype),
                pltpu.VMEM((2, rows, hd), cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, hd), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET,
        name="decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32),
      qh, at,
      # rows of (position, KV head): the stored bytes in their order
      cache_k.reshape(n_layers, b, s * kvh, hd),
      cache_v.reshape(n_layers, b, s * kvh, hd))
    return out[:, :n_heads].reshape(b, kvh, n_rep, hd)


def decode_attention(q, cache_k, cache_v, layer, pos, dtype):
    """One query position for every slot against the cache as it is
    stored. q: [B, KVH, n_rep, HD], the query heads grouped by the KV
    head they share (n_rep == 1: groups of one); cache_k, cache_v: the
    STACKED caches [L, B, S, KVH, HD] with this step's row already
    written; ``layer`` (an int, traced or not) the one to attend; pos:
    [B] int32, slot ``b`` sees rows ``t <= pos[b]``. -> [B, KVH, n_rep,
    HD] in ``dtype``. No K or V is expanded to the query's heads and no
    layer is sliced out of the stack.

    On a TPU (and in the tests' interpret mode), where ``HD % 128 ==
    0``, S divides into ``decode_block_rows`` and q has the caches'
    dtype, one Pallas kernel reads layer ``layer`` in place, and of each
    slot only the blocks that hold a visible row: the decode programs
    of every model family share it. It needs every ``pos`` in ``[0, S -
    1]``, the program's caches donated (or XLA copies them for every
    caller, kernel or not) and the program on one device (a Mosaic
    kernel cannot be partitioned, as ``rms_norm`` beside it cannot).
    Everything else takes ``_decode_attention_reference``, on a TPU
    with an entry in ``kernel_fallbacks``."""
    _, kvh, _, hd = q.shape
    block = decode_block_rows(cache_k.shape[2], kvh, hd)
    if block is None or not (q.dtype == cache_k.dtype == cache_v.dtype):
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"decode q{list(q.shape)} {q.dtype} "
                f"cache{list(cache_k.shape)} {cache_k.dtype}")
        return _decode_attention_reference(q, cache_k, cache_v, layer, pos,
                                           dtype)
    return _decode_pallas(q, cache_k, cache_v, layer, pos, dtype, block)
