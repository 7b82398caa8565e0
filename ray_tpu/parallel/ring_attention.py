"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no in-tree long-context support (SURVEY.md §5.7 — it
outsources TP/SP/CP to vLLM/DeepSpeed); here they are first-class. Two
schemes over the mesh's `seq` axis:

- **Ring attention** (blockwise attention + K/V rotation): each device
  keeps its Q shard, K/V shards rotate around the ring via
  `lax.ppermute` (ICI neighbor exchange), and softmax is accumulated
  online (log-sum-exp streaming), so full attention over sequences of
  length S costs O(S/n) memory per device and the K/V transfer overlaps
  compute rounds. Communication is nearest-neighbor — exactly the
  topology ICI is fastest at.

- **Ulysses**: `lax.all_to_all` reshards [B, S/n, H, D] → [B, S, H/n, D]
  so each device runs *full-sequence* attention on a head subset, then
  reshards back. Cheaper for moderate S with many heads; requires
  n_heads % n == 0.

Both run inside `shard_map` so XLA sees the collectives and schedules
them against compute.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attention(q, k, v, o, m, l, q_offset, kv_offset, causal, scale):
    """One streaming-softmax accumulation step.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; o: [B, Sq, H, D] accumulator;
    m/l: [B, H, Sq] running max / normalizer.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = kv_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    m_block = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_block)
    # Guard fully-masked rows (m_new == NEG_INF) against exp overflow.
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - m_safe)
    alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None].swapaxes(1, 2) + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v)
    return o_new, m_new, l_new


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool):
    """Per-device body: rotate K/V around the ring, accumulate online."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    q_offset = idx * sq
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        kv_idx = (idx - i) % n
        kv_offset = kv_idx * k_blk.shape[1]
        o, m, l = _block_attention(q, k_blk, v_blk, o, m, l,
                                   q_offset, kv_offset, causal, scale)
        # Rotate AFTER use; XLA overlaps the ppermute with the next
        # round's einsum where possible.
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk), None

    o0 = jnp.zeros_like(q, dtype=jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sq), dtype=jnp.float32)
    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k.astype(jnp.float32), v.astype(jnp.float32)),
        jnp.arange(n))
    l = jnp.maximum(l, 1e-20)
    out = o / l[..., None].swapaxes(1, 2)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = True,
                   axis_name: str = "seq"):
    """Full attention over a sequence sharded on ``axis_name``.

    q/k/v: [batch, seq, heads, head_dim], seq sharded across the mesh's
    ``seq`` axis (batch may additionally be sharded on data/fsdp — those
    axes pass through untouched).
    """
    spec = P(None, axis_name, None, None)
    fn = functools.partial(_ring_attention_local, axis_name=axis_name,
                           causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool):
    n = lax.psum(1, axis_name)

    def scatter_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_seq(x):
        # [B, S, H/n, D] -> [B, S/n, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
    if causal:
        sq = q.shape[1]
        mask = jnp.tril(jnp.ones((sq, sq), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return gather_seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, *, causal: bool = True,
                      axis_name: str = "seq"):
    """Ulysses-style sequence parallelism (head-scatter all-to-all)."""
    n = 1
    for name, size in zip(mesh.axis_names, mesh.devices.shape):
        if name == axis_name:
            n = size
    if q.shape[2] % max(n, 1) != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by seq axis "
            f"size ({n})")
    spec = P(None, axis_name, None, None)
    fn = functools.partial(_ulysses_local, axis_name=axis_name,
                           causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def reference_attention(q, k, v, *, causal: bool = True):
    """Unsharded reference for correctness tests."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
