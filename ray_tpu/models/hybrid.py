"""What the hybrid families share (models/jamba.py, models/granite.py):
a trunk of state-space mixers with an attention layer now and then, as
two stacks of layers walked by runs of one kind.

- ``runs``: the trunk as runs of consecutive layers of one kind, each a
  ``lax.scan`` segment over indices into its kind's stack.
- ``layer``: one layer's weights out of a stack by a traced index, so no
  stack is ever sliced into a copy.
- ``attn_sequence`` / ``attn_decode``: the attention sublayer (grouped-
  query, causal, NO position encoding of its own: the mixers carry
  position), over one sequence and over every slot against the cache. A
  family says what differs: its KV heads (through its configuration), a
  scale on ``q`` where its scores are not times ``head_dim ** -0.5``, a
  multiplier on what the sublayer adds to the residual stream, and
  ``qk``: what it does to ``q`` and ``k`` between projection and kernel
  (LFM2: a norm over each head, then rotary), ``qk(p, q [rows, H, HD],
  k [rows, KVH, HD], positions [rows]) -> (q, k)``, in float32.

``c`` below is the family's configuration: ``n_heads``, ``n_kv_heads``,
``head_dim``, ``norm_eps``, ``dtype``, ``attention`` ("flash" |
"reference").
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import decode_attention, flash_attention
from ray_tpu.ops.matmul import mm
from ray_tpu.ops.rmsnorm import rms_norm

SCOPE_ATTN = "attn"


def runs(layer_kinds: Sequence[str]) -> Tuple[Tuple[str, int, int], ...]:
    """The trunk as (kind, first index within its kind's stack, count)
    for each run of consecutive layers of one kind."""
    out: List[Tuple[str, int, int]] = []
    seen = {kind: 0 for kind in layer_kinds}
    for kind in layer_kinds:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen[kind], 1))
        seen[kind] += 1
    return tuple(out)


def layer(stack, index):
    """One layer's weights out of a stack, by a traced or static index."""
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False),
        stack)


def _q(h, wq, q_scale):
    """The queries as the kernels take them. The kernels keep their
    scale of ``head_dim ** -0.5``; a family whose scores are scaled
    otherwise multiplies ``q`` by the quotient (the same sum), in the
    matmul's float32 so that ``q`` is rounded once."""
    if q_scale == 1.0:
        return h @ wq
    return (jnp.dot(h, wq, preferred_element_type=jnp.float32)
            * q_scale).astype(h.dtype)


def _q_k(p, h, c, q_scale, qk, positions, q_shape):
    """-> (q in ``q_shape``, k [rows, KVH * HD]) as the kernels take
    them. With ``qk`` both leave their matmuls in float32, go through
    it by heads, and are rounded once, after it and the scale."""
    if qk is None:
        return _q(h, p["wq"], q_scale).reshape(q_shape), h @ p["wk"]
    rows, hd = h.shape[0], c.head_dim
    q, k = qk(
        p,
        jnp.dot(h, p["wq"], preferred_element_type=jnp.float32)
        .reshape(rows, c.n_heads, hd),
        jnp.dot(h, p["wk"], preferred_element_type=jnp.float32)
        .reshape(rows, c.n_kv_heads, hd), positions)
    if q_scale != 1.0:
        q = q * q_scale
    return (q.astype(h.dtype).reshape(q_shape),
            k.astype(h.dtype).reshape(rows, c.n_kv_heads * hd))


def attn_sequence(p, x, c, q_scale: float = 1.0, residual: float = 1.0,
                  qk=None):
    """One attention layer over one sequence. x [L, dim] -> (x, k, v
    [L, KVH, HD]). Causal; ``qk`` sees the positions 0 .. L - 1."""
    seq, hd = x.shape[0], c.head_dim
    with jax.named_scope(SCOPE_ATTN):
        h = rms_norm(x, p["in_norm"], c.norm_eps).astype(c.dtype)
        q, k = _q_k(p, h, c, q_scale, qk,
                    None if qk is None else jnp.arange(seq),
                    (1, seq, c.n_heads, hd))
        k = k.reshape(1, seq, c.n_kv_heads, hd)
        v = (h @ p["wv"]).reshape(1, seq, c.n_kv_heads, hd)
        n_rep = c.n_heads // c.n_kv_heads
        kk = jnp.repeat(k, n_rep, axis=2) if n_rep > 1 else k
        vv = jnp.repeat(v, n_rep, axis=2) if n_rep > 1 else v
        if c.attention == "flash":
            out = flash_attention(q, kk, vv, True)
        else:
            from ray_tpu.ops.attention import _attention_reference
            out = _attention_reference(q, kk, vv, True)
        out = mm(out.reshape(seq, c.n_heads * hd), p["wo"])
        x = x + (out if residual == 1.0 else residual * out)
    return x, k[0], v[0]


def attn_decode(p, x, k_cache, v_cache, a, pos, c, q_scale: float = 1.0,
                residual: float = 1.0, qk=None):
    """One attention layer for every slot. x [B, dim]; ``k_cache`` /
    ``v_cache`` [A, B, S, KVH, HD], of which this is layer ``a``; pos
    [B]. The layer writes its row at ``pos`` and hands the stacked K
    and V, its index and ``pos`` to ``decode_attention`` (on a TPU the
    kernel reads of each slot only the blocks up to ``pos``). -> (x,
    k_cache, v_cache), written in place where the caller's program
    donates the cache."""
    b, hd, kvh = x.shape[0], c.head_dim, c.n_kv_heads
    slots = jnp.arange(b)
    with jax.named_scope(SCOPE_ATTN):
        h = rms_norm(x, p["in_norm"], c.norm_eps).astype(c.dtype)
        q, k = _q_k(p, h, c, q_scale, qk, pos,
                    (b, kvh, c.n_heads // kvh, hd))
        # a row as the cache keeps it: [KVH, HD], or packed to 128
        # lanes where heads are narrower (ops.attention.cache_row_shape)
        row = (b,) + k_cache.shape[-2:]
        k_cache = k_cache.at[a, slots, pos].set(k.reshape(row))
        v_cache = v_cache.at[a, slots, pos].set(
            (h @ p["wv"]).reshape(row))
        out = decode_attention(q, k_cache, v_cache, a, pos, c.dtype)
        out = mm(out.reshape(b, c.n_heads * hd), p["wo"])
        x = x + (out if residual == 1.0 else residual * out)
    return x, k_cache, v_cache
