"""What the serving families behind the seam of models/family.py share
(models/jamba.py, granite.py, lfm2.py, mla.py): what is letter for
letter the same in two or more of them, written once. A function here
takes a family's functions and values, never a family's name, a
configuration's class or a flag that stands for one; what would need
one (the heads: two tie the embedding, one divides by a scaling, two
have a matrix of their own; the trunks; Granite's feed-forward with its
multiplier and its own stacks) stays in the family's module.

- ``runs``: a trunk of two kinds of layer as runs of consecutive layers
  of one kind, each a ``lax.scan`` segment over indices into its kind's
  stack.
- ``layer``: one layer's weights out of a stack by a traced index, so no
  stack is ever sliced into a copy; ``drawers``: how a family's init
  draws a matrix, a stack of them, and a norm's weight.
- ``attn_sequence`` / ``attn_decode``: the attention sublayer (grouped-
  query, causal, NO position encoding of its own: the mixers carry
  position), over one sequence and over every slot against the cache. A
  family says what differs: its KV heads (through its configuration), a
  scale on ``q`` where its scores are not times ``head_dim ** -0.5``, a
  multiplier on what the sublayer adds to the residual stream, and
  ``qk``: what it does to ``q`` and ``k`` between projection and kernel
  (LFM2: a norm over each head, then rotary), ``qk(p, q [rows, H, HD],
  k [rows, KVH, HD], positions [rows]) -> (q, k)``, in float32.
- ``dense_or_routed``: a layer's second half where the leading layers'
  is a dense gated feed-forward and the others' a routed one
  (``parallel/moe.py::held_experts_ffn``) behind a router with a
  selection bias (LFM2, MLA).
- ``forward`` / ``prefill_result``: a family's whole-sequence forward
  and its prefill's last lines, from its ``trunk`` and ``head``.

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
from ray_tpu.parallel.moe import gated_ffn, held_experts_ffn

SCOPE_ATTN = "attn"
SCOPE_MLP = "mlp"


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


def drawers(dtype):
    """-> (dense, by_layer, ones), how a family's ``X_init`` makes its
    leaves in ``dtype``: ``dense(key, shape, fan_in)`` a matrix drawn
    normal in float32 times ``fan_in ** -0.5``; ``by_layer(key, layers,
    shape, fan_in)`` a stack of them drawn a layer at a time, so that
    no float32 draw of a whole stack is ever alive; ``ones(*shape)``."""
    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def by_layer(key, layers, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in),
                           jax.random.split(key, layers))

    def ones(*shape):
        return jnp.ones(shape, dtype=dtype)

    return dense, by_layer, ones


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


def dense_or_routed(params, ff: str, index, x, live, c, n_counts: int,
                    first: int = 0, shared=None):
    """A layer's second half, layer ``index`` of stack ``ff`` ("dense" |
    "moe"). x [T, dim] -> (x, the layer's counts [n_counts] uint32 over
    the ``live`` rows, zeros from a dense layer). ``first``: the first
    expert this rank holds (it holds those of ``params["moe"]``'s
    stacks); ``shared(p, h) -> [T, dim]``: what a family with a shared
    expert adds for every row, from the layer's weights and the normed
    input. ``c``: ``norm_eps``, ``top_k``, ``scoring``."""
    p = layer(params[ff], index)
    h = rms_norm(x, p["ff_norm"], c.norm_eps)
    if ff == "dense":
        with jax.named_scope(SCOPE_MLP):
            return (x + gated_ffn(h, p["w_in"], p["w_out"]),
                    jnp.zeros((n_counts,), jnp.uint32))
    # under the scopes moe.router and moe.experts; the experts' weights
    # go as the stack's (``p``'s slices of them are never read, so
    # under jit they are never made)
    routed, counts = held_experts_ffn(
        h, p["router"], params["moe"]["w_in_e"], params["moe"]["w_out_e"],
        first, layer=index, top_k=c.top_k, live=live, scoring=c.scoring,
        bias=p["router_bias"])
    if shared is None:
        return x + routed, counts
    also = shared(p, h)
    return x + routed + also, counts


def forward(trunk, head, params, tokens, c, return_hidden: bool):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, or with
    ``return_hidden`` the final-norm hidden states [B, S, dim]. Whole
    sequences, one at a time (the tests and engine.embed).
    ``trunk(params, tokens [L], length, c) -> (hidden [L, dim] before
    the final norm, ...)``; ``head(params, x, c) -> logits``."""
    hidden = jnp.stack([
        trunk(params, tokens[i], tokens.shape[1], c)[0]
        for i in range(tokens.shape[0])])
    if return_hidden:
        return rms_norm(hidden, params["final_norm"],
                        c.norm_eps).astype(c.dtype)
    return head(params, hidden, c)


def prefill_result(head, params, c, x, length, entry, counts=None,
                   names: Sequence[str] = ()):
    """What a prefill returns, from its trunk's hidden rows ``x`` [bucket,
    dim]: (logits [1, 1, vocab] float32 of position length - 1, the
    slot's cache entry, ``counts`` with its two slot counts zeroed: a
    prefill counts no expert slots; None from a family that counts
    nothing). ``names``: what ``counts`` counts, in order."""
    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, 0)
    logits = head(params, last, c)[None]
    if counts is not None:
        hit = names.index("slots_hit")
        counts = counts.at[hit:hit + 2].set(0)  # slots_hit, slots_idle
    return logits, entry, counts
