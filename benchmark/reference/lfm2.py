"""Plain reference of the LFM2-MoE language model (``lfm2_moe``;
LiquidAI/LFM2-8B-A1B).

The forward pass as the model's public description gives it
(transformers' modeling_lfm2_moe.py: Lfm2MoeShortConv's slow path,
Lfm2MoeAttention, Lfm2MoeMLP and Lfm2MoeSparseMoeBlock); ``u`` is a
sublayer's normed input, every matrix without bias, ``norm`` an RMSNorm
with a weight:

- embedding ``x = E[token]``; head ``logits = norm(x) W_head``, two
  matrices (not tied).
- layer ``i``: ``x = x + op_i(norm(x))``, ``x = x + ff_i(norm(x))``;
  ``op_i`` attention where ``layer_types[i]`` is ``full_attention``,
  else the short convolution; ``ff_i`` a dense SwiGLU for the first
  ``n_dense_layers`` layers, the routed layer after.
- short convolution: ``[B | C | z] = u W_in``; ``s = B * z``; ``c_t =
  sum_j w[j] * s_{t - (taps - 1) + j}`` (causal, depthwise, no bias,
  zeros before the sequence); ``(C * c) W_out``. No activation.
- attention: ``q = rope(norm_q(u W_q))``, ``k = rope(norm_k(u W_k))``,
  the norms over each head with a weight of ``head_dim``, rotary over
  the whole head in the ``rotate_half`` form; grouped-query, causal
  softmax of ``q k^T / sqrt(head_dim)``; ``W_o``.
- dense: ``(silu(a) * b) W_out`` with ``[a | b] = u W_in``.
- routed: ``l = u W_g``; ``p = sigmoid(l)``; the ``top_k`` largest of
  ``p + bias``; gates ``p_e / (sum of the picked p + 1e-6) * scale``;
  the sum of ``g_e expert_e(u)`` over the picks, an expert of the dense
  form. No shared expert.

Everything in float32 with jax.numpy, matmuls at the highest precision,
no kernel, no cache, no batching, one sequence a call.

It reads the parameter tree the program's lfm2_init draws (stacks
``conv``: in_norm, w_in, conv_w [taps, dim], w_out; ``attn``: in_norm,
wq, wk, wv, wo, q_norm, k_norm; ``dense``: ff_norm, w_in, w_out;
``moe``: ff_norm, router, router_bias, w_in_e, w_out_e; embedding,
lm_head, final_norm) and nothing else of the program. DEPARTURES from
the published description, none of which changes a number: a gated
feed-forward's two input projections are ONE matrix, the gated half
first (the published ``w1`` and ``w3`` side by side: the repo's
layout); a convolution's taps are [taps, dim] where the published
``conv.weight`` is [dim, 1, taps]; ``W_in``'s thirds are taken in the
order ``[B | C | z]``. It runs beside the engine on the chip: layers are
walked one at a time and a layer's experts in blocks of
``EXPERT_BLOCK``, so that no float32 copy of a whole expert stack (1.41
GB a layer at the published widths) is ever alive.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 4


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(weight)


def _gated(h, w_in, w_out):
    ab = h @ _f32(w_in)
    half = ab.shape[-1] // 2
    return (jax.nn.silu(ab[:, :half]) * ab[:, half:]) @ _f32(w_out)


def _routed(h, layer, top_k, scale):
    """-> (the routed layer's output [S, D], margin [S]: the k-th less
    the (k+1)-th of the scores the choice is made on, ``p + bias``)."""
    p = jax.nn.sigmoid(h @ _f32(layer["router"]))            # [S, E]
    choice = p + _f32(layer["router_bias"])
    ranked = jnp.sort(choice, axis=-1)[:, ::-1]
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    _, idx = jax.lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(p, idx, axis=-1)            # [S, k]
    gate = picked / (picked.sum(-1, keepdims=True) + 1e-6) * scale
    n = layer["w_in_e"].shape[0]
    block = EXPERT_BLOCK if n % EXPERT_BLOCK == 0 else 1

    def some_experts(acc, start):
        w_in = jax.lax.dynamic_slice_in_dim(layer["w_in_e"], start, block)
        w_out = jax.lax.dynamic_slice_in_dim(layer["w_out_e"], start, block)
        for j in range(block):
            g = jnp.sum(jnp.where(idx == start + j, gate, 0.0),
                        axis=-1, keepdims=True)
            acc = acc + g * _gated(h, w_in[j], w_out[j])
        return acc, None

    out, _ = jax.lax.scan(some_experts, jnp.zeros_like(h),
                          jnp.arange(0, n, block))
    return out, margin


def _short_conv(u, layer):
    seq, dim = u.shape
    bcz = u @ _f32(layer["w_in"])
    b, c, z = bcz[:, :dim], bcz[:, dim:2 * dim], bcz[:, 2 * dim:]
    w = _f32(layer["conv_w"])                              # [taps, dim]
    taps = w.shape[0]
    s = jnp.concatenate([jnp.zeros((taps - 1, dim)), b * z], 0)
    mixed = sum(s[j:j + seq] * w[j] for j in range(taps))
    return (c * mixed) @ _f32(layer["w_out"])


def _rope(x, theta):
    """x [S, heads, HD], position = row: the ``rotate_half`` form, the
    head's first half paired with its second."""
    seq, _, hd = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]    # [S, 1, HD]
    rotated = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


def _norm_rope(q, k, layer, eps, theta):
    """What the family does to ``q`` and ``k`` between projection and
    scores: a norm over each head, then rotary."""
    q = _rms_norm(q, layer["q_norm"], eps)
    k = _rms_norm(k, layer["k_norm"], eps)
    return _rope(q, theta), _rope(k, theta)


def _attention(u, layer, n_heads, n_kv_heads, eps, theta):
    s, dim = u.shape
    hd = dim // n_heads
    q = (u @ _f32(layer["wq"])).reshape(s, n_heads, hd)
    k = (u @ _f32(layer["wk"])).reshape(s, n_kv_heads, hd)
    v = (u @ _f32(layer["wv"])).reshape(s, n_kv_heads, hd)
    q, k = _norm_rope(q, k, layer, eps, theta)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, dim) @ _f32(layer["wo"])


def _one(stack, index):
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False),
        stack)


def logits_and_margins(params: Dict[str, Any], tokens, *,
                       layer_types: Tuple[str, ...], n_dense_layers: int,
                       n_heads: int, n_kv_heads: int, top_k: int,
                       routed_scaling: float, rope_theta: float,
                       norm_eps: float):
    """tokens [S] int32 -> (logits [S, vocab], margins [S]) float32,
    one sequence, one pass. A position's margin is the smallest over
    the routed layers of the k-th less the (k+1)-th selection score
    (``sigmoid(l) + bias``: what the picks are made on, so a unit of it
    is about a fifth of a unit of router logit)."""
    def one_layer(mixer, ff):
        def body(x, index):
            at_mixer, at_ff = index
            layer = _one(params[mixer], at_mixer)
            u = _rms_norm(x, layer["in_norm"], norm_eps)
            if mixer == "attn":
                x = x + _attention(u, layer, n_heads, n_kv_heads, norm_eps,
                                   rope_theta)
            else:
                x = x + _short_conv(u, layer)
            layer = _one(params[ff], at_ff)
            u = _rms_norm(x, layer["ff_norm"], norm_eps)
            if ff == "dense":
                return (x + _gated(u, layer["w_in"], layer["w_out"]),
                        jnp.full(x.shape[:1], jnp.inf))
            routed, margin = _routed(u, layer, top_k, routed_scaling)
            return x + routed, margin
        return body

    kinds = [("attn" if t == "full_attention" else "conv",
              "dense" if i < n_dense_layers else "moe")
             for i, t in enumerate(layer_types)]
    seen = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    margins = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens])
        i = 0
        while i < len(kinds):
            # a run of layers of one kind: one loop over their indices
            # in its two stacks (compiled once a run, not a layer)
            (mixer, ff), run = kinds[i], 1
            while i + run < len(kinds) and kinds[i + run] == kinds[i]:
                run += 1
            x, margin = jax.lax.scan(
                one_layer(mixer, ff), x,
                (jnp.arange(seen[mixer], seen[mixer] + run),
                 jnp.arange(seen[ff], seen[ff] + run)))
            margins.append(margin.min(0))
            seen[mixer] += run
            seen[ff] += run
            i += run
        x = _rms_norm(x, params["final_norm"], norm_eps)
        return x @ _f32(params["lm_head"]), jnp.stack(margins).min(0)


def logits(params: Dict[str, Any], tokens, **kw):
    """tokens [S] int32 -> logits [S, vocab] float32, one sequence."""
    return logits_and_margins(params, tokens, **kw)[0]


def loss(params, tokens, targets, **kw):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(logits(params, tokens, **kw), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], 1))


def kwargs_from(config) -> Dict[str, Any]:
    """What ``logits`` needs, from the program's model configuration
    (an Lfm2Config); the sizes are the parameter tree's."""
    return dict(layer_types=tuple(config.layer_types),
                n_dense_layers=config.n_dense_layers,
                n_heads=config.n_heads, n_kv_heads=config.n_kv_heads,
                top_k=config.top_k, routed_scaling=config.routed_scaling,
                rope_theta=config.rope_theta, norm_eps=config.norm_eps)
