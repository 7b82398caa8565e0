"""Plain reference of the Granite-4.0-H language model
(``granitemoehybrid``; ibm-granite/granite-4.0-h-small), as ONE RANK of
an expert-parallel group holds it.

The forward pass as the model's public description gives it
(transformers' modeling_granitemoehybrid.py: GraniteMoeHybridMambaLayer's
slow path, GraniteMoeHybridAttention, GraniteMoeHybridMoE and the
shared MLP):

- embedding: ``x = E[token] * embedding_multiplier``; head: ``logits =
  rmsnorm(x) E^T / logits_scaling``, ``E`` tied.
- every layer: ``x = x + r * mixer(rmsnorm(x))``, then ``h =
  rmsnorm(x)``, ``x = x + r * (routed(h) + shared(h))``; ``r`` the
  ``residual_multiplier``. ``layer_types[i]`` names layer i's mixer.
- Mamba-2 mixer, ``u`` [L, dim]: ``[z | xBC | dt] = u W_in``; ``xBC =
  silu(conv(xBC))``, causal, depthwise, width 4 with bias (position t
  sees t-3..t, zeros before the start); ``xBC = [x | B | C]``, ``x``
  [H, P], B and C [N] (one group); ``dt = softplus(dt + dt_bias)`` [H];
  ``A = -exp(A_log)`` [H]; per head ``h_t = exp(dt_t A) h_{t-1} + dt_t
  x_t (outer) B_t``, ``h_0 = 0``, ``y_t = h_t C_t + D x_t``; ``y =
  rmsnorm(y * silu(z)) * w`` over all d_inner channels; ``y W_out``.
- attention: grouped-query, causal, NO position encoding, no bias,
  scores times ``attention_multiplier`` (not ``head_dim ** -0.5``).
- routed: ``l = h W_r`` over ALL experts; the ``top_k`` largest; ``g =
  softmax`` over those; expert e: ``[a | b] = h W_in,e``, ``(silu(a) *
  b) W_out,e``; the sum of ``g_e expert_e(h)`` over the picked experts
  THAT THIS RANK HOLDS (``first .. first + held - 1``): what the absent
  experts would add is left out, as in the program.
- shared: the same form, one expert, every token.

Everything in float32 with jax.numpy, matmuls at the highest precision,
the recurrence ONE STEP AT A TIME (``lax.scan`` over time: not the
chunked form the program runs, which this checks), no kernel, no cache,
no batching.

It reads the parameter tree the program's granite_init draws (stacks
``mamba``: in_norm, w_in, conv_w [taps, conv_dim], conv_b, dt_bias,
A_log, D, norm_w, w_out; ``attn``: in_norm, wq, wk, wv, wo; both:
ff_norm, router, w_in_e, w_out_e, w_in_s, w_out_s; embedding,
final_norm) and nothing else of the program. It runs beside the engine
on the chip: layers are walked one at a time and a layer's experts in
blocks of ``EXPERT_BLOCK``, so that no float32 copy of a whole expert
stack (1.36 GB a layer at the published widths) is ever alive.
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


def _routed(h, layer, top_k, first):
    """-> (the held experts' part [S, D], margin [S]: the router's
    k-th less its (k+1)-th logit)."""
    logits = h @ _f32(layer["router"])                       # [S, E]
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    vals, idx = jax.lax.top_k(logits, top_k)
    gate = jax.nn.softmax(vals, axis=-1)                     # [S, k]
    held = layer["w_in_e"].shape[0]
    block = EXPERT_BLOCK if held % EXPERT_BLOCK == 0 else 1

    def some_experts(acc, start):
        w_in = jax.lax.dynamic_slice_in_dim(layer["w_in_e"], start, block)
        w_out = jax.lax.dynamic_slice_in_dim(layer["w_out_e"], start, block)
        for j in range(block):
            g = jnp.sum(jnp.where(idx == first + start + j, gate, 0.0),
                        axis=-1, keepdims=True)
            acc = acc + g * _gated(h, w_in[j], w_out[j])
        return acc, None

    out, _ = jax.lax.scan(some_experts, jnp.zeros_like(h),
                          jnp.arange(0, held, block))
    return out, margin


def _mamba(u, layer, eps, n_heads, d_state):
    seq = u.shape[0]
    conv_w = _f32(layer["conv_w"])                     # [taps, conv_dim]
    taps, conv_dim = conv_w.shape
    d_inner = conv_dim - 2 * d_state
    p = d_inner // n_heads
    zxbcdt = u @ _f32(layer["w_in"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + conv_dim]
    dt = zxbcdt[:, d_inner + conv_dim:]                # [L, H]
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim)), xbc], 0)
    xbc = sum(padded[k:k + seq] * conv_w[k] for k in range(taps))
    xbc = jax.nn.silu(xbc + _f32(layer["conv_b"]))
    x = xbc[:, :d_inner].reshape(seq, n_heads, p)
    b = xbc[:, d_inner:d_inner + d_state]
    c = xbc[:, d_inner + d_state:]
    dt = jax.nn.softplus(dt + _f32(layer["dt_bias"]))
    a = -jnp.exp(_f32(layer["A_log"]))                 # [H]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((n_heads, p, d_state)),
                        (x, dt, b, c))
    y = (y + _f32(layer["D"])[None, :, None] * x).reshape(seq, d_inner)
    y = _rms_norm(y * jax.nn.silu(z), layer["norm_w"], eps)
    return y @ _f32(layer["w_out"])


def _attention(h, layer, n_heads, n_kv_heads, scale):
    s, dim = h.shape
    hd = dim // n_heads
    q = (h @ _f32(layer["wq"])).reshape(s, n_heads, hd)
    k = (h @ _f32(layer["wk"])).reshape(s, n_kv_heads, hd)
    v = (h @ _f32(layer["wv"])).reshape(s, n_kv_heads, hd)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, dim) @ _f32(layer["wo"])


def _one(stack, index):
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False),
        stack)


def logits_and_margins(params: Dict[str, Any], tokens, *,
                       layer_types: Tuple[str, ...], n_heads: int,
                       n_kv_heads: int, mamba_n_heads: int,
                       mamba_d_state: int, top_k: int, first_expert: int,
                       embedding_multiplier: float,
                       attention_multiplier: float,
                       residual_multiplier: float, logits_scaling: float,
                       norm_eps: float):
    """tokens [S] int32 -> (logits [S, vocab], margins [S]) float32,
    one sequence, one pass. A position's margin is the smallest over
    the layers of the router's k-th less its (k+1)-th logit."""
    kinds = ["attn" if t == "attention" else "mamba" for t in layer_types]
    r = residual_multiplier

    def one_layer(kind):
        def body(x, index):
            layer = _one(params[kind], index)
            h = _rms_norm(x, layer["in_norm"], norm_eps)
            if kind == "attn":
                x = x + r * _attention(h, layer, n_heads, n_kv_heads,
                                       attention_multiplier)
            else:
                x = x + r * _mamba(h, layer, norm_eps, mamba_n_heads,
                                   mamba_d_state)
            h = _rms_norm(x, layer["ff_norm"], norm_eps)
            routed, margin = _routed(h, layer, top_k, first_expert)
            shared = _gated(h, layer["w_in_s"], layer["w_out_s"])
            return x + r * (routed + shared), margin
        return body

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens]) * embedding_multiplier
        seen = {"attn": 0, "mamba": 0}
        margins = []
        i = 0
        while i < len(kinds):
            # a run of layers of one kind: one loop over their indices
            # in that kind's stack (compiled once a run, not a layer)
            kind, run = kinds[i], 1
            while i + run < len(kinds) and kinds[i + run] == kind:
                run += 1
            x, margin = jax.lax.scan(
                one_layer(kind), x,
                jnp.arange(seen[kind], seen[kind] + run))
            margins.append(margin.min(0))
            seen[kind] += run
            i += run
        x = _rms_norm(x, params["final_norm"], norm_eps)
        return (x @ _f32(params["embedding"]).T / logits_scaling,
                jnp.stack(margins).min(0))


def logits(params: Dict[str, Any], tokens, **kw):
    """tokens [S] int32 -> logits [S, vocab] float32, one sequence."""
    return logits_and_margins(params, tokens, **kw)[0]


def router_margins(params: Dict[str, Any], tokens, **kw):
    """tokens [S] int32 -> margins [S] float32, one sequence."""
    return logits_and_margins(params, tokens, **kw)[1]


def loss(params, tokens, targets, **kw):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(logits(params, tokens, **kw), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], 1))


def kwargs_from(config) -> Dict[str, Any]:
    """What ``logits`` needs, from the program's model configuration
    (a GraniteConfig). The rank's share is the configuration's: the
    held experts' first index (their count is the stacks') and the
    embedding's rows (the tree's)."""
    return dict(layer_types=tuple(config.layer_types),
                n_heads=config.n_heads, n_kv_heads=config.n_kv_heads,
                mamba_n_heads=config.mamba_n_heads,
                mamba_d_state=config.mamba_d_state, top_k=config.top_k,
                first_expert=config.experts_held[0],
                embedding_multiplier=config.embedding_multiplier,
                attention_multiplier=config.attention_multiplier,
                residual_multiplier=config.residual_multiplier,
                logits_scaling=config.logits_scaling,
                norm_eps=config.norm_eps)
