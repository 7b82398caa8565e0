"""Plain reference of the Mistral-7B / Mixtral-8x7B language model.

The forward pass as the models' public descriptions give it
(transformers' modeling_mistral.py and modeling_mixtral.py): token
embedding, then per layer RMSNorm -> grouped-query causal attention
with rotary embeddings (half-split rotation) -> residual -> RMSNorm ->
SwiGLU feed-forward, or for Mixtral a router (softmax over all
experts, the top k kept and renormalised, NO capacity: every token
reaches the experts it chose) -> residual; a final RMSNorm and the
output head. Everything in float32 with jax.numpy, matmuls at the
highest precision, no kernel, no cache, no batching tricks.

It reads the parameter tree the program's llama_init draws (stacked
layers: attn_norm, wq, wk, wv, wo, mlp_norm, w1 (gate), w3 (up), w2
(down), router; embedding, final_norm, lm_head) and nothing else of
the program. Layers and experts are walked one at a time so that only
one layer's (one expert's) float32 copy is alive beside the program's
own bf16 weights.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(weight)


def _rope(x, theta):
    """x: [S, H, D]; positions 0..S-1; rotate_half convention."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, layer, n_heads, n_kv_heads, theta):
    s, dim = h.shape
    hd = dim // n_heads
    q = (h @ _f32(layer["wq"])).reshape(s, n_heads, hd)
    k = (h @ _f32(layer["wk"])).reshape(s, n_kv_heads, hd)
    v = (h @ _f32(layer["wv"])).reshape(s, n_kv_heads, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(s, dim) @ _f32(layer["wo"])


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def _moe(h, layer, top_k):
    """-> (output [S, D], margin [S]): how far the router was from
    choosing another expert, the k-th less the (k+1)-th logit."""
    router_logits = h @ _f32(layer["router"])                 # [S, E]
    probs = jax.nn.softmax(router_logits, -1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / top_vals.sum(-1, keepdims=True)
    n_experts = probs.shape[-1]
    if top_k < n_experts:
        ranked, _ = jax.lax.top_k(router_logits, top_k + 1)
        margin = ranked[:, top_k - 1] - ranked[:, top_k]
    else:
        margin = jnp.full(h.shape[:1], jnp.inf)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_idx].set(top_vals)

    def one_expert(acc, e):
        y = _swiglu(h, layer["w1"][e], layer["w3"][e], layer["w2"][e])
        return acc + gates[:, e][:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(n_experts))
    return out, margin


def logits_and_margins(params: Dict[str, Any], tokens, *, n_heads: int,
                       n_kv_heads: int, rope_theta: float,
                       norm_eps: float, moe_top_k: int = 0):
    """tokens [S] int32 -> (logits [S, vocab], margins [S]) float32,
    one sequence, one pass. A position's margin is the smallest over
    the layers of its router's k-th less (k+1)-th logit: under it, a
    rounding of the hidden state sends the token to another expert.
    Infinite for a model without a router."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens])

        def one_layer(x, layer):
            h = _rms_norm(x, layer["attn_norm"], norm_eps)
            x = x + _attention(h, layer, n_heads, n_kv_heads, rope_theta)
            h = _rms_norm(x, layer["mlp_norm"], norm_eps)
            if "router" in layer:
                y, margin = _moe(h, layer, moe_top_k)
                return x + y, margin
            return (x + _swiglu(h, layer["w1"], layer["w3"], layer["w2"]),
                    jnp.full(x.shape[:1], jnp.inf))

        x, margins = jax.lax.scan(one_layer, x, params["layers"])
        x = _rms_norm(x, params["final_norm"], norm_eps)
        return x @ _f32(params["lm_head"]), margins.min(0)


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
    (a LlamaConfig)."""
    return dict(n_heads=config.n_heads, n_kv_heads=config.n_kv_heads,
                rope_theta=config.rope_theta, norm_eps=config.norm_eps,
                moe_top_k=config.moe_top_k if config.moe_experts else 0)
