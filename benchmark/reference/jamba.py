"""Plain reference of the Jamba language model (AI21-Jamba2-3B).

The forward pass as the model's public description gives it
(transformers' modeling_jamba.py, JambaMambaMixer's slow path and
JambaAttention): token embedding, then per layer RMSNorm -> mixer ->
residual -> RMSNorm -> SwiGLU feed-forward -> residual; a final RMSNorm
and logits against the transposed embedding (tied). Layer ``i`` mixes
by attention iff ``i % attn_layer_period == attn_layer_offset``, else by
a Mamba-1 selective state-space mixer.

- Mamba-1 mixer, ``u`` of [L, dim]: ``x, z = split(u W_in)``; ``x =
  silu(conv(x))``, a causal depthwise convolution over time of width 4
  with bias (position t sees t-3..t, zeros before the start); ``dt_r,
  B, C = split(x W_x)``, each through its own RMSNorm (Jamba's
  addition); ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t``, ``h_0 = 0``;
  ``y_t = h_t . C_t + D * x_t``; the output is ``(y * silu(z)) W_out``.
- attention: grouped-query (20 heads over ONE key/value head in the 3B
  model), causal, scale head_dim ** -0.5, NO rotary or other position
  encoding, no bias, no window.
- ``num_experts`` 1: every feed-forward is a dense SwiGLU.

Everything in float32 with jax.numpy, matmuls at the highest
precision, the recurrence one step at a time (``lax.scan`` over time),
no kernel, no cache, no batching tricks.

It reads the parameter tree the program's jamba_init draws (the stacks
``mamba``: in_norm, w_in, conv_w [taps, d_inner], conv_b, w_x, dt_norm,
b_norm, c_norm, w_dt, b_dt, A_log, D, w_out, ff_norm, w_gate, w_up,
w_down; and ``attn``: in_norm, wq, wk, wv, wo, ff_norm, w_gate, w_up,
w_down; embedding, final_norm) and nothing else of the program. Layers
are walked one at a time so that only one layer's float32 copy is alive
beside the program's own bf16 weights.

Departures from the published code, each without effect on the
result: ``A_log`` is read as [N, d_inner], the transpose of the
published [d_inner, N] (the program's tree keeps it so); the
convolution's weight is read as [taps, d_inner] where torch keeps
[d_inner, 1, taps]; ``w_x`` is one matrix whose columns are split into
dt_r, B, C in the published order; the published code computes the
recurrence in float32 inside an otherwise bf16 model, here all is
float32.
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


def _swiglu(x, layer, eps):
    h = _rms_norm(x, layer["ff_norm"], eps)
    return x + (jax.nn.silu(h @ _f32(layer["w_gate"]))
                * (h @ _f32(layer["w_up"]))) @ _f32(layer["w_down"])


def _mamba(u, layer, eps):
    seq = u.shape[0]
    conv_w = _f32(layer["conv_w"])                       # [taps, d_inner]
    taps, d_inner = conv_w.shape
    n = layer["A_log"].shape[0]
    xz = u @ _f32(layer["w_in"])
    x, z = xz[:, :d_inner], xz[:, d_inner:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d_inner)), x], 0)
    x = sum(padded[k:k + seq] * conv_w[k] for k in range(taps))
    x = jax.nn.silu(x + _f32(layer["conv_b"]))
    dbc = x @ _f32(layer["w_x"])
    r = dbc.shape[1] - 2 * n
    dt_r = _rms_norm(dbc[:, :r], layer["dt_norm"], eps)
    b = _rms_norm(dbc[:, r:r + n], layer["b_norm"], eps)
    c = _rms_norm(dbc[:, r + n:], layer["c_norm"], eps)
    dt = jax.nn.softplus(dt_r @ _f32(layer["w_dt"]) + _f32(layer["b_dt"]))
    a = -jnp.exp(_f32(layer["A_log"]))                   # [N, d_inner]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[None, :] * a) * h \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((n, d_inner)), (x, dt, b, c))
    y = y + _f32(layer["D"]) * x
    return (y * jax.nn.silu(z)) @ _f32(layer["w_out"])


def _attention(h, layer, n_heads, n_kv_heads):
    s, dim = h.shape
    hd = dim // n_heads
    q = (h @ _f32(layer["wq"])).reshape(s, n_heads, hd)
    k = (h @ _f32(layer["wk"])).reshape(s, n_kv_heads, hd)
    v = (h @ _f32(layer["wv"])).reshape(s, n_kv_heads, hd)
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


def logits_and_margins(params: Dict[str, Any], tokens, *, n_layers: int,
                       attn_layer_period: int, attn_layer_offset: int,
                       n_heads: int, n_kv_heads: int, norm_eps: float):
    """tokens [S] int32 -> (logits [S, vocab], margins [S]) float32,
    one sequence, one pass. The model has no router: every margin is
    infinite."""
    kinds = ["attn" if i % attn_layer_period == attn_layer_offset
             else "mamba" for i in range(n_layers)]

    def one_layer(kind):
        def body(x, index):
            layer = _one(params[kind], index)
            h = _rms_norm(x, layer["in_norm"], norm_eps)
            if kind == "attn":
                x = x + _attention(h, layer, n_heads, n_kv_heads)
            else:
                x = x + _mamba(h, layer, norm_eps)
            return _swiglu(x, layer, norm_eps), None
        return body

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens])
        seen = {"attn": 0, "mamba": 0}
        i = 0
        while i < n_layers:
            # a run of layers of one kind: one loop over their indices
            # in that kind's stack (compiled once a run, not a layer)
            kind, run = kinds[i], 1
            while i + run < n_layers and kinds[i + run] == kind:
                run += 1
            x, _ = jax.lax.scan(
                one_layer(kind), x,
                jnp.arange(seen[kind], seen[kind] + run))
            seen[kind] += run
            i += run
        x = _rms_norm(x, params["final_norm"], norm_eps)
        return (x @ _f32(params["embedding"]).T,
                jnp.full(tokens.shape[:1], jnp.inf))


def logits(params: Dict[str, Any], tokens, **kw):
    """tokens [S] int32 -> logits [S, vocab] float32, one sequence."""
    return logits_and_margins(params, tokens, **kw)[0]


def loss(params, tokens, targets, **kw):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(logits(params, tokens, **kw), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], 1))


def kwargs_from(config) -> Dict[str, Any]:
    """What ``logits`` needs, from the program's model configuration
    (a JambaConfig)."""
    return dict(n_layers=config.n_layers,
                attn_layer_period=config.attn_layer_period,
                attn_layer_offset=config.attn_layer_offset,
                n_heads=config.n_heads, n_kv_heads=config.n_kv_heads,
                norm_eps=config.norm_eps)
