"""Plain reference of the DeepSeek-V3 block (``deepseek_v3``,
``kimi_k2``; moonshotai/Kimi-K2.7-Code): latent attention over routed
experts, as ONE RANK of an expert-parallel group sees it.

The forward pass as the model's public description gives it
(transformers' modeling_deepseek_v3.py: DeepseekV3Attention,
DeepseekV3MLP, DeepseekV3MoE and DeepseekV3TopkRouter); ``u`` is a
sublayer's normed input, every matrix without bias, ``norm`` an RMSNorm
with a weight:

- embedding ``x = E[token]``; head ``logits = norm(x) W_head``, two
  matrices (not tied).
- layer ``i``: ``x = x + attention(norm(x))``, ``x = x + ff_i(norm(x))``;
  ``ff_i`` a dense SwiGLU for the first ``n_dense_layers`` layers, the
  routed layer plus the shared expert after.
- attention, the EXPANDED form and no other: ``c_q = norm_q(u W_qa)``,
  ``q = c_q W_qb`` [S, H, nope + rope]; ``[c_kv | k_r] = u W_kva``, ``c =
  norm_kv(c_kv)``; rotary on each head's ``q_rope`` and on the ONE
  shared ``k_r`` with YaRN's inverse frequencies (``inv_freq_i = f_i /
  factor x ramp_i + f_i (1 - ramp_i)``, the ramp between the pair
  indices that turn ``beta_fast`` and ``beta_slow`` times over the
  original context); ``[k_nope | v] = c W_kvb`` [S, H, nope + v], ``k =
  [k_nope | k_r]``; causal softmax of ``q k^T (nope + rope) ** -0.5 m **
  2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``; ``W_o``.
- dense: ``(silu(a) * b) W_out`` with ``[a | b] = u W_in``.
- routed: ``l = u W_g``; ``p = sigmoid(l)``; the ``top_k`` largest of
  ``p + bias`` (one group: no group limit); gates ``p_e / (sum of the
  picked p + 1e-20) * scale``; the sum of ``g_e expert_e(u)`` over the
  picks THAT THIS RANK HOLDS (``experts_held = (first, count)``: what
  the absent experts would add is left out here as in the program, and
  the partial sum goes on); plus the shared expert, once.

Everything in float32 with jax.numpy, matmuls at the highest precision,
no kernel, no cache, no batching, one sequence a call. It never carries
the query into the latent space: that the program's decode step, which
does, agrees with this is what the comparison shows.

It reads the parameter tree the program's mla_init draws (stacks
``attn``: in_norm, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, wo;
``dense``: ff_norm, w_in, w_out; ``moe``: ff_norm, router, router_bias,
w_in_e, w_out_e, w_in_s, w_out_s; embedding, lm_head, final_norm) and
nothing else of the program. DEPARTURES from the published description,
none of which changes a number on seeded weights: rotary pairs a rope
part's first half with its second (``rotate_half`` over the part as it
lies) where the published code first gathers neighbouring lanes, a
fixed permutation of ``W_qb``'s and ``W_kva``'s rotary columns that
``q_rope . k_rope`` does not see; a gated feed-forward's two input
projections are ONE matrix, the gated half first. It runs beside the
engine's weights on the chip: layers are walked one at a time, the
heads in blocks of ``HEAD_BLOCK``, a layer's experts in blocks of
``EXPERT_BLOCK`` and a dense feed-forward's width in blocks of
``FF_BLOCK`` columns, so that no [H, S, S] score array and no float32
copy of a whole weight stack is ever alive.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK = 8
EXPERT_BLOCK = 4
FF_BLOCK = 2048


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(weight)


def _gated(h, w_in, w_out):
    """``(silu(a) * b) W_out``, the width walked in blocks of FF_BLOCK
    columns (an expert's 2048 are one)."""
    inner = w_out.shape[0]
    block = FF_BLOCK if inner % FF_BLOCK == 0 else inner

    def some_columns(acc, start):
        a = h @ _f32(jax.lax.dynamic_slice_in_dim(w_in, start, block, 1))
        b = h @ _f32(jax.lax.dynamic_slice_in_dim(w_in, inner + start,
                                                  block, 1))
        return acc + (jax.nn.silu(a) * b) @ _f32(
            jax.lax.dynamic_slice_in_dim(w_out, start, block, 0)), None

    out, _ = jax.lax.scan(some_columns, jnp.zeros_like(h),
                          jnp.arange(0, inner, block))
    return out


def routed(h, layer, first: int, top_k: int, scale: float):
    """-> (the part of the routed layer's output [S, D] that the experts
    ``first .. first + held - 1`` add, margin [S]: the k-th less the
    (k+1)-th of the scores the choice is made on, ``p + bias``, picks
    [held]: how many of the S rows picked each held expert)."""
    p = jax.nn.sigmoid(h @ _f32(layer["router"]))            # [S, E]
    choice = p + _f32(layer["router_bias"])
    ranked = jnp.sort(choice, axis=-1)[:, ::-1]
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    _, idx = jax.lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(p, idx, axis=-1)            # [S, k]
    gate = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
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
    picks = jnp.sum(idx[:, :, None] == first + jnp.arange(held), (0, 1))
    return out, margin, picks


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies over ``dim`` rotary lanes: [dim / 2]."""
    def pair_of(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    f = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, inv_freq, amplitude):
    """x [S, heads, rope], position = row: the ``rotate_half`` form, the
    part's first half paired with its second."""
    seq, _, d = x.shape
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return (x * jnp.cos(angle) + rotated * jnp.sin(angle)) * amplitude


def _attention(u, layer, n_heads, nope, rope, v_dim, eps, yarn):
    s = u.shape[0]
    rank = layer["kv_norm"].shape[0]
    inv_freq = yarn_inv_freq(rope, yarn["theta"], yarn["factor"],
                             yarn["original_max"], yarn["beta_fast"],
                             yarn["beta_slow"])
    m_all = mscale(yarn["factor"], yarn["mscale_all_dim"])
    amplitude = mscale(yarn["factor"], yarn["mscale"]) / m_all
    scale = (nope + rope) ** -0.5 * m_all ** 2
    c_q = _rms_norm(u @ _f32(layer["w_qa"]), layer["q_norm"], eps)
    kv = u @ _f32(layer["w_kva"])
    c = _rms_norm(kv[:, :rank], layer["kv_norm"], eps)
    k_rope = _rope(kv[:, None, rank:], inv_freq, amplitude)   # [S, 1, rope]
    causal = jnp.tril(jnp.ones((s, s), bool))
    block = HEAD_BLOCK if n_heads % HEAD_BLOCK == 0 else 1
    w_qb = layer["w_qb"].reshape(-1, n_heads, nope + rope)
    w_kvb = layer["w_kvb"].reshape(rank, n_heads, nope + v_dim)
    wo = layer["wo"].reshape(n_heads, v_dim, -1)

    def some_heads(acc, start):
        q = jnp.einsum("sr,rhd->shd", c_q, _f32(
            jax.lax.dynamic_slice_in_dim(w_qb, start, block, 1)))
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], inv_freq, amplitude)], -1)
        kv_b = jnp.einsum("sr,rhd->shd", c, _f32(
            jax.lax.dynamic_slice_in_dim(w_kvb, start, block, 1)))
        k = jnp.concatenate(
            [kv_b[..., :nope],
             jnp.broadcast_to(k_rope, (s, block, rope))], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                         kv_b[..., nope:])
        return acc + jnp.einsum("qhd,hdo->qo", out, _f32(
            jax.lax.dynamic_slice_in_dim(wo, start, block, 0))), None

    out, _ = jax.lax.scan(some_heads, jnp.zeros_like(u),
                          jnp.arange(0, n_heads, block))
    return out


def _one(stack, index):
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, keepdims=False),
        stack)


def forward(params: Dict[str, Any], tokens, *, n_dense_layers: int,
            n_heads: int, qk_nope_dim: int, qk_rope_dim: int,
            v_head_dim: int, experts_held: Tuple[int, int], top_k: int,
            routed_scaling: float, yarn: Dict[str, float], norm_eps: float):
    """tokens [S] int32 -> (logits [S, vocab], margins [S], picks
    [routed layers, held]) float32, one sequence, one pass. A position's
    margin is the smallest over the routed layers of the k-th less the
    (k+1)-th selection score (``sigmoid(l) + bias``: what the picks are
    made on); ``picks`` counts the positions that picked each held
    expert, layer by layer."""
    n_layers = params["attn"]["in_norm"].shape[0]

    def attend(x, index):
        layer = _one(params["attn"], index)
        return x + _attention(_rms_norm(x, layer["in_norm"], norm_eps),
                              layer, n_heads, qk_nope_dim, qk_rope_dim,
                              v_head_dim, norm_eps, yarn)

    def dense_layer(x, index):
        x = attend(x, index)
        layer = _one(params["dense"], index)
        u = _rms_norm(x, layer["ff_norm"], norm_eps)
        return x + _gated(u, layer["w_in"], layer["w_out"]), None

    def routed_layer(x, index):
        x = attend(x, n_dense_layers + index)
        layer = _one(params["moe"], index)
        u = _rms_norm(x, layer["ff_norm"], norm_eps)
        out, margin, picks = routed(u, layer, experts_held[0], top_k,
                                    routed_scaling)
        return (x + out + _gated(u, layer["w_in_s"], layer["w_out_s"]),
                (margin, picks))

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens])
        margins = jnp.full(x.shape[:1], jnp.inf)
        picks = jnp.zeros((0, experts_held[1]), jnp.int32)
        if n_dense_layers:
            x, _ = jax.lax.scan(dense_layer, x, jnp.arange(n_dense_layers))
        if n_layers > n_dense_layers:
            x, (margin, picks) = jax.lax.scan(
                routed_layer, x, jnp.arange(n_layers - n_dense_layers))
            margins = margin.min(0)
        x = _rms_norm(x, params["final_norm"], norm_eps)
        return x @ _f32(params["lm_head"]), margins, picks


def logits_and_margins(params: Dict[str, Any], tokens, **kw):
    """tokens [S] int32 -> (logits [S, vocab], margins [S])."""
    return forward(params, tokens, **kw)[:2]


def logits(params: Dict[str, Any], tokens, **kw):
    """tokens [S] int32 -> logits [S, vocab] float32, one sequence."""
    return forward(params, tokens, **kw)[0]


def loss(params, tokens, targets, **kw):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(logits(params, tokens, **kw), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], 1))


def kwargs_from(config) -> Dict[str, Any]:
    """What ``logits`` needs, from the program's model configuration
    (an MlaConfig); the sizes are the parameter tree's."""
    return dict(
        n_dense_layers=config.n_dense_layers, n_heads=config.n_heads,
        qk_nope_dim=config.qk_nope_dim, qk_rope_dim=config.qk_rope_dim,
        v_head_dim=config.v_head_dim,
        experts_held=tuple(config.experts_held), top_k=config.top_k,
        routed_scaling=config.routed_scaling,
        yarn=dict(theta=config.rope_theta, factor=config.rope_factor,
                  original_max=config.rope_original_max,
                  beta_fast=config.rope_beta_fast,
                  beta_slow=config.rope_beta_slow,
                  mscale=config.rope_mscale,
                  mscale_all_dim=config.rope_mscale_all_dim),
        norm_eps=config.norm_eps)
