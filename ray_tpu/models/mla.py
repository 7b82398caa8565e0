"""Decoders with multi-head LATENT attention (MLA) over routed experts:
the DeepSeek-V3 block (``deepseek_v3``, ``kimi_k2``;
moonshotai/Kimi-K2.7-Code), served as ONE RANK of an expert-parallel
group with data-parallel attention.

Every layer is ``x = x + attention(norm(x))`` then ``x = x +
ff(norm(x))``; ``ff`` is a dense SwiGLU of width ``dense_dim`` for the
first ``n_dense_layers`` layers and the routed layer with its shared
expert after. One more norm, then the head; embedding and head are two
matrices.

- attention, ``h`` [T, dim]: ``c_q = norm_q(h W_qa)`` (``q_lora_rank``
  wide), ``q = c_q W_qb`` [T, H, nope + rope]; ``[c_kv | k_r] = h
  W_kva`` (``kv_lora_rank`` + rope), ``c = norm_kv(c_kv)``; rotary on
  each head's last ``rope`` lanes and on ``k_r``, ONE key head that all
  H query heads share, with YaRN's frequencies (``ops/rope.py``, the
  halves form: a fixed permutation of the published pairing's columns,
  which ``q_rope . k_rope`` does not see). What a position leaves in
  the cache is its LATENT ROW ``[c | k_r]``, ``kv_lora_rank + rope``
  values and not H heads of keys and values.
  * expanded (a prefill): ``[k_nope | v] = c W_kvb`` [T, H, nope + v],
    ``k = [k_nope | k_r]``, causal softmax of ``q k^T x s``, ``s =
    (nope + rope) ** -0.5 x m ** 2`` with ``m`` YaRN's attention factor
    (``ops.rope.yarn_mscale``), through ``flash_attention`` with keys of
    192 over values of 128.
  * absorbed (a decode step): ``W_kvb``'s head ``i`` is ``[W_UK^i |
    W_UV^i]``; ``q_lat^i = q_nope^i (W_UK^i)^T``, ``score = (q_lat^i . c
    + q_rope^i . k_r) x s``, ``o_lat^i = sum p c``, ``o^i = o_lat^i
    W_UV^i``: the same numbers, and one multi-query attention of H
    heads over the latent rows as they lie, through ``decode_attention``
    with the cache as K AND V (the query ``[q_lat | q_rope | 0]``, the
    output's first ``kv_lora_rank`` lanes ``o_lat``). No row is ever
    expanded.
- routed: ``parallel/moe.py::held_experts_ffn``, scoring ``sigmoid``
  with a selection bias (``noaux_tc``; one group, so no group limit),
  gates the picked sigmoids over ``their sum + 1e-20`` times
  ``routed_scaling``; this rank computes the experts ``experts_held =
  (first, count)`` and leaves the others' part out: a prefill's rows
  are sorted by expert and only the (row, pick) pairs that fell on the
  held experts are gathered and multiplied, a chunk of the sorted order
  at a time up to their counted number (a thirty-second of the ``top_k
  x bucket`` pairs at 12 of 384; every one of them, whatever the
  router's skew). The shared expert is on every rank's device and
  serves the rank's own rows, so it is counted once here.

Design for the TPU:

- Three stacks, layers on axis 0: ``attn`` (every layer's), ``dense``
  and ``moe``. The leading dense layers are walked in Python, the
  routed ones as one ``lax.scan`` over indices, so no stack is sliced
  into a copy and the expert layer gets the ``moe`` stack whole.
- The cache is ONE leaf, ``latent`` [L, B, S, 1, 640]: a row's 576
  values in whole tiles of 128 lanes, the last 64 zero (a TPU would pad
  a minor axis of 576 to 640 anyway). 1.25 KiB a position and layer
  where 64 expanded heads would take 40.
- A prefill into a padded bucket is exact by construction: attention
  is causal, so a real position never sees the padding, whose rows are
  junk that a decode step masks by position.
- Precision as in the other serving families: bf16 weights and matmul
  inputs, float32 between matmuls, the few-rows split in a decode step
  (ops/matmul.py), the absorption's two products among them.

Serving only: the expert layer's serving form holds no training batch.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import hybrid
from ray_tpu.models.family import LATENT, ModelFamily
from ray_tpu.models.hybrid import SCOPE_ATTN, layer as _layer
from ray_tpu.ops.attention import decode_attention, flash_attention
from ray_tpu.ops.matmul import few_rows, mm as _mm
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.rope import (apply_rope, rope_at, yarn_inv_freq,
                              yarn_mscale)
from ray_tpu.parallel.moe import (BIAS_COUNTS, EXPERT_COUNTS as _LAYER_COUNTS,
                                  Scoring, gated_ffn)

# jax.named_scope names inside the attention sublayer's "attn", so that
# a trace viewer groups device ops (the expert layer's "moe.router" and
# "moe.experts" are its module's)
SCOPE_Q = "mla.q"              # the two query projections and their norm
SCOPE_LATENT = "mla.latent"    # the latent row: projection, norm, rotary
SCOPE_EXPAND = "mla.expand"    # prefill: W_kvb over the positions
SCOPE_ABSORB = "mla.absorb"    # decode: into and out of the latent space
SCOPE_MLP = hybrid.SCOPE_MLP    # a dense feed-forward (hybrid.dense_or_routed)
SCOPE_SHARED = "moe.shared"
SCOPE_HEAD = "head"

_LANES = 128

# what the family's programs count on the device: the expert layer's
# five and what the selection bias did to the picks
EXPERT_COUNTS = _LAYER_COUNTS + BIAS_COUNTS


@dataclass(frozen=True)
class MlaConfig:
    vocab_size: int = 163840          # the rows of embedding and head HELD
    dim: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 1           # first_k_dense_replace
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    dense_dim: int = 18432            # intermediate_size
    n_experts: int = 384              # the router's outputs
    experts_held: Tuple[int, int] = (0, 384)   # (first index, count)
    top_k: int = 8
    expert_dim: int = 2048            # moe_intermediate_size
    shared_expert_dim: int = 2048     # n_shared_experts x expert_dim
    routed_scaling: float = 2.827
    # deviation of the selection bias ``mla_init`` draws (a trained
    # model's is a buffer moved by its load balancing; 0: no bias)
    router_bias_std: float = 0.01
    rope_theta: float = 50000.0
    rope_factor: float = 64.0         # rope_scaling (yarn)
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | reference

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(f"n_dense_layers {self.n_dense_layers} of "
                             f"{self.n_layers} layers")
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_experts} experts")
        if self.qk_rope_dim % 2:
            raise ValueError("rotary turns pairs: qk_rope_dim must be even")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_dim(self) -> int:
        """The values a position leaves in the cache: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_lanes(self) -> int:
        """A cache row's lanes: ``latent_dim`` in whole tiles of 128."""
        return -(-self.latent_dim // _LANES) * _LANES

    @property
    def sm_scale(self) -> float:
        """What the scores are multiplied by: the key width's ``**
        -0.5`` times the square of YaRN's attention factor."""
        return (self.qk_head_dim ** -0.5
                * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2)

    @property
    def rope_amplitude(self) -> float:
        """The factor on rotary's cos and sin (1 where ``mscale`` and
        ``mscale_all_dim`` agree, as the published ones do)."""
        return (yarn_mscale(self.rope_factor, self.rope_mscale)
                / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    @property
    def scoring(self) -> Scoring:
        return Scoring("sigmoid", eps=1e-20, scale=self.routed_scaling)

    @staticmethod
    def tiny(**kw) -> "MlaConfig":
        """Test-scale: one leading dense layer and three routed ones, 4
        heads of 16 + 8 over values of 16, a latent of 32, 16 experts of
        which this rank holds the first 4, top-3, a bias that moves
        picks, a rotary stretched 4 times over 128 positions."""
        defaults = dict(
            vocab_size=512, dim=64, n_layers=4, n_dense_layers=1,
            n_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16, dense_dim=96, n_experts=16,
            experts_held=(0, 4), top_k=3, expert_dim=32,
            shared_expert_dim=32, routed_scaling=1.5, router_bias_std=0.1,
            rope_theta=100.0, rope_factor=4.0, rope_original_max=128,
            rope_beta_fast=4.0, max_seq_len=128, attention="reference")
        defaults.update(kw)
        return MlaConfig(**defaults)


def mla_init(rng, config: MlaConfig) -> Dict[str, Any]:
    """The parameter pytree: ``embedding`` [V, dim], ``lm_head`` [dim,
    V], ``final_norm``, and three stacks (layers on axis 0): ``attn``
    (in_norm, w_qa [dim, q_lora_rank], q_norm, w_qb [q_lora_rank, H x
    (nope + rope)], w_kva [dim, kv_lora_rank + rope], kv_norm, w_kvb
    [kv_lora_rank, H x (nope + v)] a head's ``[k_nope | v]`` side by
    side, wo [H x v, dim]), ``dense`` (ff_norm, w_in [dim, 2 I] the
    gated half first, w_out) and ``moe`` (ff_norm, router [dim, E],
    router_bias [E] float32, the HELD experts' w_in_e [held, dim, 2 I]
    and w_out_e [held, I, dim], the shared expert's w_in_s, w_out_s).

    Matrices normal with ``fan_in ** -0.5``, as the other families draw
    them, norm weights 1, the selection bias normal with
    ``router_bias_std`` (a buffer, kept float32). Every matrix is drawn
    a layer at a time, so that no float32 draw of a whole stack is ever
    alive."""
    c = config
    h, held = c.n_heads, c.experts_held[1]
    keys = jax.random.split(rng, 5)

    dense, by_layer, ones = hybrid.drawers(c.dtype)
    n, d, e = c.n_layers, c.n_dense_layers, c.n_moe_layers
    ka = jax.random.split(keys[0], 5)
    attn = {
        "in_norm": ones(n, c.dim),
        "w_qa": by_layer(ka[0], n, (c.dim, c.q_lora_rank), c.dim),
        "q_norm": ones(n, c.q_lora_rank),
        "w_qb": by_layer(ka[1], n, (c.q_lora_rank, h * c.qk_head_dim),
                         c.q_lora_rank),
        "w_kva": by_layer(ka[2], n, (c.dim, c.latent_dim), c.dim),
        "kv_norm": ones(n, c.kv_lora_rank),
        "w_kvb": by_layer(ka[3], n, (c.kv_lora_rank,
                                     h * (c.qk_nope_dim + c.v_head_dim)),
                          c.kv_lora_rank),
        "wo": by_layer(ka[4], n, (h * c.v_head_dim, c.dim),
                       h * c.v_head_dim)}
    kd = jax.random.split(keys[1], 2)
    dense_ff = {
        "ff_norm": ones(d, c.dim),
        "w_in": by_layer(kd[0], d, (c.dim, 2 * c.dense_dim), c.dim),
        "w_out": by_layer(kd[1], d, (c.dense_dim, c.dim), c.dense_dim)}
    ke = jax.random.split(keys[2], 6)
    moe = {
        "ff_norm": ones(e, c.dim),
        "router": dense(ke[0], (e, c.dim, c.n_experts), c.dim),
        "router_bias": jax.random.normal(
            ke[1], (e, c.n_experts), jnp.float32) * c.router_bias_std,
        "w_in_e": by_layer(ke[2], e, (held, c.dim, 2 * c.expert_dim),
                           c.dim),
        "w_out_e": by_layer(ke[3], e, (held, c.expert_dim, c.dim),
                            c.expert_dim),
        "w_in_s": by_layer(ke[4], e, (c.dim, 2 * c.shared_expert_dim),
                           c.dim),
        "w_out_s": by_layer(ke[5], e, (c.shared_expert_dim, c.dim),
                            c.shared_expert_dim)}
    return {"embedding": dense(keys[3], (c.vocab_size, c.dim), c.dim),
            "lm_head": dense(keys[4], (c.dim, c.vocab_size), c.dim),
            "attn": attn, "dense": dense_ff, "moe": moe,
            "final_norm": ones(c.dim)}


def _ff(params, ff: str, index, x, live, c: MlaConfig):
    """The layer's second half, layer ``index`` of stack ``ff``. x [T,
    dim] -> (x, the layer's EXPERT_COUNTS uint32 over the ``live`` rows,
    zeros from a dense layer). This rank's experts of the routed ones,
    and the shared expert for every row."""
    return hybrid.dense_or_routed(params, ff, index, x, live, c,
                                  len(EXPERT_COUNTS), c.experts_held[0],
                                  _shared)


def _shared(p, h):
    with jax.named_scope(SCOPE_SHARED):
        return gated_ffn(h, p["w_in_s"], p["w_out_s"])


def _rotate(x, positions, c: MlaConfig):
    """Rotary on x [rows, heads, rope] float32, row ``i`` at
    ``positions[i]``, with YaRN's frequencies."""
    cos, sin = rope_at(positions, c.qk_rope_dim, inv_freq=yarn_inv_freq(
        c.qk_rope_dim, c.rope_theta, c.rope_factor, c.rope_original_max,
        c.rope_beta_fast, c.rope_beta_slow))
    if c.rope_amplitude != 1.0:
        cos, sin = cos * c.rope_amplitude, sin * c.rope_amplitude
    return apply_rope(x[None], cos, sin)[0]


def _q_and_latent(p, h, positions, c: MlaConfig):
    """What both forms share. h [rows, dim] float32, normed -> (q_nope
    [rows, H, nope], q_rope [rows, H, rope] rotated, the latent rows
    ``[c | k_r | 0]`` [rows, latent_lanes], ``c`` normed and ``k_r``
    rotated), all float32."""
    rows = h.shape[0]
    with jax.named_scope(SCOPE_Q):
        c_q = rms_norm(_mm(h, p["w_qa"]), p["q_norm"], c.norm_eps)
        q = _mm(c_q, p["w_qb"]).reshape(rows, c.n_heads, c.qk_head_dim)
        q_nope = q[..., :c.qk_nope_dim]
        q_rope = _rotate(q[..., c.qk_nope_dim:], positions, c)
    with jax.named_scope(SCOPE_LATENT):
        kv = _mm(h, p["w_kva"])
        latent = jnp.concatenate(
            [rms_norm(kv[:, :c.kv_lora_rank], p["kv_norm"], c.norm_eps),
             _rotate(kv[:, None, c.kv_lora_rank:], positions, c)[:, 0],
             jnp.zeros((rows, c.latent_lanes - c.latent_dim), jnp.float32)],
            axis=-1)
    return q_nope, q_rope, latent


def _attn_sequence(p, x, c: MlaConfig):
    """One attention layer over one sequence, the EXPANDED form. x [L,
    dim] float32 -> (x, the sequence's latent rows [L, latent_lanes] in
    the model's dtype, as the cache keeps them). Causal; the keys and
    values are expanded from the rows as the cache holds them."""
    seq, heads = x.shape[0], c.n_heads
    with jax.named_scope(SCOPE_ATTN):
        h = rms_norm(x, p["in_norm"], c.norm_eps)
        q_nope, q_rope, latent = _q_and_latent(p, h, jnp.arange(seq), c)
        latent = latent.astype(c.dtype)
        with jax.named_scope(SCOPE_EXPAND):
            kv = jnp.dot(latent[:, :c.kv_lora_rank], p["w_kvb"],
                         preferred_element_type=jnp.float32).reshape(
                             seq, heads, c.qk_nope_dim + c.v_head_dim)
        k_rope = jnp.broadcast_to(
            latent[:, None, c.kv_lora_rank:c.latent_dim],
            (seq, heads, c.qk_rope_dim))
        q = jnp.concatenate([q_nope, q_rope], -1).astype(c.dtype)[None]
        k = jnp.concatenate([kv[..., :c.qk_nope_dim].astype(c.dtype),
                             k_rope], -1)[None]
        v = kv[..., c.qk_nope_dim:].astype(c.dtype)[None]
        if c.attention == "flash":
            out = flash_attention(q, k, v, True, sm_scale=c.sm_scale)
        else:
            from ray_tpu.ops.attention import _attention_reference
            out = _attention_reference(q, k, v, True, c.sm_scale)
        x = x + _mm(out.reshape(seq, heads * c.v_head_dim), p["wo"])
    return x, latent


def _attn_decode(p, x, cache, a, pos, c: MlaConfig):
    """One attention layer for every slot, the ABSORBED form. x [B,
    dim]; ``cache`` the latent rows [L, B, S, 1, latent_lanes], of which
    this is layer ``a``; pos [B]. The layer writes its row at ``pos``
    and hands the stacked cache, as keys and as values, its index and
    ``pos`` to ``decode_attention``. -> (x, cache), written in place
    where the caller's program donates the cache."""
    b, heads, rank = x.shape[0], c.n_heads, c.kv_lora_rank
    with jax.named_scope(SCOPE_ATTN):
        h = rms_norm(x, p["in_norm"], c.norm_eps)
        q_nope, q_rope, latent = _q_and_latent(p, h, pos, c)
        cache = cache.at[a, jnp.arange(b), pos, 0].set(
            latent.astype(cache.dtype))
        # W_kvb by heads: a head's [W_UK | W_UV]
        w = p["w_kvb"].reshape(rank, heads, c.qk_nope_dim + c.v_head_dim)
        with jax.named_scope(SCOPE_ABSORB):
            both, merge = few_rows(q_nope.transpose(1, 0, 2), c.dtype)
            q_lat = merge(jnp.einsum(
                "hbd,chd->hbc", both, w[..., :c.qk_nope_dim],
                preferred_element_type=jnp.float32))          # [H, B, rank]
        q = jnp.concatenate(
            [q_lat.transpose(1, 0, 2), q_rope,
             jnp.zeros((b, heads, c.latent_lanes - c.latent_dim),
                       jnp.float32)], -1).astype(c.dtype)
        out = decode_attention(q[:, None], cache, cache, a, pos, c.dtype,
                               sm_scale=c.sm_scale)  # [B, 1, H, lanes]
        with jax.named_scope(SCOPE_ABSORB):
            both, merge = few_rows(out[:, 0, :, :rank].transpose(1, 0, 2),
                                   c.dtype)
            o = merge(jnp.einsum(
                "hbc,chd->hbd", both, w[..., c.qk_nope_dim:],
                preferred_element_type=jnp.float32))          # [H, B, v]
        x = x + _mm(o.transpose(1, 0, 2).reshape(b, heads * c.v_head_dim),
                    p["wo"])
    return x, cache


def _trunk(params, tokens, length, c: MlaConfig):
    """tokens [L] int32 -> (hidden [L, dim] before the final norm, the
    sequence's cache entry as mla_init_cache lays it out, with a slot
    axis of one, EXPERT_COUNTS uint32 summed over the layers, of the
    positions before ``length``)."""
    x = params["embedding"][tokens].astype(jnp.float32)
    live = jnp.arange(tokens.shape[0]) < length
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    rows = []
    for i in range(c.n_dense_layers):
        x, latent = _attn_sequence(_layer(params["attn"], i), x, c)
        x, _ = _ff(params, "dense", i, x, live, c)
        rows.append(latent[None])

    def body(carry, i):
        x, counts = carry
        x, latent = _attn_sequence(
            _layer(params["attn"], c.n_dense_layers + i), x, c)
        x, n = _ff(params, "moe", i, x, live, c)
        return (x, counts + n), latent

    if c.n_moe_layers:
        (x, counts), latent = jax.lax.scan(body, (x, counts),
                                           jnp.arange(c.n_moe_layers))
        rows.append(latent)
    return x, {"latent": jnp.concatenate(rows)[:, None, :, None]}, counts


def _head(params, x, c: MlaConfig):
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps)
        return jnp.dot(x.astype(c.dtype), params["lm_head"],
                       preferred_element_type=jnp.float32)


def mla_forward(params, tokens, config: MlaConfig,
                return_hidden: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, or with
    ``return_hidden`` the final-norm hidden states [B, S, dim]. Whole
    sequences, one at a time, the expanded form (the tests and
    engine.embed)."""
    return hybrid.forward(_trunk, _head, params, tokens, config,
                          return_hidden)


def mla_init_cache(config: MlaConfig, batch: int, max_seq: int):
    """The serving cache, one pytree of ONE leaf with the slot on axis
    1: ``latent`` [L, B, S, 1, latent_lanes], a position's ``[c | k_r |
    0]`` (one "KV head" of ``latent_lanes``, as ``decode_attention``
    takes a cache)."""
    c = config
    return {"latent": jnp.zeros((c.n_layers, batch, max_seq, 1,
                                 c.latent_lanes), c.dtype)}


def mla_prefill(params, tokens, length, config: MlaConfig, lora=None):
    """Forward over one prompt padded to a bucket. tokens [1, bucket]
    int32, ``length`` its true length (traced: one program a bucket) ->
    (logits [1, 1, vocab] float32 of position length - 1, that slot's
    cache entry: the prompt's LATENT rows, EXPERT_COUNTS uint32 of the
    prompt's own positions; a prefill counts no expert slots). Rows at
    padded positions are junk that decode never attends (it masks by
    position)."""
    c = config
    x, entry, counts = _trunk(params, tokens[0], length, c)
    return hybrid.prefill_result(_head, params, c, x, length, entry,
                                 counts, EXPERT_COUNTS)


def mla_decode_step(params, token, cache, pos, live, config: MlaConfig,
                    lora_bank=None, lora_idx=None):
    """One token for every slot. token, pos: [B] int32 (the token at
    position ``pos``); ``live`` [B]: which slots hold a request (the
    others are parked: computed, not counted); ``cache`` as
    mla_init_cache gives it. -> (logits [B, vocab] float32, the cache
    with every slot's latent row written at ``pos``, EXPERT_COUNTS
    uint32 of this step). The caller's program must donate the cache and
    run on one device, and every ``pos`` must lie in ``[0, S-1]``."""
    c = config
    x = params["embedding"][token].astype(jnp.float32)           # [B, D]
    live = live.astype(bool)
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    latent = cache["latent"]
    for i in range(c.n_dense_layers):
        x, latent = _attn_decode(_layer(params["attn"], i), x, latent, i,
                                 pos, c)
        x, _ = _ff(params, "dense", i, x, live, c)

    def body(carry, i):
        x, latent, counts = carry
        a = c.n_dense_layers + i
        x, latent = _attn_decode(_layer(params["attn"], a), x, latent, a,
                                 pos, c)
        x, n = _ff(params, "moe", i, x, live, c)
        return (x, latent, counts + n), None

    if c.n_moe_layers:
        (x, latent, counts), _ = jax.lax.scan(
            body, (x, latent, counts), jnp.arange(c.n_moe_layers))
    return _head(params, x, c), {"latent": latent}, counts


FAMILY = ModelFamily.of(
    init=mla_init, forward=mla_forward, init_cache=mla_init_cache,
    prefill=mla_prefill, decode_step=mla_decode_step, dense_only=LATENT,
    expert_counts=EXPERT_COUNTS,
    kv_row_shape=lambda c: (1, c.latent_lanes))
