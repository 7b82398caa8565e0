"""Granite-4.0-H family decoder (``granitemoehybrid``): Mamba-2 mixers
beside an attention layer now and then, a routed and a shared
feed-forward in every layer, served as ONE RANK of an expert-parallel
group.

Every layer is ``x = x + r * mixer(norm(x))`` then ``x = x + r *
(routed(h) + shared(h))`` with ``h = norm(x)`` and ``r`` the
``residual_multiplier``. The embedding is multiplied by
``embedding_multiplier`` and tied to the head, whose logits are divided
by ``logits_scaling``. ``layer_types`` says, layer by layer, which mixer.

- Mamba-2 mixer, ``u`` [L, dim]: ``[z | xBC | dt] = u W_in``; ``xBC =
  silu(conv(xBC))``, causal, depthwise, width ``d_conv``, with bias;
  ``xBC = [x | B | C]`` with ``x`` [H, P] (heads of ``head_dim`` P), B
  and C [N] (one group); ``dt = softplus(dt + dt_bias)`` [H], ``A =
  -exp(A_log)`` [H]: a scalar decay a head. In float32, per head ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t`` (h [P, N]), ``y_t = h_t
  C_t + D x_t``; then ``y = rmsnorm(y * silu(z)) * w`` over all d_inner
  channels and ``y W_out``.
- attention: grouped-query, causal, no position encoding, scores times
  ``attention_multiplier`` (NOT ``head_dim ** -0.5``): models/hybrid.py
  scales ``q`` by the quotient and the kernels keep their scale.
- routed: the router over all ``n_experts``, the ``top_k`` largest,
  gates a softmax over those; this rank computes the experts
  ``experts_held = (first, count)`` and leaves the others' part out
  (parallel/moe.py ``held_experts_ffn``: nothing dropped, no capacity).
  The shared expert is on every rank's device and serves the rank's own
  rows, so it is counted once here.

Design for the TPU:

- Two stacks (``params["mamba"]``, ``params["attn"]``, layers on axis 0,
  each with its feed-forwards) walked by runs of one kind as
  ``lax.scan`` segments (models/hybrid.py).
- The state a decode step carries: ``ssm`` [M, B, H, P, N] float32 with
  the N = 128 states LAST, on a float32 tile's 128 lanes, and the P =
  64 rows of a head on its sublanes (64 = 8 x 8): no padding, where [H,
  N, P] would pad 64 lanes to 128 and double the 4.19 MB a layer and
  slot; ``conv`` [M, B, d_conv - 1, d_inner + 2N]; ``k`` / ``v`` [A, B,
  S, KVH, HD]. All ride in the layer loops' carry, written in place;
  the program that calls ``granite_decode_step`` donates the cache.
- The prefill's recurrence is the chunked form (``chunk_size``): inside
  a chunk the masked product ``(L * (C B^T)) (dt x)``, between chunks
  the state carried. All matmuls, float32 at the highest precision:
  about 8.5 MFLOP a token and layer beside the input projection's 137.
- A prefill into a padded bucket is exact by construction: a position
  at or past ``length`` has ``dt = 0``, so it neither decays nor feeds
  the state, and the convolution's carried inputs are those of the
  last ``d_conv - 1`` real positions.
- Precision as in models/jamba.py: bf16 weights and matmul inputs,
  float32 between matmuls, the few-rows split in a decode step
  (ops/matmul.py). The state stays float32.

Serving only: the chunked recurrence has no backward written.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import hybrid
from ray_tpu.models.family import CONSUMED, ModelFamily
from ray_tpu.models.hybrid import (attn_decode, attn_sequence,
                                   layer as _layer, runs)
from ray_tpu.ops.matmul import mm as _mm
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.ssd_update import ssd_update
from ray_tpu.parallel.moe import (EXPERT_COUNTS, gated_ffn,
                                  held_experts_ffn)

# jax.named_scope names, so that a trace viewer groups device ops
SCOPE_IN_PROJ = "ssd.in_proj"
SCOPE_CONV = "ssd.conv"
SCOPE_SCAN = "ssd.scan"          # prefill: the chunked recurrence
SCOPE_UPDATE = "ssd.update"      # decode: one step of it for every slot
SCOPE_OUT_PROJ = "ssd.out_proj"
SCOPE_SHARED = "moe.shared"
SCOPE_HEAD = "head"

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352          # the rows of the embedding HELD
    dim: int = 4096
    layer_types: Tuple[str, ...] = (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    n_experts: int = 72               # the router's outputs
    experts_held: Tuple[int, int] = (0, 72)   # (first index, count)
    top_k: int = 10
    expert_dim: int = 768             # intermediate_size
    shared_expert_dim: int = 1536     # shared_intermediate_size
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | reference

    def __post_init__(self):
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C is implemented "
                             f"(mamba_n_groups={self.mamba_n_groups})")
        if self.d_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_expand * dim must equal "
                             "mamba_n_heads * mamba_d_head")
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_experts} experts")
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``layer_types`` under the stacks' names."""
        return tuple("attn" if t == "attention" else "mamba"
                     for t in self.layer_types)

    @property
    def n_attn_layers(self) -> int:
        return self.layer_kinds.count("attn")

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_kinds.count("mamba")

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        return runs(self.layer_kinds)

    @property
    def q_scale(self) -> float:
        """What ``q`` is multiplied by, so that kernels that scale the
        scores by ``head_dim ** -0.5`` give ``attention_multiplier``."""
        return self.attention_multiplier * self.head_dim ** 0.5

    @staticmethod
    def tiny(**kw) -> "GraniteConfig":
        """Test-scale: both kinds of layer in four, 8 experts of which
        this rank holds the first 4, top-3, multipliers that are not
        1 and a score scale that is not ``head_dim ** -0.5``."""
        defaults = dict(
            vocab_size=512, dim=64,
            layer_types=("mamba", "mamba", "attention", "mamba"),
            n_heads=4, n_kv_heads=2, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, mamba_chunk_size=16, n_experts=8,
            experts_held=(0, 4), top_k=3, expert_dim=32,
            shared_expert_dim=48, embedding_multiplier=3.0,
            attention_multiplier=0.125, residual_multiplier=0.5,
            logits_scaling=2.0, max_seq_len=128, attention="reference")
        defaults.update(kw)
        return GraniteConfig(**defaults)


def granite_init(rng, config: GraniteConfig) -> Dict[str, Any]:
    """The parameter pytree: ``embedding`` (tied to the head; the rows
    this rank holds), ``final_norm``, and the two stacks ``mamba`` and
    ``attn`` (layers on axis 0), each layer with its router over all
    experts, the HELD experts' stacked weights and the shared expert.

    Matrices as the other families draw them (normal, ``fan_in **
    -0.5``); an ``input_linear``'s first half is the gated one. The
    mixer's own parameters by Mamba-2's convention: ``A_log =
    log(uniform(1, 16))`` a head, ``D = 1``, ``dt_bias`` the inverse
    softplus of a log-uniform draw in [1e-3, 1e-1]; these three stay
    float32. An expert stack is drawn a layer at a time, so that no
    float32 draw of a whole stack is ever alive."""
    c = config
    hd, di = c.head_dim, c.d_inner
    heads, held = c.mamba_n_heads, c.experts_held[1]
    k_embed, k_mamba, k_attn = jax.random.split(rng, 3)

    dense, by_layer, ones = hybrid.drawers(c.dtype)
    def ffn(keys, layers):
        return {
            "ff_norm": ones(layers, c.dim),
            "router": dense(keys[0], (layers, c.dim, c.n_experts), c.dim),
            "w_in_e": by_layer(keys[1], layers,
                               (held, c.dim, 2 * c.expert_dim), c.dim),
            "w_out_e": by_layer(keys[2], layers,
                                (held, c.expert_dim, c.dim), c.expert_dim),
            "w_in_s": dense(keys[3], (layers, c.dim,
                                      2 * c.shared_expert_dim), c.dim),
            "w_out_s": dense(keys[4], (layers, c.shared_expert_dim, c.dim),
                             c.shared_expert_dim)}

    m, a = c.n_mamba_layers, c.n_attn_layers
    km = jax.random.split(k_mamba, 11)
    dt = jnp.exp(jax.random.uniform(km[4], (m, heads), jnp.float32)
                 * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
    mamba = {
        "in_norm": ones(m, c.dim),
        "w_in": dense(km[0], (m, c.dim, di + c.conv_dim + heads), c.dim),
        "conv_w": dense(km[1], (m, c.mamba_d_conv, c.conv_dim),
                        c.mamba_d_conv),
        "conv_b": dense(km[2], (m, c.conv_dim), c.mamba_d_conv),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            km[3], (m, heads), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((m, heads), jnp.float32),
        "norm_w": ones(m, di),
        "w_out": dense(km[5], (m, di, c.dim), di),
        **ffn(km[6:11], m)}
    ka = jax.random.split(k_attn, 9)
    attn = {
        "in_norm": ones(a, c.dim),
        "wq": dense(ka[0], (a, c.dim, c.n_heads * hd), c.dim),
        "wk": dense(ka[1], (a, c.dim, c.n_kv_heads * hd), c.dim),
        "wv": dense(ka[2], (a, c.dim, c.n_kv_heads * hd), c.dim),
        "wo": dense(ka[3], (a, c.n_heads * hd, c.dim), c.n_heads * hd),
        **ffn(ka[4:9], a)}
    return {"embedding": dense(k_embed, (c.vocab_size, c.dim), c.dim),
            "mamba": mamba, "attn": attn, "final_norm": ones(c.dim)}


def _ffn(p, stack, index, x, live, c: GraniteConfig):
    """The layer's second half: the held experts' part of the routed
    feed-forward and the shared expert, times the residual multiplier.
    ``p`` is layer ``index`` of ``stack``; the experts' weights go to
    the expert layer as the stack's, so that none is sliced out. x [T,
    dim] -> (x, the layer's EXPERT_COUNTS uint32 over the ``live``
    rows)."""
    h = rms_norm(x, p["ff_norm"], c.norm_eps)
    # under the scopes moe.router and moe.experts
    routed, counts = held_experts_ffn(
        h, p["router"], stack["w_in_e"], stack["w_out_e"],
        c.experts_held[0], top_k=c.top_k, live=live, layer=index)
    with jax.named_scope(SCOPE_SHARED):
        shared = gated_ffn(h, p["w_in_s"], p["w_out_s"])
    return x + c.residual_multiplier * (routed + shared), counts


def _split_in_proj(zxbcdt, c: GraniteConfig):
    di = c.d_inner
    return (zxbcdt[..., :di], zxbcdt[..., di:di + c.conv_dim],
            zxbcdt[..., di + c.conv_dim:])


def _gated_norm_out(p, x, y, z, c: GraniteConfig):
    """The mixer's end: ``rmsnorm(y * silu(z)) * w`` over all d_inner
    channels, the output projection, the residual."""
    with jax.named_scope(SCOPE_OUT_PROJ):
        y = rms_norm(y * jax.nn.silu(z), p["norm_w"], c.norm_eps)
        return x + c.residual_multiplier * _mm(y, p["w_out"])


def ssd_chunked(xs, dt, a, b, cc, chunk: int):
    """Mamba-2's recurrence over one sequence from a zero state, in the
    chunked ("state-space dual") form. xs [L, H, P], dt [L, H] (0 at a
    position that is to leave the state alone), a [H] (negative), b and
    cc [L, N], all float32 -> (y [L, H, P] without the ``D x`` term,
    the state [H, P, N] after the last position).

    With ``l_t = dt_t a`` and ``cum`` its running sum inside a chunk:
    within a chunk ``y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s)
    dt_s x_s``, a masked [Q, Q] product a head; a chunk hands on ``S =
    sum_s exp(cum_Q - cum_s) dt_s x_s (outer) B_s`` and the state
    before it decays by ``exp(cum_Q)``; the state carried in adds
    ``exp(cum_t) (h C_t)``. Matmuls at the highest precision: the decay
    factors are what the state is made of."""
    seq, heads, p = xs.shape
    q = min(chunk, seq)
    nc = -(-seq // q)
    if nc * q != seq:
        # up to whole chunks with positions of dt = 0, which neither
        # decay nor feed the state
        xs, dt, b, cc = (jnp.pad(v, ((0, nc * q - seq),)
                                 + ((0, 0),) * (v.ndim - 1))
                         for v in (xs, dt, b, cc))
    xs = (xs * dt[:, :, None]).reshape(nc, q, heads, p)       # dt x
    b, cc = b.reshape(nc, q, -1), cc.reshape(nc, q, -1)
    cum = jnp.cumsum((dt * a).reshape(nc, q, heads), axis=1)  # [c, Q, H]
    # inside the chunks
    diff = cum[:, :, None, :] - cum[:, None, :, :]            # [c, t, s, H]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("ctn,csn->cts", cc, b, precision=_HIGHEST)
    y = jnp.einsum("ctsh,cshp->cthp", cb[..., None] * decay, xs,
                   precision=_HIGHEST)
    # what each chunk hands on, and the states between the chunks
    to_end = jnp.exp(cum[:, -1:, :] - cum)                    # [c, Q, H]
    handed = jnp.einsum("csh,cshp,csn->chpn", to_end, xs, b,
                        precision=_HIGHEST)

    def carry(h, inp):
        whole, s = inp
        return whole[:, None, None] * h + s, h

    last, before = jax.lax.scan(
        carry, jnp.zeros(handed.shape[1:], jnp.float32),
        (jnp.exp(cum[:, -1, :]), handed))
    y = y + jnp.einsum("chpn,ctn->cthp", before, cc,
                       precision=_HIGHEST) * jnp.exp(cum)[..., None]
    return y.reshape(nc * q, heads, p)[:seq], last


def _mamba_sequence(p, x, length, c: GraniteConfig):
    """One Mamba-2 layer's mixer over one sequence. x [L, dim] float32
    -> (x, ssm [H, P, N] float32 after position length - 1, conv
    [d_conv - 1, conv_dim]: the convolution's inputs at the last real
    positions)."""
    di, n, taps = c.d_inner, c.mamba_d_state, c.mamba_d_conv
    seq = x.shape[0]
    u = rms_norm(x, p["in_norm"], c.norm_eps)
    with jax.named_scope(SCOPE_IN_PROJ):
        z, xbc, dt = _split_in_proj(_mm(u, p["w_in"]), c)
    with jax.named_scope(SCOPE_CONV):
        # position t sees t-3..t, zeros before the start
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        xc = p["conv_b"].astype(jnp.float32)
        for k in range(taps):
            xc = xc + padded[k:k + seq] * p["conv_w"][k].astype(jnp.float32)
        xc = jax.nn.silu(xc)
        conv = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, 0)
    with jax.named_scope(SCOPE_SCAN):
        xs = xc[:, :di].reshape(seq, c.mamba_n_heads, c.mamba_d_head)
        dt = jnp.where(jnp.arange(seq)[:, None] < length,
                       jax.nn.softplus(dt + p["dt_bias"]), 0.0)
        y, ssm = ssd_chunked(xs, dt, -jnp.exp(p["A_log"]), xc[:, di:di + n],
                             xc[:, di + n:], c.mamba_chunk_size)
        y = (y + p["D"][None, :, None] * xs).reshape(seq, di)
    return _gated_norm_out(p, x, y, z, c), ssm, conv


def _trunk(params, tokens, length, c: GraniteConfig):
    """tokens [L] int32 -> (hidden [L, dim] before the final norm, the
    sequence's cache entry as granite_init_cache lays it out, with a
    slot axis of one, EXPERT_COUNTS uint32 summed over the layers,
    of the positions before ``length``)."""
    x = (params["embedding"][tokens].astype(jnp.float32)
         * c.embedding_multiplier)
    live = jnp.arange(tokens.shape[0]) < length
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    ks, vs, ssms, convs = [], [], [], []
    for kind, first, count in c.runs:
        if kind == "attn":
            for a in range(first, first + count):
                p = _layer(params["attn"], a)
                x, k, v = attn_sequence(p, x, c, c.q_scale,
                                        c.residual_multiplier)
                x, n = _ffn(p, params["attn"], a, x, live, c)
                counts = counts + n
                ks.append(k)
                vs.append(v)
            continue

        def body(carry, m):
            x, counts = carry
            p = _layer(params["mamba"], m)
            x, ssm, conv = _mamba_sequence(p, x, length, c)
            x, n = _ffn(p, params["mamba"], m, x, live, c)
            return (x, counts + n), (ssm, conv)

        (x, counts), (ssm, conv) = jax.lax.scan(
            body, (x, counts), jnp.arange(first, first + count))
        ssms.append(ssm)
        convs.append(conv)
    entry = {"k": jnp.stack(ks)[:, None], "v": jnp.stack(vs)[:, None],
             "ssm": jnp.concatenate(ssms)[:, None],
             "conv": jnp.concatenate(convs)[:, None].astype(c.dtype)}
    return x, entry, counts


def _head(params, x, c: GraniteConfig):
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(c.dtype),
                          params["embedding"],
                          preferred_element_type=jnp.float32) \
            / c.logits_scaling


def granite_forward(params, tokens, config: GraniteConfig,
                    return_hidden: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, or with
    ``return_hidden`` the final-norm hidden states [B, S, dim]. Whole
    sequences, one at a time (the tests and engine.embed)."""
    return hybrid.forward(_trunk, _head, params, tokens, config,
                          return_hidden)


def granite_init_cache(config: GraniteConfig, batch: int, max_seq: int):
    """The serving cache, one pytree whose every leaf has the slot on
    axis 1: ``k`` / ``v`` [A, B, S, KVH, HD], ``ssm`` [M, B, H, P, N]
    float32 (the states last: see the module's text) and ``conv`` [M,
    B, d_conv - 1, conv_dim]."""
    c = config
    kv = (c.n_attn_layers, batch, max_seq, c.n_kv_heads, c.head_dim)
    m = c.n_mamba_layers
    return {"k": jnp.zeros(kv, c.dtype), "v": jnp.zeros(kv, c.dtype),
            "ssm": jnp.zeros((m, batch, c.mamba_n_heads, c.mamba_d_head,
                              c.mamba_d_state), jnp.float32),
            "conv": jnp.zeros((m, batch, c.mamba_d_conv - 1, c.conv_dim),
                              c.dtype)}


def granite_prefill(params, tokens, length, config: GraniteConfig,
                    lora=None):
    """Forward over one prompt padded to a bucket. tokens [1, bucket]
    int32, ``length`` its true length (traced: one program a bucket) ->
    (logits [1, 1, vocab] float32 of position length - 1, that slot's
    cache entry, EXPERT_COUNTS uint32 of the prompt's own
    positions; a prefill counts no expert slots). K/V rows at padded
    positions are junk that decode never attends (it masks by
    position); the recurrent state is that of the true last token."""
    c = config
    x, entry, counts = _trunk(params, tokens[0], length, c)
    return hybrid.prefill_result(_head, params, c, x, length, entry,
                                 counts, EXPERT_COUNTS)


def granite_decode_step(params, token, cache, pos, live,
                        config: GraniteConfig, lora_bank=None,
                        lora_idx=None):
    """One token for every slot. token, pos: [B] int32 (the token at
    position ``pos``); ``live`` [B]: which slots hold a request (the
    others are parked: computed, not counted); ``cache`` as
    granite_init_cache gives it. -> (logits [B, vocab] float32, the
    cache with every live slot's state moved one step and every slot's
    K/V row written at ``pos``, EXPERT_COUNTS uint32 of this step).

    Only a live slot's recurrent state moves (``ops.ssd_update``: one
    pass over it, in place in the stack): a parked slot's keeps the
    bytes it had, which the next admission replaces whole, and its
    mixer output is zero. The caller's program must donate the cache
    and run on one device, and every ``pos`` must lie in ``[0, S-1]``."""
    c = config
    b = token.shape[0]
    di, n = c.d_inner, c.mamba_d_state
    x = (params["embedding"][token].astype(jnp.float32)
         * c.embedding_multiplier)                               # [B, D]
    live = live.astype(bool)
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    k_cache, v_cache = cache["k"], cache["v"]
    ssm, conv = cache["ssm"], cache["conv"]

    def mamba_body(carry, m):
        x, ssm, conv, counts = carry
        p = _layer(params["mamba"], m)
        u = rms_norm(x, p["in_norm"], c.norm_eps)
        with jax.named_scope(SCOPE_IN_PROJ):
            z, xbc, dt = _split_in_proj(_mm(u, p["w_in"]), c)
        with jax.named_scope(SCOPE_CONV):
            window = jnp.concatenate(
                [jax.lax.dynamic_index_in_dim(conv, m, keepdims=False)
                 .astype(jnp.float32), xbc[:, None, :]],
                axis=1)                               # [B, taps, conv_dim]
            xc = jax.nn.silu(
                jnp.sum(window * p["conv_w"].astype(jnp.float32)[None],
                        axis=1) + p["conv_b"].astype(jnp.float32))
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, window[:, 1:].astype(conv.dtype), m, 0)
        with jax.named_scope(SCOPE_UPDATE):
            xs = xc[:, :di].reshape(b, c.mamba_n_heads, c.mamba_d_head)
            bb, cc = xc[:, di:di + n], xc[:, di + n:]
            dt = jax.nn.softplus(dt + p["dt_bias"])              # [B, H]
            ssm, y = ssd_update(
                ssm, m, live, jnp.exp(dt * -jnp.exp(p["A_log"])),
                dt[:, :, None] * xs, bb, cc, p["D"][None, :, None] * xs)
            y = y.reshape(b, di)
        x = _gated_norm_out(p, x, y, z, c)
        x, n_layer = _ffn(p, params["mamba"], m, x, live, c)
        return (x, ssm, conv, counts + n_layer), None

    for kind, first, count in c.runs:
        if kind == "mamba":
            (x, ssm, conv, counts), _ = jax.lax.scan(
                mamba_body, (x, ssm, conv, counts),
                jnp.arange(first, first + count))
            continue
        for a in range(first, first + count):
            p = _layer(params["attn"], a)
            x, k_cache, v_cache = attn_decode(
                p, x, k_cache, v_cache, a, pos, c, c.q_scale,
                c.residual_multiplier)
            x, n_layer = _ffn(p, params["attn"], a, x, live, c)
            counts = counts + n_layer
    logits = _head(params, x, c)
    return logits, {"k": k_cache, "v": v_cache, "ssm": ssm,
                    "conv": conv}, counts


FAMILY = ModelFamily.of(
    init=granite_init, forward=granite_forward,
    init_cache=granite_init_cache, prefill=granite_prefill,
    decode_step=granite_decode_step, dense_only=CONSUMED,
    expert_counts=EXPERT_COUNTS, skips_parked_state=True)
