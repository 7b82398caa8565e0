"""LFM2-MoE family decoder (``lfm2_moe``; LiquidAI/LFM2-8B-A1B): gated
short-convolution mixers beside an attention layer now and then, a few
leading dense feed-forwards and then a routed one in every layer, its
router a sigmoid with a selection bias.

Every layer is ``x = x + op(norm(x))`` then ``x = x + ff(norm(x))``.
``layer_types[i]`` says which ``op``; ``ff`` is a dense SwiGLU of width
``dense_dim`` for ``i < n_dense_layers`` and the routed layer after.
After the last layer one more norm (the family's ``embedding_norm``,
here ``final_norm``), then the head; embedding and head are two
matrices.

- short convolution, ``u`` [L, dim]: ``[B | C | z] = u W_in`` (three
  equal parts in that order); ``s = B * z``; ``c_t = sum_j w[j] *
  s_{t - (taps - 1) + j}``, causal, depthwise, ``conv_taps`` = 3 taps,
  no bias, zeros before the sequence; ``y = (C * c) W_out``. No
  activation, no recurrence: what a decode step consumes is the last
  ``taps - 1`` columns of ``s``.
- attention: grouped-query, causal; ``q`` and ``k`` through an RMSNorm
  over each head (a weight of ``head_dim``), then rotary over the whole
  head in the ``rotate_half`` form (``ops/rope.py``, the Llama family's);
  scores times ``head_dim ** -0.5``. models/hybrid.py's sublayer with
  ``qk=_norm_rope``: the two families before this one do nothing to
  ``q`` and ``k`` there, so the sublayer takes what a family does as a
  function and stays one.
- routed: ``parallel/moe.py::held_experts_ffn`` with every expert held
  (``first = 0``), scoring ``sigmoid``: picks by ``sigmoid(l) + b``,
  gates the picked sigmoids over ``their sum + 1e-6`` times
  ``routed_scaling``. No shared expert.

Design for the TPU:

- Four stacks, layers on axis 0: the mixers' ``conv`` and ``attn``, the
  feed-forwards' ``dense`` and ``moe``. A layer is one of each pair;
  the trunk is walked by runs of consecutive layers of one (mixer,
  feed-forward) kind, a conv run as a ``lax.scan`` segment over
  indices, so no stack is sliced into a copy and the expert layer gets
  the ``moe`` stack whole.
- The cache: ``k`` / ``v`` [A, B, S, KVH * HD / 128, 128] and ``conv``
  [M, B, taps - 1, dim], all in the model's dtype (8 KiB of state a slot
  and layer at the published width). Heads of 64 reach the kernels
  written for 128 lanes through ``ops/attention.py``: a prefill's are
  zero-padded (``_flash_padded``), the cache keeps two KV heads a row
  of 128 lanes (``cache_row_shape``; a TPU would pad a minor axis of 64
  to twice its bytes) and the decode kernel reads it as it lies.
- A prefill into a padded bucket is exact by construction: the state it
  hands over is ``s`` at positions ``length - 2, length - 1``, and a
  causal layer's real positions never see the padding.
- A decode step computes parked slots too and writes their state (8 KiB
  a slot and layer: skipping them would save nothing); an admission
  replaces a slot's state whole.
- Precision as in models/jamba.py: bf16 weights and matmul inputs,
  float32 between matmuls, the few-rows split in a decode step
  (ops/matmul.py).

Serving only: the expert layer's serving form holds no training batch.
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
from ray_tpu.ops.attention import cache_row_shape
from ray_tpu.ops.matmul import mm as _mm
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_at
from ray_tpu.parallel.moe import (BIAS_COUNTS, EXPERT_COUNTS as _LAYER_COUNTS,
                                  Scoring)

# jax.named_scope names, so that a trace viewer groups device ops
# (the attention sublayer's "attn" and the expert layer's "moe.router",
# "moe.experts" are their modules')
SCOPE_CONV = "lfm2.conv"
SCOPE_MLP = hybrid.SCOPE_MLP    # a dense feed-forward (hybrid.dense_or_routed)
SCOPE_HEAD = "head"

# what the family's programs count on the device: the expert layer's
# five and what the selection bias did to the picks
EXPERT_COUNTS = _LAYER_COUNTS + BIAS_COUNTS


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    layer_types: Tuple[str, ...] = (
        ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 4
        + ("full_attention", "conv", "conv") * 2)
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    conv_taps: int = 3                # conv_L_cache
    dense_dim: int = 7168             # intermediate_size
    n_experts: int = 32
    top_k: int = 4
    expert_dim: int = 1792            # moe_intermediate_size
    routed_scaling: float = 1.0
    # deviation of the selection bias ``lfm2_init`` draws (a trained
    # model's is a buffer moved by its load balancing; 0: no bias)
    router_bias_std: float = 0.1
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | reference

    def __post_init__(self):
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(f"n_dense_layers {self.n_dense_layers} of "
                             f"{self.n_layers} layers")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("dim must divide into n_heads, n_heads into "
                             "n_kv_heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer as ``<mixer>+<feed-forward>``: the two stacks it
        takes one layer from."""
        return tuple(
            ("attn" if t == "full_attention" else "conv")
            + ("+dense" if i < self.n_dense_layers else "+moe")
            for i, t in enumerate(self.layer_types))

    def _count(self, stack: str) -> int:
        return sum(stack in kind.split("+") for kind in self.layer_kinds)

    @property
    def n_attn_layers(self) -> int:
        return self._count("attn")

    @property
    def n_conv_layers(self) -> int:
        return self._count("conv")

    @property
    def n_moe_layers(self) -> int:
        return self._count("moe")

    @property
    def runs(self) -> Tuple[Tuple[str, str, int, int, int], ...]:
        """The trunk as (mixer, feed-forward, the run's first index in
        the mixer's stack, in the feed-forward's stack, count) for each
        run of consecutive layers of one kind."""
        out, seen = [], {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
        for kind, _, count in runs(self.layer_kinds):
            mixer, ff = kind.split("+")
            out.append((mixer, ff, seen[mixer], seen[ff], count))
            seen[mixer] += count
            seen[ff] += count
        return tuple(out)

    @property
    def scoring(self) -> Scoring:
        return Scoring("sigmoid", eps=1e-6, scale=self.routed_scaling)

    @staticmethod
    def tiny(**kw) -> "Lfm2Config":
        """Test-scale: one leading dense layer, then both mixers under
        routed layers, 8 experts top-3, heads of 16 over 2 KV heads, a
        bias that moves picks."""
        defaults = dict(
            vocab_size=512, dim=64,
            layer_types=("conv", "full_attention", "conv", "conv",
                         "full_attention"),
            n_dense_layers=1, n_heads=4, n_kv_heads=2, dense_dim=96,
            n_experts=8, top_k=3, expert_dim=32, routed_scaling=1.5,
            router_bias_std=0.2, max_seq_len=128, attention="reference")
        defaults.update(kw)
        return Lfm2Config(**defaults)


def lfm2_init(rng, config: Lfm2Config) -> Dict[str, Any]:
    """The parameter pytree: ``embedding`` [V, dim], ``lm_head`` [dim,
    V], ``final_norm``, and four stacks (layers on axis 0): ``conv``
    (in_norm, w_in [dim, 3 dim] as ``[B | C | z]``, conv_w [taps, dim],
    w_out), ``attn`` (in_norm, wq, wk, wv, wo, q_norm, k_norm [HD]),
    ``dense`` (ff_norm, w_in [dim, 2 I] the gated half first, w_out) and
    ``moe`` (ff_norm, router [dim, E], router_bias [E] float32, w_in_e
    [E, dim, 2 I], w_out_e [E, I, dim]).

    Matrices normal with ``fan_in ** -0.5``, as the other families draw
    them, the taps with ``taps ** -0.5``, norm weights 1, the selection
    bias normal with ``router_bias_std`` (a buffer, kept float32). An
    expert stack is drawn a layer at a time, so that no float32 draw of
    a whole stack is ever alive."""
    c = config
    hd = c.head_dim
    keys = jax.random.split(rng, 6)

    dense, by_layer, ones = hybrid.drawers(c.dtype)
    m, a = c.n_conv_layers, c.n_attn_layers
    d, e = c.n_dense_layers, c.n_moe_layers
    kc = jax.random.split(keys[0], 3)
    conv = {"in_norm": ones(m, c.dim),
            "w_in": dense(kc[0], (m, c.dim, 3 * c.dim), c.dim),
            "conv_w": dense(kc[1], (m, c.conv_taps, c.dim), c.conv_taps),
            "w_out": dense(kc[2], (m, c.dim, c.dim), c.dim)}
    ka = jax.random.split(keys[1], 4)
    attn = {"in_norm": ones(a, c.dim),
            "wq": dense(ka[0], (a, c.dim, c.n_heads * hd), c.dim),
            "wk": dense(ka[1], (a, c.dim, c.n_kv_heads * hd), c.dim),
            "wv": dense(ka[2], (a, c.dim, c.n_kv_heads * hd), c.dim),
            "wo": dense(ka[3], (a, c.n_heads * hd, c.dim), c.n_heads * hd),
            "q_norm": ones(a, hd), "k_norm": ones(a, hd)}
    kd = jax.random.split(keys[2], 2)
    dense_ff = {"ff_norm": ones(d, c.dim),
                "w_in": dense(kd[0], (d, c.dim, 2 * c.dense_dim), c.dim),
                "w_out": dense(kd[1], (d, c.dense_dim, c.dim), c.dense_dim)}
    ke = jax.random.split(keys[3], 4)
    moe = {"ff_norm": ones(e, c.dim),
           "router": dense(ke[0], (e, c.dim, c.n_experts), c.dim),
           "router_bias": jax.random.normal(
               ke[1], (e, c.n_experts), jnp.float32) * c.router_bias_std,
           "w_in_e": by_layer(ke[2], e, (c.n_experts, c.dim,
                                         2 * c.expert_dim), c.dim),
           "w_out_e": by_layer(ke[3], e, (c.n_experts, c.expert_dim, c.dim),
                               c.expert_dim)}
    return {"embedding": dense(keys[4], (c.vocab_size, c.dim), c.dim),
            "lm_head": dense(keys[5], (c.dim, c.vocab_size), c.dim),
            "conv": conv, "attn": attn, "dense": dense_ff, "moe": moe,
            "final_norm": ones(c.dim)}


def _norm_rope(c: Lfm2Config):
    """What this family does to ``q`` and ``k`` between projection and
    kernel (models/hybrid.py's ``qk``): an RMSNorm over each head's
    ``head_dim`` (64 wide, under the kernel's lanes: ``rms_norm`` takes
    its plain form, fused with the rotation beside it), then rotary at
    each row's position. q [rows, H, HD], k [rows, KVH, HD], float32."""
    def qk(p, q, k, positions):
        q = rms_norm(q, p["q_norm"], c.norm_eps)
        k = rms_norm(k, p["k_norm"], c.norm_eps)
        cos, sin = rope_at(positions, c.head_dim, c.rope_theta)
        q, k = (apply_rope(x[None], cos, sin)[0] for x in (q, k))
        return q, k
    return qk


def _ff(params, ff: str, index, x, live, c: Lfm2Config):
    """The layer's second half, layer ``index`` of stack ``ff``. x [T,
    dim] -> (x, the layer's EXPERT_COUNTS uint32 over the ``live`` rows,
    zeros from a dense layer). Every expert is held, none is shared."""
    return hybrid.dense_or_routed(params, ff, index, x, live, c,
                                  len(EXPERT_COUNTS))


def _conv_in(p, x, c: Lfm2Config):
    """-> (``s = B * z`` and ``C``, each [rows, dim] float32)."""
    bcz = _mm(rms_norm(x, p["in_norm"], c.norm_eps), p["w_in"])
    return bcz[:, :c.dim] * bcz[:, 2 * c.dim:], bcz[:, c.dim:2 * c.dim]


def _conv_sequence(p, x, length, c: Lfm2Config):
    """One short-convolution mixer over one sequence. x [L, dim]
    float32 -> (x, state [taps - 1, dim]: ``s`` at the last real
    positions, ``length - taps + 1 .. length - 1``)."""
    seq, taps = x.shape[0], c.conv_taps
    with jax.named_scope(SCOPE_CONV):
        s, gate = _conv_in(p, x, c)
        # position t sees t-2..t, zeros before the start
        padded = jnp.pad(s, ((taps - 1, 0), (0, 0)))
        w = p["conv_w"].astype(jnp.float32)
        mixed = sum(padded[j:j + seq] * w[j] for j in range(taps))
        state = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, 0)
        return x + _mm(gate * mixed, p["w_out"]), state


def _trunk(params, tokens, length, c: Lfm2Config):
    """tokens [L] int32 -> (hidden [L, dim] before the final norm, the
    sequence's cache entry as lfm2_init_cache lays it out, with a slot
    axis of one, EXPERT_COUNTS uint32 summed over the layers, of the
    positions before ``length``)."""
    x = params["embedding"][tokens].astype(jnp.float32)
    live = jnp.arange(tokens.shape[0]) < length
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    qk = _norm_rope(c)
    ks, vs, states = [], [], []
    for mixer, ff, first, ff_first, count in c.runs:
        if mixer == "attn":
            for i in range(count):
                x, k, v = attn_sequence(_layer(params["attn"], first + i),
                                        x, c, qk=qk)
                x, n = _ff(params, ff, ff_first + i, x, live, c)
                counts = counts + n
                ks.append(k)
                vs.append(v)
            continue

        def body(carry, i, ff=ff, first=first, ff_first=ff_first):
            x, counts = carry
            x, state = _conv_sequence(_layer(params["conv"], first + i),
                                      x, length, c)
            x, n = _ff(params, ff, ff_first + i, x, live, c)
            return (x, counts + n), state

        (x, counts), state = jax.lax.scan(body, (x, counts),
                                          jnp.arange(count))
        states.append(state)
    rows = (len(ks), 1, tokens.shape[0]) + cache_row_shape(c.n_kv_heads,
                                                           c.head_dim)
    entry = {"k": jnp.stack(ks).reshape(rows),
             "v": jnp.stack(vs).reshape(rows),
             "conv": jnp.concatenate(states)[:, None].astype(c.dtype)}
    return x, entry, counts


def _head(params, x, c: Lfm2Config):
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps)
        return jnp.dot(x.astype(c.dtype), params["lm_head"],
                       preferred_element_type=jnp.float32)


def lfm2_forward(params, tokens, config: Lfm2Config,
                 return_hidden: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, or with
    ``return_hidden`` the final-norm hidden states [B, S, dim]. Whole
    sequences, one at a time (the tests and engine.embed)."""
    return hybrid.forward(_trunk, _head, params, tokens, config,
                          return_hidden)


def lfm2_init_cache(config: Lfm2Config, batch: int, max_seq: int):
    """The serving cache, one pytree whose every leaf has the slot on
    axis 1: ``k`` / ``v`` [A, B, S, KVH * HD / 128, 128] (heads of 64:
    two KV heads a row of 128 lanes, ``ops.attention.cache_row_shape``)
    and ``conv`` [M, B, taps - 1, dim], the convolution's last inputs."""
    c = config
    kv = (c.n_attn_layers, batch, max_seq) + cache_row_shape(
        c.n_kv_heads, c.head_dim)
    return {"k": jnp.zeros(kv, c.dtype), "v": jnp.zeros(kv, c.dtype),
            "conv": jnp.zeros((c.n_conv_layers, batch, c.conv_taps - 1,
                               c.dim), c.dtype)}


def lfm2_prefill(params, tokens, length, config: Lfm2Config, lora=None):
    """Forward over one prompt padded to a bucket. tokens [1, bucket]
    int32, ``length`` its true length (traced: one program a bucket) ->
    (logits [1, 1, vocab] float32 of position length - 1, that slot's
    cache entry, EXPERT_COUNTS uint32 of the prompt's own positions; a
    prefill counts no expert slots). K/V rows at padded positions are
    junk that decode never attends (it masks by position); the
    convolution's state is that of the true last token."""
    c = config
    x, entry, counts = _trunk(params, tokens[0], length, c)
    return hybrid.prefill_result(_head, params, c, x, length, entry,
                                 counts, EXPERT_COUNTS)


def lfm2_decode_step(params, token, cache, pos, live, config: Lfm2Config,
                     lora_bank=None, lora_idx=None):
    """One token for every slot. token, pos: [B] int32 (the token at
    position ``pos``); ``live`` [B]: which slots hold a request (the
    others are parked: computed, not counted); ``cache`` as
    lfm2_init_cache gives it. -> (logits [B, vocab] float32, the cache
    with every slot's convolution state moved one step and its K/V row
    written at ``pos``, EXPERT_COUNTS uint32 of this step). The caller's
    program must donate the cache and run on one device, and every
    ``pos`` must lie in ``[0, S-1]``."""
    c = config
    x = params["embedding"][token].astype(jnp.float32)           # [B, D]
    live = live.astype(bool)
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    k_cache, v_cache, conv = cache["k"], cache["v"], cache["conv"]
    qk = _norm_rope(c)

    def conv_layer(x, conv, m):
        p = _layer(params["conv"], m)
        with jax.named_scope(SCOPE_CONV):
            s, gate = _conv_in(p, x, c)
            window = jnp.concatenate(
                [jax.lax.dynamic_index_in_dim(conv, m, keepdims=False)
                 .astype(jnp.float32), s[:, None, :]],
                axis=1)                                  # [B, taps, dim]
            mixed = jnp.sum(
                window * p["conv_w"].astype(jnp.float32)[None], axis=1)
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, window[:, 1:].astype(conv.dtype), m, 0)
            return x + _mm(gate * mixed, p["w_out"]), conv

    for mixer, ff, first, ff_first, count in c.runs:
        if mixer == "attn":
            for i in range(count):
                x, k_cache, v_cache = attn_decode(
                    _layer(params["attn"], first + i), x, k_cache, v_cache,
                    first + i, pos, c, qk=qk)
                x, n = _ff(params, ff, ff_first + i, x, live, c)
                counts = counts + n
            continue

        def body(carry, i, ff=ff, first=first, ff_first=ff_first):
            x, conv, counts = carry
            x, conv = conv_layer(x, conv, first + i)
            x, n = _ff(params, ff, ff_first + i, x, live, c)
            return (x, conv, counts + n), None

        (x, conv, counts), _ = jax.lax.scan(body, (x, conv, counts),
                                            jnp.arange(count))
    return (_head(params, x, c), {"k": k_cache, "v": v_cache, "conv": conv},
            counts)


FAMILY = ModelFamily.of(
    init=lfm2_init, forward=lfm2_forward, init_cache=lfm2_init_cache,
    prefill=lfm2_prefill, decode_step=lfm2_decode_step,
    dense_only=CONSUMED, expert_counts=EXPERT_COUNTS,
    kv_row_shape=lambda c: cache_row_shape(c.n_kv_heads, c.head_dim))
