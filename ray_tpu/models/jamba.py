"""Jamba-family decoder: Mamba-1 mixers beside a few attention layers.

One trunk, two layer kinds. Every layer is ``x = x + mixer(norm(x))``
then ``x = x + swiglu(norm(x))``; layer ``i`` mixes by attention iff
``i % attn_layer_period == attn_layer_offset`` and by a Mamba-1
selective state-space mixer otherwise (AI21's Jamba: 13 Mamba layers to
one of attention in the 3B model). The attention layers are grouped-
query (one KV head in the 3B model), causal, WITHOUT any position
encoding: the Mamba layers carry position. The embedding is tied to the
output head.

The Mamba-1 mixer, per sequence, ``u`` of [L, dim]: ``x, z = split(u
W_in)``; ``x = silu(conv(x))``, a causal depthwise convolution over time
of width ``d_conv`` with bias; ``dt_r, B, C = split(x W_x)``, each
through an RMSNorm of its own (Jamba's addition to Mamba); ``dt =
softplus(dt_r W_dt + b_dt)``; then the recurrence of
ops/selective_scan.py in float32 with ``A = -exp(A_log)``; the output
is ``(y * silu(z)) W_out``.

Design for the TPU:

- The 26 + 2 layers are two stacks (``params["mamba"]``,
  ``params["attn"]``, layers on axis 0) and the trunk is a chain of
  ``lax.scan`` segments, one per run of consecutive Mamba layers, with
  the attention layers between them: one compiled body per kind
  whatever the depth. A segment scans layer indices and reads its
  layer's weights from the whole stack (what a scan over ``xs`` lowers
  to), so no stack is ever sliced into a copy.
- The state a decode step carries is ``ssm`` [M, B, N, d_inner]
  float32 (the states on sublanes, the channels on lanes: with N = 16
  last, a float32 tile would pad it eightfold) and ``conv`` [M, B,
  d_conv - 1, d_inner], beside the attention layers' ``k`` / ``v`` [A,
  B, S, KVH, HD]. All four ride in the layer loop's carry and are
  written in place; the program that calls ``jamba_decode_step`` must
  donate the cache.
- A prefill into a padded bucket is exact by construction, not by
  tolerance: positions at or past ``length`` leave the recurrent state
  untouched and the convolution's carried inputs are those of positions
  ``length - 3 .. length - 1``.
- ``A_log`` is kept as [N, d_inner], the transpose of the published
  [d_inner, N], for the same tiling reason.
- Precision: weights and matmul inputs are bf16, accumulation float32,
  and what lies between two matmuls stays float32 (``ops.matmul.mm``).
  28 layers of two sublayers in series add up what every rounding in
  between costs; a matmul of few rows (a decode step of up to
  ``ops.matmul.SPLIT_ROWS`` slots) also carries its input's low half
  through, for 3% of the step, so decode rounds less than prefill.

Serving only: the scan has no backward (ops/selective_scan.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import hybrid
from ray_tpu.models.family import CONSUMED, ModelFamily
from ray_tpu.models.hybrid import (SCOPE_MLP, attn_decode, attn_sequence,
                                   layer as _layer, runs)
from ray_tpu.ops.matmul import mm as _mm
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.selective_scan import selective_scan

# jax.named_scope names, so that a trace viewer groups device ops
SCOPE_IN_PROJ = "mamba.in_proj"
SCOPE_CONV = "mamba.conv"
SCOPE_SCAN = "mamba.scan"        # prefill: the recurrence over a prompt
SCOPE_UPDATE = "mamba.update"    # decode: one step of it for every slot
SCOPE_OUT_PROJ = "mamba.out_proj"
SCOPE_HEAD = "head"


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    hidden_dim: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | reference

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(
            "attn" if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.n_layers))

    @property
    def n_attn_layers(self) -> int:
        return self.layer_kinds.count("attn")

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_kinds.count("mamba")

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """The trunk as (kind, first index within its kind's stack,
        count) for each run of consecutive layers of one kind."""
        return runs(self.layer_kinds)

    @staticmethod
    def tiny(**kw) -> "JambaConfig":
        """Test-scale: both kinds of layer in four."""
        defaults = dict(vocab_size=512, dim=64, n_layers=4, n_heads=4,
                        n_kv_heads=1, hidden_dim=128, attn_layer_period=4,
                        attn_layer_offset=2, mamba_dt_rank=4,
                        max_seq_len=128, attention="reference")
        defaults.update(kw)
        return JambaConfig(**defaults)


def jamba_init(rng, config: JambaConfig) -> Dict[str, Any]:
    """The parameter pytree: ``embedding`` (tied to the head),
    ``final_norm``, and the two stacks ``mamba`` and ``attn`` (layers
    on axis 0, each with its feed-forward).

    Matrices as llama_init draws them (normal, fan_in ** -0.5). The
    mixer's own parameters by Mamba-1's convention, so that random
    weights give a state that neither dies nor explodes: ``A_log =
    log(1..N)`` for every channel, ``D = 1``, ``b_dt`` the inverse
    softplus of a log-uniform draw in [1e-3, 1e-1], ``W_dt`` uniform
    in +-dt_rank ** -0.5. ``A_log``, ``D`` and ``b_dt`` stay float32."""
    c = config
    hd, di, n, r = c.head_dim, c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    k_embed, k_mamba, k_attn = jax.random.split(rng, 3)

    dense, _, ones = hybrid.drawers(c.dtype)

    def mlp(keys, layers):
        return {
            "ff_norm": ones(layers, c.dim),
            "w_gate": dense(keys[0], (layers, c.dim, c.hidden_dim), c.dim),
            "w_up": dense(keys[1], (layers, c.dim, c.hidden_dim), c.dim),
            "w_down": dense(keys[2], (layers, c.hidden_dim, c.dim),
                            c.hidden_dim)}

    m, a = c.n_mamba_layers, c.n_attn_layers
    km = jax.random.split(k_mamba, 10)
    dt = jnp.exp(jax.random.uniform(km[5], (m, di), jnp.float32)
                 * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
    mamba = {
        "in_norm": ones(m, c.dim),
        "w_in": dense(km[0], (m, c.dim, 2 * di), c.dim),
        "conv_w": dense(km[1], (m, c.mamba_d_conv, di), c.mamba_d_conv),
        "conv_b": dense(km[2], (m, di), c.mamba_d_conv),
        "w_x": dense(km[3], (m, di, r + 2 * n), di),
        "dt_norm": ones(m, r), "b_norm": ones(m, n), "c_norm": ones(m, n),
        "w_dt": jax.random.uniform(
            km[4], (m, r, di), jnp.float32, -(r ** -0.5),
            r ** -0.5).astype(c.dtype),
        "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None],
            (m, n, di)),
        "D": jnp.ones((m, di), jnp.float32),
        "w_out": dense(km[6], (m, di, c.dim), di),
        **mlp(km[7:10], m)}
    ka = jax.random.split(k_attn, 7)
    attn = {
        "in_norm": ones(a, c.dim),
        "wq": dense(ka[0], (a, c.dim, c.n_heads * hd), c.dim),
        "wk": dense(ka[1], (a, c.dim, c.n_kv_heads * hd), c.dim),
        "wv": dense(ka[2], (a, c.dim, c.n_kv_heads * hd), c.dim),
        "wo": dense(ka[3], (a, c.n_heads * hd, c.dim), c.n_heads * hd),
        **mlp(ka[4:7], a)}
    return {"embedding": dense(k_embed, (c.vocab_size, c.dim), c.dim),
            "mamba": mamba, "attn": attn, "final_norm": ones(c.dim)}


def _mlp(p, x, c: JambaConfig):
    with jax.named_scope(SCOPE_MLP):
        h = rms_norm(x, p["ff_norm"], c.norm_eps)
        return x + _mm(jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"]),
                       p["w_down"])


def _dt_b_c(p, xc, c: JambaConfig):
    """xc [..., d_inner] after the convolution -> dt [..., d_inner], B
    and C [..., N], all float32: the projection, Jamba's three norms,
    and the step size.

    What feeds the recurrence is kept in float32 from the matmuls'
    accumulators on (they are 192 and d_inner wide: nothing to save).
    The step size above all: its pre-activation lies around -5, where
    bf16 resolves 0.03, and softplus there turns an absolute error into
    a relative one of the same size in dt, so in every decay factor
    exp(dt A) of every state (on the chip the engine's log-probabilities
    then differed from the float32 reference's by 0.033-0.037 on
    average, against 0.04 allowed)."""
    r, n = c.mamba_dt_rank, c.mamba_d_state
    dbc = _mm(xc, p["w_x"])
    dt_r = rms_norm(dbc[..., :r], p["dt_norm"], c.norm_eps)
    b = rms_norm(dbc[..., r:r + n], p["b_norm"], c.norm_eps)
    cc = rms_norm(dbc[..., r + n:], p["c_norm"], c.norm_eps)
    dt = jax.nn.softplus(_mm(dt_r, p["w_dt"]) + p["b_dt"])
    return dt, b, cc


def _mamba_sequence(p, x, length, c: JambaConfig):
    """One Mamba layer over one sequence. x [L, dim] float32 -> (x,
    ssm [N, d_inner] float32 after position length - 1, conv [d_conv -
    1, d_inner]: the convolution's inputs at the last positions)."""
    di, taps = c.d_inner, c.mamba_d_conv
    seq = x.shape[0]
    u = rms_norm(x, p["in_norm"], c.norm_eps)
    with jax.named_scope(SCOPE_IN_PROJ):
        xz = _mm(u, p["w_in"])
        xs, z = xz[:, :di], xz[:, di:]
    with jax.named_scope(SCOPE_CONV):
        # position t sees t-3..t, zeros before the start
        padded = jnp.pad(xs, ((taps - 1, 0), (0, 0)))
        xc = p["conv_b"].astype(jnp.float32)
        for k in range(taps):
            xc = xc + padded[k:k + seq] * p["conv_w"][k].astype(jnp.float32)
        # rounded once, as the next matmul's input and the scan's
        xc = jax.nn.silu(xc).astype(c.dtype)
        conv = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, 0)
    with jax.named_scope(SCOPE_SCAN):
        dt, b, cc = _dt_b_c(p, xc, c)
        y, ssm = selective_scan(
            xc, dt, b, cc, -jnp.exp(p["A_log"]), p["D"],
            jnp.zeros((c.mamba_d_state, di), jnp.float32), length,
            out_dtype=jnp.float32)
    with jax.named_scope(SCOPE_OUT_PROJ):
        x = x + _mm(y * jax.nn.silu(z), p["w_out"])
    return x, ssm, conv


def _trunk(params, tokens, length, c: JambaConfig):
    """tokens [L] int32 -> (hidden [L, dim] before the final norm,
    the sequence's cache entry as jamba_init_cache lays it out, with a
    slot axis of one)."""
    x = params["embedding"][tokens].astype(jnp.float32)
    ks, vs, ssms, convs = [], [], [], []
    for kind, first, count in c.runs:
        if kind == "attn":
            for a in range(first, first + count):
                p = _layer(params["attn"], a)
                x, k, v = attn_sequence(p, x, c)
                x = _mlp(p, x, c)
                ks.append(k)
                vs.append(v)
            continue

        def body(x, m):
            p = _layer(params["mamba"], m)
            x, ssm, conv = _mamba_sequence(p, x, length, c)
            return _mlp(p, x, c), (ssm, conv)

        x, (ssm, conv) = jax.lax.scan(
            body, x, jnp.arange(first, first + count))
        ssms.append(ssm)
        convs.append(conv)
    entry = {"k": jnp.stack(ks)[:, None], "v": jnp.stack(vs)[:, None],
             "ssm": jnp.concatenate(ssms)[:, None],
             "conv": jnp.concatenate(convs)[:, None]}
    return x, entry


def _head(params, x, c: JambaConfig):
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps)
        return jnp.einsum("...d,vd->...v", x.astype(c.dtype),
                          params["embedding"],
                          preferred_element_type=jnp.float32)


def jamba_forward(params, tokens, config: JambaConfig,
                  return_hidden: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32, or with
    ``return_hidden`` the final-norm hidden states [B, S, dim]. Whole
    sequences, one at a time (the tests and engine.embed)."""
    return hybrid.forward(_trunk, _head, params, tokens, config,
                          return_hidden)


def jamba_init_cache(config: JambaConfig, batch: int, max_seq: int):
    """The serving cache, one pytree whose every leaf has the slot on
    axis 1: ``k`` / ``v`` [A, B, S, KVH, HD] for the attention layers,
    ``ssm`` [M, B, N, d_inner] float32 and ``conv`` [M, B, d_conv - 1,
    d_inner] for the Mamba layers."""
    c = config
    kv = (c.n_attn_layers, batch, max_seq, c.n_kv_heads, c.head_dim)
    m = c.n_mamba_layers
    return {"k": jnp.zeros(kv, c.dtype), "v": jnp.zeros(kv, c.dtype),
            "ssm": jnp.zeros((m, batch, c.mamba_d_state, c.d_inner),
                             jnp.float32),
            "conv": jnp.zeros((m, batch, c.mamba_d_conv - 1, c.d_inner),
                              c.dtype)}


def jamba_prefill(params, tokens, length, config: JambaConfig, lora=None):
    """Forward over one prompt padded to a bucket. tokens [1, bucket]
    int32, ``length`` its true length (traced: one program a bucket) ->
    (logits [1, 1, vocab] float32 of position length - 1, that slot's
    cache entry, None: the family counts nothing on the device). K/V
    rows at padded positions are junk that decode never attends (it
    masks by position); the recurrent state is that of the true last
    token."""
    c = config
    x, entry = _trunk(params, tokens[0], length, c)
    return hybrid.prefill_result(_head, params, c, x, length, entry)


def jamba_decode_step(params, token, cache, pos, live,
                      config: JambaConfig, lora_bank=None, lora_idx=None):
    """One token for every slot. token, pos: [B] int32 (the token at
    position ``pos``); ``cache`` as jamba_init_cache gives it. ->
    (logits [B, vocab] float32, the cache with every slot's state moved
    one step and its K/V row written at ``pos``, None). ``live`` is
    unused: a parked slot is moved like a live one.

    Every slot's recurrent state is updated, a parked slot's too: what
    it holds then is junk that the next admission replaces whole. The
    cache rides in the layer loops' carry and is written in place; an
    attention layer writes its row and hands the stacked K and V, its
    index and ``pos`` to ``decode_attention`` (on a TPU the kernel
    reads of each slot only the blocks up to ``pos``). The caller's
    program must donate the cache and run on one device, and every
    ``pos`` must lie in ``[0, S-1]``."""
    c = config
    di = c.d_inner
    x = params["embedding"][token].astype(jnp.float32)          # [B, D]
    k_cache, v_cache = cache["k"], cache["v"]
    ssm, conv = cache["ssm"], cache["conv"]

    def mamba_body(carry, m):
        x, ssm, conv = carry
        p = _layer(params["mamba"], m)
        u = rms_norm(x, p["in_norm"], c.norm_eps)
        with jax.named_scope(SCOPE_IN_PROJ):
            xz = _mm(u, p["w_in"])
            xs, z = xz[:, :di], xz[:, di:]
        with jax.named_scope(SCOPE_CONV):
            window = jnp.concatenate(
                [jax.lax.dynamic_index_in_dim(conv, m, keepdims=False)
                 .astype(jnp.float32), xs[:, None, :]],
                axis=1)                                 # [B, taps, di]
            xc = jax.nn.silu(
                jnp.sum(window * p["conv_w"].astype(jnp.float32)[None],
                        axis=1) + p["conv_b"].astype(jnp.float32))
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, window[:, 1:].astype(conv.dtype), m, 0)
        with jax.named_scope(SCOPE_UPDATE):
            # xc stays float32 here (the prefill rounds it once, for
            # the kernel to read half the bytes)
            dt, bb, cc = _dt_b_c(p, xc, c)
            h = jax.lax.dynamic_index_in_dim(ssm, m, keepdims=False)
            h = (jnp.exp(dt[:, None, :] * -jnp.exp(p["A_log"])[None])
                 * h + (dt * xc)[:, None, :] * bb[:, :, None])
            y = jnp.sum(h * cc[:, :, None], axis=1) + p["D"] * xc
            ssm = jax.lax.dynamic_update_index_in_dim(ssm, h, m, 0)
        with jax.named_scope(SCOPE_OUT_PROJ):
            x = x + _mm(y * jax.nn.silu(z), p["w_out"])
        return (_mlp(p, x, c), ssm, conv), None

    for kind, first, count in c.runs:
        if kind == "mamba":
            (x, ssm, conv), _ = jax.lax.scan(
                mamba_body, (x, ssm, conv),
                jnp.arange(first, first + count))
            continue
        for a in range(first, first + count):
            p = _layer(params["attn"], a)
            x, k_cache, v_cache = attn_decode(p, x, k_cache, v_cache, a,
                                              pos, c)
            x = _mlp(p, x, c)
    logits = _head(params, x, c)
    return logits, {"k": k_cache, "v": v_cache, "ssm": ssm,
                    "conv": conv}, None


FAMILY = ModelFamily.of(
    init=jamba_init, forward=jamba_forward, init_cache=jamba_init_cache,
    prefill=jamba_prefill, decode_step=jamba_decode_step,
    dense_only=CONSUMED)
