"""Llama-family decoder (Llama-2/3 architecture), functional JAX.

The flagship model for the Train path (BASELINE.md north star:
Llama-2-7B fine-tune ≥35% MFU on v5p). Design choices for TPU:

- Layers are *stacked* (leading n_layers axis) and iterated with
  `lax.scan`: one compiled block regardless of depth, fast compiles,
  and `jax.checkpoint` per block gives layer-granular rematerialization.
  With ``remat=True`` a block keeps its input, its narrow residuals
  and, of a dense FFN, the gate and up products before the activation
  (`REMAT_SAVED`), so the backward pass runs no product of the forward
  pass again: only the norms, ``silu`` and the elementwise product are
  recomputed. Per layer ``tokens * (2*dim + 2*n_heads*head_dim +
  2*n_kv_heads*head_dim + 2*hidden_dim)`` elements in the model dtype
  plus ``4 * tokens * n_heads`` bytes of float32 row sums: 737 MiB a
  layer at 8192 tokens of Mistral-7B's widths, of which 448 MiB are
  the FFN's two (``2 * tokens * hidden_dim``), 225 the narrow ones and
  64 the input that full recomputation kept too. The compiled step's
  temporaries grow by up to twice what is kept. A routed FFN
  (``moe_experts``) keeps nothing and is recomputed whole. A job at
  the memory's edge has to cut its batch.
- All matmuls stay [tokens, features] × [features, out] — large, MXU-
  shaped, bfloat16 by default with float32 accumulation.
- Attention pluggable: "flash" (Pallas kernel, ray_tpu/ops/attention.py),
  "reference" (jnp), or "ring"/"ulysses" (sequence-parallel,
  ray_tpu/parallel/ring_attention.py) — selected by the sharding config,
  not the model code.
- Sharding is external: `llama_sharding_rules(mode)` returns rules for
  this parameter tree (ddp/fsdp/tp/fsdp_tp), applied via
  ray_tpu.parallel.sharding. The model itself is sharding-agnostic.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.accelerators import jax_backend
from ray_tpu.models.family import ModelFamily
from ray_tpu.ops.attention import (SAVED_LSE, SAVED_OUT, decode_attention,
                                   flash_attention)
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import ShardingRules


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | reference | ring | ulysses
    # Recompute in the backward pass what is elementwise (the norms,
    # silu, the FFN's product) and keep what a matrix product or a
    # kernel made (REMAT_SAVED: a further 2 * tokens * hidden_dim
    # elements a dense layer; the module docstring has what all cost).
    remat: bool = True
    # Chunked cross-entropy: tokens per chunk (0/None = dense loss).
    # Avoids materializing [B, S, vocab] fp32 logits — at large batch
    # the dominant activation — and keeps the head's gradient [dim,
    # vocab] and the hidden states' [tokens, dim] from the forward pass
    # instead (see chunked_cross_entropy).
    ce_chunk_tokens: int = 0
    # Mixture-of-Experts: >0 replaces the dense FFN with moe_experts
    # expert FFNs routed top-k, expert-parallel over the "expert" mesh
    # axis (ray_tpu/parallel/moe.py; no reference analog — SURVEY §2.3
    # X4 commits EP in-tree).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    # weight of the Switch-style load-balancing aux loss (per layer)
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    # --- presets -------------------------------------------------------
    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, dim=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, hidden_dim=14336,
                           max_seq_len=8192, rope_theta=500000.0, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-scale config that runs on the 8-device CPU mesh."""
        defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                        dtype=jnp.float32, attention="reference",
                        remat=False)
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def mixtral_8x7b(**kw) -> "LlamaConfig":
        """Mixtral-8x7B shape: 8-expert top-2 SwiGLU MoE on a
        Mistral-7B trunk (GQA 8 KV heads). Routed through
        parallel/moe.py, expert-parallel over the "expert" axis."""
        defaults = dict(vocab_size=32000, dim=4096, n_layers=32,
                        n_heads=32, n_kv_heads=8, hidden_dim=14336,
                        max_seq_len=32768, rope_theta=1e6,
                        moe_experts=8, moe_top_k=2)
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_moe(**kw) -> "LlamaConfig":
        """Test-scale MoE config for the 8-device CPU mesh."""
        defaults = dict(moe_experts=4, moe_top_k=2)
        defaults.update(kw)
        return LlamaConfig.tiny(**defaults)

    @staticmethod
    def small_1b(**kw) -> "LlamaConfig":
        defaults = dict(vocab_size=32000, dim=2048, n_layers=16,
                        n_heads=16, n_kv_heads=16, hidden_dim=5504,
                        max_seq_len=2048)
        defaults.update(kw)
        return LlamaConfig(**defaults)

    def num_params(self) -> int:
        hd = self.head_dim
        ffn_copies = max(1, self.moe_experts)
        per_layer = (
            self.dim * self.n_heads * hd          # wq
            + 2 * self.dim * self.n_kv_heads * hd  # wk, wv
            + self.n_heads * hd * self.dim         # wo
            + ffn_copies * 3 * self.dim * self.hidden_dim  # w1, w2, w3
            + (self.dim * self.moe_experts if self.moe_experts else 0)
            + 2 * self.dim                         # norms
        )
        return (self.vocab_size * self.dim * 2     # embedding + lm_head
                + self.n_layers * per_layer + self.dim)


def llama_init(rng, config: LlamaConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree (layers stacked on axis 0)."""
    c = config
    hd = c.head_dim
    k_embed, k_layers, k_head = jax.random.split(rng, 3)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(c.dtype)

    keys = jax.random.split(k_layers, 7)

    def stack(key, shape, fan_in):
        return dense(key, (c.n_layers, *shape), fan_in)

    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((c.n_layers, c.dim), dtype=c.dtype),
        "wq": stack(keys[0], (c.dim, c.n_heads * hd), c.dim),
        "wk": stack(keys[1], (c.dim, c.n_kv_heads * hd), c.dim),
        "wv": stack(keys[2], (c.dim, c.n_kv_heads * hd), c.dim),
        "wo": stack(keys[3], (c.n_heads * hd, c.dim), c.n_heads * hd),
        "mlp_norm": jnp.ones((c.n_layers, c.dim), dtype=c.dtype),
    }
    if c.moe_experts:
        # expert-stacked FFN weights [L, E, ...] + per-layer router
        layers["router"] = stack(keys[6], (c.dim, c.moe_experts), c.dim)
        layers["w1"] = stack(keys[4], (c.moe_experts, c.dim, c.hidden_dim),
                             c.dim)
        layers["w3"] = stack(keys[5], (c.moe_experts, c.dim, c.hidden_dim),
                             c.dim)
        layers["w2"] = stack(
            jax.random.fold_in(keys[6], 1),
            (c.moe_experts, c.hidden_dim, c.dim), c.hidden_dim)
    else:
        layers["w1"] = stack(keys[4], (c.dim, c.hidden_dim), c.dim)
        layers["w3"] = stack(keys[5], (c.dim, c.hidden_dim), c.dim)
        layers["w2"] = stack(keys[6], (c.hidden_dim, c.dim), c.hidden_dim)
    params = {
        "embedding": dense(k_embed, (c.vocab_size, c.dim), c.dim),
        "layers": layers,
        "final_norm": jnp.ones((c.dim,), dtype=c.dtype),
        "lm_head": dense(k_head, (c.dim, c.vocab_size), c.dim),
    }
    return params


# jax.named_scope names on the bodies that prefill, decode and the
# loss share, so that a trace viewer groups device ops by them
# (metadata only: the programs are the same)
SCOPE_ATTENTION = "attention"   # with its cache slice and update
SCOPE_FFN = "ffn"
SCOPE_HEAD = "head"             # final norm and output projection
SCOPE_LOSS = "cross_entropy"

# What a rematerialized block keeps for its backward pass besides its
# input. q and k are named after rope and k and v BEFORE the
# grouped-query repeat (at n_kv_heads; the repeat is a copy and is
# recomputed), the attention output and the flash kernel's row sums
# where they are produced (ops/attention.py names the kernel's own;
# `_attention` names the output of the other implementations), the
# stream after the attention projection, and a dense FFN's gate and up
# products BEFORE silu (`_ffn`; the backward pass needs the
# pre-activation, and silu and gate * up fuse into their consumers).
# The norms are recomputed, and a routed or int8 FFN whole.
REMAT_SAVED = ("attn_q", "attn_k", "attn_v", SAVED_OUT, SAVED_LSE,
               "attn_proj", "ffn_gate", "ffn_up")


def _attention(q, k, v, config: LlamaConfig, mesh):
    """Dispatch to the configured attention implementation."""
    n_rep = config.n_heads // config.n_kv_heads
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    if config.attention == "flash":
        # names its own output, once, in the layout its backward reads
        return flash_attention(q, k, v, True, mesh)
    if config.attention == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention
        out = ring_attention(q, k, v, mesh, causal=True)
    elif config.attention == "ulysses":
        from ray_tpu.parallel.ring_attention import ulysses_attention
        out = ulysses_attention(q, k, v, mesh, causal=True)
    else:
        from ray_tpu.ops.attention import _attention_reference
        out = _attention_reference(q, k, v, True)
    return checkpoint_name(out, SAVED_OUT)


def _int8_mm(x2d, w8, scale):
    """x2d @ dequant(w8, scale): the Pallas in-register-dequant kernel
    on TPU (halved HBM weight traffic — ops/quant_matmul.py), an XLA
    dequant matmul elsewhere (CPU tests; same math, so outputs agree
    across backends up to accumulation order)."""
    if jax_backend.on_tpu():
        from ray_tpu.ops.quant_matmul import int8_matmul
        return int8_matmul(x2d.astype(jnp.bfloat16), w8, scale) \
            .astype(x2d.dtype)
    return (x2d @ w8.astype(x2d.dtype)) * scale.astype(x2d.dtype)


def _ffn(layer_params, h, config: LlamaConfig):
    """FFN output (pre-residual): dense SwiGLU or the MoE layer.
    Returns (y, aux) — aux is the MoE load-balancing loss (0 if dense)."""
    c = config
    if c.moe_experts:
        from ray_tpu.parallel.moe import moe_ffn
        return moe_ffn(h, layer_params["router"], layer_params["w1"],
                       layer_params["w3"], layer_params["w2"],
                       top_k=c.moe_top_k,
                       capacity_factor=c.moe_capacity_factor)
    if "w1_q8" in layer_params:
        # weight-only int8 serving path (quantize_llama_ffn)
        b_t = h.shape[:-1]
        h2 = h.reshape(-1, h.shape[-1])
        gate = jax.nn.silu(_int8_mm(h2, layer_params["w1_q8"],
                                    layer_params["w1_s"]))
        up = _int8_mm(h2, layer_params["w3_q8"], layer_params["w3_s"])
        y = _int8_mm((gate * up).astype(h.dtype),
                     layer_params["w2_q8"], layer_params["w2_s"])
        return (y.reshape(*b_t, -1).astype(h.dtype),
                jnp.zeros((), jnp.float32))
    # named BEFORE silu: what a rematerialized block keeps (REMAT_SAVED)
    gate = jax.nn.silu(checkpoint_name(h @ layer_params["w1"], "ffn_gate"))
    up = checkpoint_name(h @ layer_params["w3"], "ffn_up")
    return (gate * up) @ layer_params["w2"], jnp.zeros((), jnp.float32)


def quantize_llama_ffn(params, config: LlamaConfig):
    """Weight-only int8 for the stacked FFN weights (w1/w3/w2 — ~2/3
    of a dense Llama's parameters): replaces each [L, K, N] stack with
    an int8 stack plus per-output-channel scales. Attention
    projections and lm_head stay in the working dtype (their HBM
    traffic is a minority and the KV cache dominates attention reads).
    Reference analog: vLLM quantization passthrough
    (llm/_internal/serve/engines/vllm/vllm_models.py:214)."""
    if config.moe_experts:
        raise ValueError("int8 quantization supports dense FFNs only "
                         "(MoE expert stacks are not wired)")
    from ray_tpu.ops.quant_matmul import quantize_int8
    layers = dict(params["layers"])
    for name in ("w1", "w3", "w2"):
        if name not in layers:
            raise ValueError(f"params missing FFN stack {name!r}")
        w8, scale = jax.vmap(quantize_int8)(layers.pop(name))
        layers[name + "_q8"] = w8
        layers[name + "_s"] = scale
    return {**params, "layers": layers}


def _block(layer_params, x, cos, sin, config: LlamaConfig, mesh,
           lora=None):
    """One transformer block. Returns (x, (k, v)) — K/V are post-rope,
    the layout the KV cache stores; training callers discard them.
    ``lora``: optional (A_q, B_q, A_v, B_v, scale) low-rank deltas on
    the q/v projections (zero extra cost when absent)."""
    c = config
    b, s, _ = x.shape
    hd = c.head_dim
    with jax.named_scope(SCOPE_ATTENTION):
        h = rms_norm(x, layer_params["attn_norm"], c.norm_eps, mesh)
        q = h @ layer_params["wq"]
        k = h @ layer_params["wk"]
        v = h @ layer_params["wv"]
        if lora is not None:
            a_q, b_q, a_v, b_v, scale = lora
            q = q + scale * _lora_delta(h, a_q, b_q)
            v = v + scale * _lora_delta(h, a_v, b_v)
        q = q.reshape(b, s, c.n_heads, hd)
        k = k.reshape(b, s, c.n_kv_heads, hd)
        v = v.reshape(b, s, c.n_kv_heads, hd)
        q = checkpoint_name(apply_rope(q, cos, sin), "attn_q")
        k = checkpoint_name(apply_rope(k, cos, sin), "attn_k")
        v = checkpoint_name(v, "attn_v")
        attn = _attention(q, k, v, c, mesh)
        x = checkpoint_name(
            x + attn.reshape(b, s, c.n_heads * hd) @ layer_params["wo"],
            "attn_proj")
    with jax.named_scope(SCOPE_FFN):
        h = rms_norm(x, layer_params["mlp_norm"], c.norm_eps, mesh)
        y, aux = _ffn(layer_params, h, c)
        x = x + y
    return x, (k, v), aux


def llama_forward(params, tokens, config: LlamaConfig, mesh=None,
                  return_aux: bool = False, return_hidden: bool = False):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32).
    With return_aux, also returns the summed MoE load-balancing loss.
    With return_hidden, returns the final-norm hidden states INSTEAD of
    logits (the lm_head matmul is skipped — chunked_cross_entropy
    applies it chunk-wise so the [B, S, vocab] tensor never
    materializes)."""
    c = config
    x = params["embedding"][tokens].astype(c.dtype)
    cos, sin = rope_frequencies(c.head_dim, tokens.shape[1], c.rope_theta)

    block = functools.partial(_block, config=c, mesh=mesh)
    if c.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.save_only_these_names(
                *REMAT_SAVED))

    def scan_body(carry, layer_params):
        x, aux_sum = carry
        x, _kv, aux = block(layer_params, x, cos, sin)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps, mesh)
        if return_hidden:
            return (x, aux_sum) if return_aux else x
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    if return_aux:
        return logits, aux_sum
    return logits


# Chunks of the loss's forward rule laid out one after another rather
# than looped over. Unrolled, the compiler folds dW's float32 sum into
# the chunks' products (the last one into the optimizer's update) where
# a loop reads and writes a [D, V] float32 carry every chunk: 3.8 ms of
# a 422 ms step at 2 chunks of 4096 x 32768, and no more memory.
_CE_UNROLL = 4


def _ce_chunk(lm_head, h_c, t_c, m_c):
    """One chunk of the loss: its float32 logits [chunk, V], their
    log-sum-exp and the chunk's share of the masked NLL sum."""
    logits = (h_c @ lm_head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
    return logits, lse, jnp.sum((lse - tgt) * m_c)


@jax.custom_vjp
def _chunked_nll(h, lm_head, t, m):
    """Masked token-mean NLL of chunks h [n, chunk, D], targets t and
    weights m [n, chunk]: one head product a chunk, no logits kept."""
    def body(total, chunk):
        return total + _ce_chunk(lm_head, *chunk)[2], None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, t, m))
    return total / jnp.maximum(jnp.sum(m), 1.0)


def _chunked_nll_fwd(h, lm_head, t, m):
    """The loss and, while a chunk's logits are there, the head's two
    gradient products: dlogits = (softmax - onehot) * m / count is known
    in the forward chunk, so the backward rule has no logits to
    recompute. Residuals are dH [n, chunk, D] and dW [D, V]."""
    count = jnp.maximum(jnp.sum(m), 1.0)

    def body(carry, inp):
        total, d_w = carry
        h_c, t_c, m_c = inp
        logits, lse, part = _ce_chunk(lm_head, h_c, t_c, m_c)
        hit = jnp.arange(logits.shape[-1])[None, :] == t_c[:, None]
        dlogits = ((jnp.exp(logits - lse[:, None]) - hit)
                   * (m_c / count)[:, None])
        # the operands' precision is what the transpose of the forward's
        # .astype(float32) gives autodiff; dW sums the chunks in float32
        dlogits = dlogits.astype(jnp.result_type(h_c, lm_head))
        d_w = d_w + jnp.dot(h_c.T, dlogits,
                            preferred_element_type=jnp.float32)
        return (total + part, d_w), (dlogits @ lm_head.T).astype(h.dtype)

    (total, d_w), d_h = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32),
               jnp.zeros(lm_head.shape, jnp.float32)), (h, t, m),
        unroll=_CE_UNROLL)
    return total / count, (d_h, d_w.astype(lm_head.dtype))


def _chunked_nll_bwd(residuals, g):
    d_h, d_w = residuals
    # targets and weights get no cotangent
    return ((g * d_h).astype(d_h.dtype), (g * d_w).astype(d_w.dtype),
            None, None)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


def chunked_cross_entropy(hidden, lm_head, targets, mask=None, *,
                          chunk_tokens: int = 2048):
    """Token-mean NLL without materializing [B, S, vocab] logits.

    The output projection + log-softmax run per token-chunk in a scan:
    peak memory drops from O(B*S*V) fp32 (the dominant activation at
    train shapes — e.g. 4.2 GB at B16/S2048/V32k) to O(chunk*V). No
    chunk's logits are kept for the backward pass and none are computed
    twice: under differentiation the forward chunk also forms the loss's
    gradient with respect to its logits and applies the head's two
    gradient products there (``_chunked_nll_fwd``), so a train step runs
    the three [tokens, D] x [D, V] products the mathematics needs, and
    keeps dH [tokens, D] and dW [D, V] until the backward pass scales
    them by the incoming cotangent. On TPU the freed HBM buys a larger
    batch, which is where the MFU is (reference analog: memory-
    efficient losses in large-vocab LM training; the reference itself
    has no in-tree model code).
    """
    dim = hidden.shape[-1]
    flat_h = hidden.reshape(-1, dim)
    flat_t = targets.reshape(-1)
    n = flat_h.shape[0]
    flat_m = (jnp.ones((n,), jnp.float32) if mask is None
              else mask.reshape(-1).astype(jnp.float32))
    chunk = min(chunk_tokens, n)
    pad = (-n) % chunk
    if pad:
        flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
        flat_t = jnp.pad(flat_t, (0, pad))
        flat_m = jnp.pad(flat_m, (0, pad))  # padded tokens weigh 0
    n_chunks = flat_h.shape[0] // chunk
    with jax.named_scope(SCOPE_LOSS):
        return _chunked_nll(flat_h.reshape(n_chunks, chunk, dim), lm_head,
                            flat_t.reshape(n_chunks, chunk),
                            flat_m.reshape(n_chunks, chunk))


def llama_loss(params, tokens, targets, config: LlamaConfig, mesh=None,
               mask=None):
    """Next-token cross-entropy (+ MoE load-balancing aux when MoE).
    ``config.ce_chunk_tokens`` switches to the chunked loss that never
    materializes the [B, S, vocab] logits."""
    if config.ce_chunk_tokens:
        hidden, aux = llama_forward(params, tokens, config, mesh,
                                    return_aux=True, return_hidden=True)
        loss = chunked_cross_entropy(
            hidden, params["lm_head"], targets, mask,
            chunk_tokens=config.ce_chunk_tokens)
    else:
        logits, aux = llama_forward(params, tokens, config, mesh,
                                    return_aux=True)
        with jax.named_scope(SCOPE_LOSS):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, targets[..., None],
                                     axis=-1)[..., 0]
            if mask is None:
                loss = -jnp.mean(ll)
            else:
                loss = (-jnp.sum(ll * mask)
                        / jnp.maximum(jnp.sum(mask), 1.0))
    if config.moe_experts:
        loss = loss + config.moe_aux_weight * aux / config.n_layers
    return loss


def llama_sharding_rules(mode: str = "fsdp_tp") -> ShardingRules:
    """Sharding rules for this parameter tree (leading axis = layers).

    Modes: ddp | fsdp | tp | fsdp_tp | ep — the JaxTrainer's DDP/FSDP/TP
    settings lower to these (reference analog:
    train/torch/train_loop_utils.py prepare_model wrapping DDP/FSDP;
    here it's a declarative mapping instead of a wrapper). MoE trees
    need no flag: ndim-constrained rule variants shard the 4-D
    expert-stacked FFN weights on D/H (never the expert axis).
    """
    def ffn(spec_in: P, spec_out: P):
        # 4-D variants for MoE expert-stacked weights [L, E, D, H]
        # (matched by ndim, so dense 3-D weights fall through).
        moe_in = P(None, None, *spec_in[1:])
        moe_out = P(None, None, *spec_out[1:])
        return [
            (r"layers/(w1|w3)", moe_in, 4),
            (r"layers/w2", moe_out, 4),
            (r"layers/(wq|wk|wv|w1|w3)", spec_in),
            (r"layers/(wo|w2)", spec_out),
        ]

    if mode == "ddp":
        return ShardingRules(rules=[(r".*", P())])
    if mode == "fsdp":
        return ShardingRules(rules=[
            (r"embedding", P("fsdp", None)),
            (r"lm_head", P(None, "fsdp")),
            *ffn(P(None, "fsdp", None), P(None, None, "fsdp")),
            (r".*", P()),
        ])
    if mode == "tp":
        return ShardingRules(rules=[
            (r"embedding", P(None, "model")),
            (r"lm_head", P(None, "model")),
            *ffn(P(None, None, "model"), P(None, "model", None)),
            (r".*", P()),
        ])
    if mode == "fsdp_tp":
        return ShardingRules(rules=[
            (r"embedding", P("fsdp", "model")),
            (r"lm_head", P(None, ("fsdp", "model"))),
            *ffn(P(None, "fsdp", "model"), P(None, "model", "fsdp")),
            (r".*", P()),
        ])
    if mode == "ep":
        # Expert parallelism: expert-stacked FFN weights [L, E, D, H]
        # partitioned on the "expert" mesh axis; GSPMD turns the MoE
        # dispatch/combine einsums into all-to-alls (parallel/moe.py).
        # Attention/router/embeddings replicate (compose with data axis
        # for the batch).
        return ShardingRules(rules=[
            (r"layers/(w1|w2|w3)", P(None, "expert", None, None)),
            (r".*", P()),
        ])
    raise ValueError(f"unknown sharding mode {mode}")


# ---------------------------------------------------------------------------
# Inference: KV-cache prefill + single-token decode
# (reference analog: the vLLM engine the reference wraps for serving,
# python/ray/llm/_internal/serve/engines/vllm/ — here the engine is
# in-tree and TPU-native: static-shape caches, jitted decode over the
# whole batch, continuous batching handled by ray_tpu.llm.engine)
# ---------------------------------------------------------------------------

def lora_init(rng, config: LlamaConfig, rank: int = 8,
              alpha: float = 16.0) -> Dict[str, Any]:
    """A LoRA adapter on the q/v projections (the classic placement).
    B starts at zero so a fresh adapter is the identity; ``alpha/rank``
    scaling is folded into B so inference needs no extra multiply."""
    c = config
    hd = c.head_dim
    kq, kv = jax.random.split(rng)
    scale = alpha / rank

    def a(key):
        # A maps dim -> rank regardless of the projection's output size
        return (jax.random.normal(key, (c.n_layers, c.dim, rank),
                                  dtype=jnp.float32)
                * (c.dim ** -0.5)).astype(c.dtype)

    return {
        "A_q": a(kq),
        "B_q": jnp.zeros((c.n_layers, rank, c.n_heads * hd), c.dtype),
        "A_v": a(kv),
        "B_v": jnp.zeros((c.n_layers, rank, c.n_kv_heads * hd), c.dtype),
        "scale": jnp.asarray(scale, c.dtype),
    }


def _lora_delta(h, a, b):
    """h @ A @ B with a possibly per-slot-gathered A/B.
    h: [B, S, D]; a: [D, r] or [B, D, r]; b: [r, H] or [B, r, H]."""
    if a.ndim == 2:
        return (h @ a) @ b
    t = jnp.einsum("bsd,bdr->bsr", h, a)
    return jnp.einsum("bsr,brh->bsh", t, b)


def llama_init_cache(config: LlamaConfig, batch: int, max_seq: int):
    """KV cache pair, each [L, B, S, KVH, HD] in the model dtype.

    ``llama_decode_step`` reads the pair in this layout and writes one
    row per layer and slot in place, so the program that calls it must
    donate both (``donate_argnums``): a cache that is not donated is
    copied whole, every step."""
    c = config
    shape = (c.n_layers, batch, max_seq, c.n_kv_heads, c.head_dim)
    return jnp.zeros(shape, c.dtype), jnp.zeros(shape, c.dtype)


def llama_prefill(params, tokens, config: LlamaConfig, lora=None):
    """Forward over a padded prompt, keeping per-layer K/V.

    tokens: [B, S] int32 -> (logits [B, S, vocab] f32,
    k [L, B, S, KVH, HD], v [L, B, S, KVH, HD]). Positions are arange;
    junk K/V at padding positions is never attended later because decode
    masks by true position. ``lora``: optional adapter pytree
    (lora_init) applied to the whole (batch-1) prefill — per-request
    multi-LoRA happens at decode via the bank path.
    """
    c = config
    hd = c.head_dim
    b, s = tokens.shape
    x = params["embedding"][tokens].astype(c.dtype)
    cos, sin = rope_frequencies(hd, s, c.rope_theta)

    if lora is None:
        def body(x, layer_params):
            x, kv, _aux = _block(layer_params, x, cos, sin, c, None)
            return x, kv

        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    else:
        def body(x, layer):
            layer_params, a_q, b_q, a_v, b_v = layer
            x, kv, _aux = _block(
                layer_params, x, cos, sin, c, None,
                lora=(a_q, b_q, a_v, b_v, lora["scale"]))
            return x, kv

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], lora["A_q"], lora["B_q"],
                      lora["A_v"], lora["B_v"]))
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps)
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, ks, vs


def llama_decode_step(params, token, cache_k, cache_v, pos,
                      config: LlamaConfig, lora_bank=None, lora_idx=None):
    """One token for every sequence in the batch.

    token: [B] int32 (the token at position `pos`); pos: [B] int32;
    cache_k/v: [L, B, S, KVH, HD]. Returns (logits [B, vocab] f32,
    cache_k, cache_v) with the new K/V written at `pos`.

    The caches are attended over as they are stored and written in
    place: they ride in the layer scan's carry, each layer scatters its
    [B, KVH, HD] row at ``[l, arange(B), pos]`` and then hands the
    STACKED caches, ``l`` and ``pos`` to ``decode_attention``, and the
    query heads are grouped by the KV head they share (head h belongs
    to KV head ``h // n_rep``), so no K or V is expanded to
    ``n_heads``, no layer is sliced out or handed back whole, and on a
    TPU with heads of 128 the kernel reads of each slot only the
    blocks up to its ``pos`` (a slot parked at 0: one block). The
    caller's program must donate both caches, or XLA copies them every
    step, and run on one device (the kernels cannot be partitioned).
    Every ``pos`` must lie in ``[0, S-1]`` (the engine parks idle slots
    at 0 or at the last row): a scatter drops a row that is out of
    bounds where ``dynamic_update_slice`` would clamp it, and the
    kernel would read blocks past the cache's end.

    Multi-LoRA: ``lora_bank`` stacks adapters on a leading axis
    ({A_q: [N, L, D, r], ...}; index 0 all-zero = no adapter) and
    ``lora_idx`` [B] picks one per slot — the vLLM-style batched-gather
    design, TPU-friendly because N and r are static and tiny.
    """
    c = config
    n_layers, b, s, kvh, hd = cache_k.shape
    n_rep = c.n_heads // kvh
    x = params["embedding"][token][:, None, :].astype(c.dtype)  # [B,1,D]
    cos, sin = rope_frequencies(hd, s, c.rope_theta)
    pos_2d = pos[:, None]                                       # [B,1]
    slots = jnp.arange(b)
    if lora_bank is not None:
        # [N, L, ...] -> [L, N, ...] so the layer scan consumes them
        bank = {k2: jnp.swapaxes(v2, 0, 1)
                for k2, v2 in lora_bank.items() if k2 != "scale"}
        lora_scale = lora_bank["scale"]

    def body(carry, layer):
        x, cache_k, cache_v = carry
        if lora_bank is not None:
            layer_params, l, a_q, b_q, a_v, b_v = layer
        else:
            layer_params, l = layer
        with jax.named_scope(SCOPE_ATTENTION):
            h = rms_norm(x, layer_params["attn_norm"], c.norm_eps)
            q = (h @ layer_params["wq"]).reshape(b, 1, c.n_heads, hd)
            k = (h @ layer_params["wk"]).reshape(b, 1, kvh, hd)
            v = (h @ layer_params["wv"]).reshape(b, 1, kvh, hd)
            if lora_bank is not None:
                dq = _lora_delta(h, a_q[lora_idx], b_q[lora_idx])
                dv = _lora_delta(h, a_v[lora_idx], b_v[lora_idx])
                q = q + (lora_scale * dq).reshape(b, 1, c.n_heads, hd)
                v = v + (lora_scale * dv).reshape(b, 1, kvh, hd)
            q = apply_rope(q, cos, sin, positions=pos_2d)
            k = apply_rope(k, cos, sin, positions=pos_2d)
            cache_k = cache_k.at[l, slots, pos].set(k[:, 0])
            cache_v = cache_v.at[l, slots, pos].set(v[:, 0])
            attn = decode_attention(q.reshape(b, kvh, n_rep, hd), cache_k,
                                    cache_v, l, pos, c.dtype)
            x = x + (attn.reshape(b, 1, c.n_heads * hd)
                     @ layer_params["wo"])
        with jax.named_scope(SCOPE_FFN):
            h = rms_norm(x, layer_params["mlp_norm"], c.norm_eps)
            y, _aux = _ffn(layer_params, h, c)  # MoE-aware (decode too)
            x = x + y
        return (x, cache_k, cache_v), None

    xs = (params["layers"], jnp.arange(n_layers))
    if lora_bank is not None:
        xs += (bank["A_q"], bank["B_q"], bank["A_v"], bank["B_v"])
    (x, cache_k, cache_v), _ = jax.lax.scan(
        body, (x, cache_k, cache_v), xs)
    with jax.named_scope(SCOPE_HEAD):
        x = rms_norm(x, params["final_norm"], c.norm_eps)
        logits = (x[:, 0] @ params["lm_head"]).astype(jnp.float32)
    return logits, cache_k, cache_v


def llama_verify_step(params, tokens, cache_k, cache_v, pos,
                      config: LlamaConfig):
    """Score a G-token speculative chunk in ONE target forward.

    tokens: [B, G] int32 — the current token followed by G-1 draft
    proposals; pos: [B] int32 chunk start positions; cache_k/v:
    [L, B, S, KVH, HD]. Returns (logits [B, G, vocab] f32, cache_k,
    cache_v) with the chunk's K/V written at pos..pos+G-1 per slot.
    logits[:, g] is the target's distribution for the token AFTER
    chunk input g — the verifier for draft g+1 (speculative decoding,
    Leviathan et al. 2023; reference analog: vLLM's spec-decode
    scorer). G is static, so XLA sees one fixed-shape program per
    chunk width.
    """
    c = config
    n_layers, b, s, kvh, hd = cache_k.shape
    g = tokens.shape[1]
    n_rep = c.n_heads // c.n_kv_heads
    x = params["embedding"][tokens].astype(c.dtype)           # [B,G,D]
    cos, sin = rope_frequencies(hd, s, c.rope_theta)
    positions = pos[:, None] + jnp.arange(g)[None, :]         # [B,G]
    # chunk position i attends cache slot t iff t <= pos+i (the write
    # below lands the chunk's own K/V inside that window)
    visible = (jnp.arange(s)[None, None, :]
               <= positions[:, :, None])                      # [B,G,S]

    def body(x, layer):
        layer_params, ck, cv = layer                # ck [B,S,KVH,HD]
        h = rms_norm(x, layer_params["attn_norm"], c.norm_eps)
        q = (h @ layer_params["wq"]).reshape(b, g, c.n_heads, hd)
        k = (h @ layer_params["wk"]).reshape(b, g, kvh, hd)
        v = (h @ layer_params["wv"]).reshape(b, g, kvh, hd)
        q = apply_rope(q, cos, sin, positions=positions)
        k = apply_rope(k, cos, sin, positions=positions)
        write = jax.vmap(
            lambda cache, new, p: jax.lax.dynamic_update_slice(
                cache, new, (p, 0, 0)))
        ck = write(ck, k, pos)
        cv = write(cv, v, pos)
        kk = jnp.repeat(ck, n_rep, axis=2) if n_rep > 1 else ck
        vv = jnp.repeat(cv, n_rep, axis=2) if n_rep > 1 else cv
        scores = jnp.einsum("bghd,bshd->bhgs", q, kk).astype(jnp.float32)
        scores = scores * (hd ** -0.5)
        scores = jnp.where(visible[:, None, :, :], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
        attn = jnp.einsum("bhgs,bshd->bghd", weights, vv)
        x = x + attn.reshape(b, g, c.n_heads * hd) @ layer_params["wo"]
        h = rms_norm(x, layer_params["mlp_norm"], c.norm_eps)
        y, _aux = _ffn(layer_params, h, c)
        return x + y, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], cache_k, cache_v))
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, new_k, new_v


# What the serving engine's dense path asks of a family
# (models/family.py), for the family whose cache is a PAIR of keys and
# values and whose prefill has no use for the prompt's length; it alone
# takes the LoRA arguments. The engine's other step programs call the
# functions above by name.

def _family_prefill(params, tokens, length, config, lora):
    logits, ks, vs = llama_prefill(params, tokens, config, lora=lora)
    return logits, (ks, vs), None


def _family_decode_step(params, token, cache, pos, live, config, lora_bank,
                        lora_idx):
    logits, ck, cv = llama_decode_step(
        params, token, *cache, pos, config, lora_bank=lora_bank,
        lora_idx=lora_idx)
    return logits, (ck, cv), None


FAMILY = ModelFamily.of(
    init=llama_init, forward=llama_forward, init_cache=llama_init_cache,
    prefill=_family_prefill, decode_step=_family_decode_step)
