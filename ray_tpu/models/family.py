"""What the serving engine asks of a language-model family.

The engine's dense path (admission, bucketed prefill, the whole-batch
decode step, the sampler) knows no model: it asks the family of
``EngineConfig.model`` for the functions below and handles the serving
cache as an opaque pytree whose every leaf has the slot on axis 1. A
slot is handed over by writing a batch-1 entry of that pytree over the
slot's part of every leaf (``insert_slot``).

The engine's step programs beyond the dense path (a draft model,
multi_step, the prefix cache, chunked prefill, LoRA banks, the
disaggregated path) are the Llama family's: they take the cache as a
pair of keys and values whose rows can be written again and kept
apart. ``dense_only`` is a family's word on why they do not run over
ITS cache (state that a step consumes, a state-space mixer's; rows of a
latent that are neither keys nor values), and the engine refuses them
for it by that reason; "" for the family they were written for.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax


@dataclass(frozen=True)
class ModelFamily:
    # (rng, config) -> params
    init: Callable
    # (params, tokens [B, S], config) -> final-norm hidden [B, S, dim]
    hidden: Callable
    # (config, batch, max_seq) -> cache
    init_cache: Callable
    # (params, tokens [1, bucket], length, config, lora) -> (logits
    # [1, n, vocab] float32, entry, counts). ``length`` is the prompt's
    # true length, traced; a family that has no use for it ignores it
    # and its programs do not hold it. n is the bucket, or 1 where the
    # family returns the row of position length - 1 alone.
    prefill: Callable
    # (params, token [B], cache, pos [B], live [B], config, lora_bank,
    # lora_idx) -> (logits [B, vocab] float32, cache, counts); the
    # caller donates cache. ``live`` says which slots hold a request
    # (the others are parked: computed, and counted by nobody).
    decode_step: Callable
    # cache -> bytes by kind, {"kv": ..., "recurrent": ...} or
    # {"latent": ...}
    cache_bytes: Callable
    # why the engine's step programs beyond the dense path (a draft
    # model, multi_step, the prefix cache, chunked prefill, the
    # disaggregated path) do not run over this family's cache, as a
    # clause about "the cache of a <family>"; "": they do
    dense_only: str = ""
    # parallel.moe.EXPERT_COUNTS (and, after them, a family's own
    # names, as BIAS_COUNTS) where the family's programs count their
    # expert layers' picks ON THE DEVICE: the ``counts`` above are then
    # [n] uint32 of that prompt or step, and the engine adds them up in
    # an array its programs hand on and reads it where metrics flush.
    # (): the family has no routed experts, ``counts`` is None.
    expert_counts: tuple = ()
    # whether decode_step leaves a parked slot's recurrent state where
    # it lies, neither read nor written (the engine then counts, a
    # dense step, the slots x ``config.n_mamba_layers`` moved and parked)
    skips_parked_state: bool = False
    # config -> the last two axes of the cache's K and V leaves, [rows,
    # lanes] of one position: (n_kv_heads, head_dim) unless the family
    # packs narrow heads (ops.attention.cache_row_shape); what the
    # engine asks ops.attention.decode_block_rows about
    kv_row_shape: Callable = lambda c: (c.n_kv_heads, c.head_dim)


def insert_slot(cache, entry, slot):
    """``entry`` (a cache of one slot; its leaves may be shorter than
    the cache's on the later axes, as a bucket is shorter than max_seq)
    written over slot ``slot`` of ``cache``, leaf by leaf. Under jit
    with ``cache`` donated this is in place."""
    def write(c, e):
        return jax.lax.dynamic_update_slice(
            c, e.astype(c.dtype), (0, slot) + (0,) * (c.ndim - 2))
    return jax.tree.map(write, cache, entry)


# ``dense_only`` of the families whose cache holds recurrent state, and
# of the one whose cache is rows of a latent
_CONSUMED = "holds recurrent state that a decode step consumes"
_LATENT = ("is rows of a latent that two attention forms of its own "
           "read, not keys and values")


def _nbytes(leaves) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in leaves))


@functools.cache
def _llama() -> ModelFamily:
    from ray_tpu.models import llama

    def prefill(params, tokens, length, config, lora):
        logits, ks, vs = llama.llama_prefill(params, tokens, config,
                                             lora=lora)
        return logits, (ks, vs), None

    def decode_step(params, token, cache, pos, live, config, lora_bank,
                    lora_idx):
        logits, ck, cv = llama.llama_decode_step(
            params, token, *cache, pos, config, lora_bank=lora_bank,
            lora_idx=lora_idx)
        return logits, (ck, cv), None

    return ModelFamily(
        init=llama.llama_init,
        hidden=lambda params, tokens, config: llama.llama_forward(
            params, tokens, config, return_hidden=True),
        init_cache=llama.llama_init_cache, prefill=prefill,
        decode_step=decode_step,
        cache_bytes=lambda cache: {"kv": _nbytes(cache), "recurrent": 0})


@functools.cache
def _jamba() -> ModelFamily:
    from ray_tpu.models import jamba

    def prefill(params, tokens, length, config, lora):
        return (*jamba.jamba_prefill(params, tokens, length, config), None)

    def decode_step(params, token, cache, pos, live, config, lora_bank,
                    lora_idx):
        return (*jamba.jamba_decode_step(params, token, cache, pos, config),
                None)

    return ModelFamily(
        init=jamba.jamba_init,
        hidden=lambda params, tokens, config: jamba.jamba_forward(
            params, tokens, config, return_hidden=True),
        init_cache=jamba.jamba_init_cache, prefill=prefill,
        decode_step=decode_step,
        cache_bytes=lambda cache: {
            "kv": _nbytes([cache["k"], cache["v"]]),
            "recurrent": _nbytes([cache["ssm"], cache["conv"]])},
        dense_only=_CONSUMED)


@functools.cache
def _granite() -> ModelFamily:
    from ray_tpu.models import granite

    def prefill(params, tokens, length, config, lora):
        return granite.granite_prefill(params, tokens, length, config)

    def decode_step(params, token, cache, pos, live, config, lora_bank,
                    lora_idx):
        return granite.granite_decode_step(params, token, cache, pos, live,
                                           config)

    return ModelFamily(
        init=granite.granite_init,
        hidden=lambda params, tokens, config: granite.granite_forward(
            params, tokens, config, return_hidden=True),
        init_cache=granite.granite_init_cache, prefill=prefill,
        decode_step=decode_step,
        cache_bytes=lambda cache: {
            "kv": _nbytes([cache["k"], cache["v"]]),
            "recurrent": _nbytes([cache["ssm"], cache["conv"]])},
        dense_only=_CONSUMED,
        expert_counts=granite.EXPERT_COUNTS,
        skips_parked_state=True)


@functools.cache
def _lfm2() -> ModelFamily:
    from ray_tpu.models import lfm2
    from ray_tpu.ops.attention import cache_row_shape

    def prefill(params, tokens, length, config, lora):
        return lfm2.lfm2_prefill(params, tokens, length, config)

    def decode_step(params, token, cache, pos, live, config, lora_bank,
                    lora_idx):
        return lfm2.lfm2_decode_step(params, token, cache, pos, live,
                                     config)

    return ModelFamily(
        init=lfm2.lfm2_init,
        hidden=lambda params, tokens, config: lfm2.lfm2_forward(
            params, tokens, config, return_hidden=True),
        init_cache=lfm2.lfm2_init_cache, prefill=prefill,
        decode_step=decode_step,
        cache_bytes=lambda cache: {
            "kv": _nbytes([cache["k"], cache["v"]]),
            "recurrent": _nbytes([cache["conv"]])},
        dense_only=_CONSUMED,
        expert_counts=lfm2.EXPERT_COUNTS,
        kv_row_shape=lambda c: cache_row_shape(c.n_kv_heads, c.head_dim))


@functools.cache
def _mla() -> ModelFamily:
    from ray_tpu.models import mla

    def prefill(params, tokens, length, config, lora):
        return mla.mla_prefill(params, tokens, length, config)

    def decode_step(params, token, cache, pos, live, config, lora_bank,
                    lora_idx):
        return mla.mla_decode_step(params, token, cache, pos, live, config)

    return ModelFamily(
        init=mla.mla_init,
        hidden=lambda params, tokens, config: mla.mla_forward(
            params, tokens, config, return_hidden=True),
        init_cache=mla.mla_init_cache, prefill=prefill,
        decode_step=decode_step,
        cache_bytes=lambda cache: {"latent": _nbytes([cache["latent"]])},
        dense_only=_LATENT,
        expert_counts=mla.EXPERT_COUNTS,
        kv_row_shape=lambda c: (1, c.latent_lanes))


_FAMILIES: Dict[str, Callable[[], ModelFamily]] = {
    "LlamaConfig": _llama, "JambaConfig": _jamba,
    "GraniteConfig": _granite, "Lfm2Config": _lfm2, "MlaConfig": _mla}


def family_of(config: Any) -> ModelFamily:
    """The family of a model configuration, by the configuration's
    class (LlamaConfig, JambaConfig, GraniteConfig, Lfm2Config,
    MlaConfig)."""
    try:
        return _FAMILIES[type(config).__name__]()
    except KeyError:
        raise TypeError(
            f"no model family for a {type(config).__name__} "
            f"(have {sorted(_FAMILIES)})") from None
