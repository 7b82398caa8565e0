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

import importlib
from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax


@dataclass(frozen=True)
class ModelFamily:
    # (rng, config) -> params
    init: Callable
    # (params, tokens [B, S], config) -> final-norm hidden [B, S, dim]
    hidden: Callable
    # (config, batch, max_seq) -> cache: a dict of named leaves (or the
    # Llama family's pair of keys and values), the slot on axis 1 of
    # each. The names say what kind of bytes a leaf holds, to
    # ``cache_bytes`` and to the series ENGINE_CACHE_BYTES: ``k`` and
    # ``v`` are "kv", ``latent`` is "latent", every other leaf is
    # "recurrent" (a state that a decode step consumes); a cache with
    # keys and values says its recurrent bytes too, 0 where it has none
    init_cache: Callable
    # (params, tokens [1, bucket], length, config, lora) -> (logits
    # [1, n, vocab] float32, entry, counts). ``length`` is the prompt's
    # true length, traced; a family that has no use for it ignores it
    # and its programs do not hold it. n is the bucket, or 1 where the
    # family returns the row of position length - 1 alone. ``lora``
    # (and ``lora_bank``, ``lora_idx`` below) are None for every family
    # with a ``dense_only``: its functions take them and ignore them.
    prefill: Callable
    # (params, token [B], cache, pos [B], live [B], config, lora_bank,
    # lora_idx) -> (logits [B, vocab] float32, cache, counts); the
    # caller donates cache. ``live`` says which slots hold a request
    # (the others are parked: computed, and counted by nobody).
    decode_step: Callable
    # cache -> bytes by kind, {"kv": ..., "recurrent": ...} or
    # {"latent": ...}: the function ``cache_bytes`` below, for all
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

    @classmethod
    def of(cls, *, init, forward, init_cache, prefill, decode_step,
           **words) -> "ModelFamily":
        """A family from its module's own functions, at the end of that
        module. ``forward(params, tokens, config, return_hidden=)`` is
        its whole-sequence forward, of which ``hidden`` is one form;
        ``words`` are the fields after ``cache_bytes``."""
        return cls(
            init=init,
            hidden=lambda params, tokens, config: forward(
                params, tokens, config, return_hidden=True),
            init_cache=init_cache, prefill=prefill, decode_step=decode_step,
            cache_bytes=cache_bytes, **words)


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
CONSUMED = "holds recurrent state that a decode step consumes"
LATENT = ("is rows of a latent that two attention forms of its own "
          "read, not keys and values")


def cache_bytes(cache) -> Dict[str, int]:
    """A cache's bytes by kind, from its leaves' names (the rule at
    ``ModelFamily.init_cache``)."""
    named = cache if isinstance(cache, dict) else dict(zip("kv", cache))
    kinds = {"k": "kv", "v": "kv", "latent": "latent"}
    total: Dict[str, int] = {}
    for name, leaf in named.items():
        kind = kinds.get(name, "recurrent")
        total[kind] = total.get(kind, 0) + int(leaf.size
                                               * leaf.dtype.itemsize)
    if "kv" in total:
        total.setdefault("recurrent", 0)
    return {kind: total[kind] for kind in ("kv", "recurrent", "latent")
            if kind in total}


# a configuration's class name -> the module that ends with its
# ``FAMILY``, imported on first use (this module imports no model, so a
# model's module imports ModelFamily from here whichever comes first)
_FAMILIES: Dict[str, str] = {
    "LlamaConfig": "ray_tpu.models.llama",
    "JambaConfig": "ray_tpu.models.jamba",
    "GraniteConfig": "ray_tpu.models.granite",
    "Lfm2Config": "ray_tpu.models.lfm2",
    "MlaConfig": "ray_tpu.models.mla"}


def family_of(config: Any) -> ModelFamily:
    """The family of a model configuration, by the configuration's
    class (LlamaConfig, JambaConfig, GraniteConfig, Lfm2Config,
    MlaConfig)."""
    try:
        module = _FAMILIES[type(config).__name__]
    except KeyError:
        raise TypeError(
            f"no model family for a {type(config).__name__} "
            f"(have {sorted(_FAMILIES)})") from None
    return importlib.import_module(module).FAMILY
