"""The Granite-4.0-H family (ray_tpu.models.granite; ``model_type``
granitemoehybrid): a trunk of Mamba-2 mixers with an attention layer
where ``layer_types`` says so, a routed and a shared feed-forward in
every layer, four multipliers, a tied embedding; served as one rank of
an expert-parallel group (``experts_held`` of the router's
``router_outputs`` experts, a slice of the vocabulary). The
granite-4.0-h-small configuration file names it. Serving only: the
chunked recurrence has no backward and the expert layer's serving form
holds no training batch."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import BenchError
from benchmark.programs.controls import state_bf16

# what the CPU rehearsal runs in place of the published sizes
# (GraniteConfig.tiny's): four layers of which the third is attention,
# 8 experts of which the first 4 are held, top-3, multipliers that are
# not 1
_REHEARSAL = dict(
    vocab_size=512, dim=64,
    layer_types=("mamba", "mamba", "attention", "mamba"), n_heads=4,
    n_kv_heads=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    mamba_chunk_size=16, n_experts=8, experts_held=(0, 4), top_k=3,
    expert_dim=32, shared_expert_dim=48, embedding_multiplier=3.0,
    attention_multiplier=0.125, residual_multiplier=0.5,
    logits_scaling=2.0, attention="reference")


def _model_kwargs(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The published keys, as GraniteConfig names them."""
    if config["position_embedding_type"] != "nope":
        raise BenchError("the program's Granite has no position encoding "
                         "in its attention layers (\"nope\")")
    if (not config["tie_word_embeddings"] or config["mamba_proj_bias"]
            or not config["mamba_conv_bias"] or config["attention_bias"]):
        raise BenchError("the program's Granite ties its embedding, has a "
                         "bias on the convolution and none on the "
                         "projections")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise BenchError("layer_types does not name num_hidden_layers "
                         "layers")
    first, count = config["experts_held"]
    if count != config["num_local_experts"]:
        raise BenchError("experts_held does not hold num_local_experts "
                         "experts")
    kw = dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_chunk_size=config["mamba_chunk_size"],
        n_experts=config["router_outputs"], experts_held=(first, count),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["intermediate_size"],
        shared_expert_dim=config["shared_intermediate_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]), attention="flash")
    if rehearse:
        import jax.numpy as jnp
        kw.update(_REHEARSAL, dtype=jnp.float32)
    return kw


def serving_model(config: Dict[str, Any], max_seq: int, rehearse: bool):
    try:
        from ray_tpu.models.granite import GraniteConfig
    except ImportError as exc:
        raise BenchError(f"the program has no Granite family: {exc}") from exc
    return GraniteConfig(max_seq_len=max_seq,
                         **_model_kwargs(config, rehearse))


def training(config: Dict[str, Any], sizes: Dict[str, Any],
             rehearse: bool) -> Dict[str, Any]:
    raise BenchError("the Granite family has no training path yet: the "
                     "chunked recurrence has no backward, and the expert "
                     "layer's serving form holds no training batch")


def vocab_size(config: Dict[str, Any], rehearse: bool) -> int:
    return _REHEARSAL["vocab_size"] if rehearse else config["vocab_size"]


def kernels(program_name: str) -> List[str]:
    """What the engine's programs hold on a TPU: a prefill program
    flash attention (the one attention layer) and rms_norm; the decode
    programs decode_attention, rms_norm and, since PR 58, ssd_update
    (the live slots' state moved in one pass). The chunked recurrence
    and the expert layer are plain XLA (the grouped matmul is
    ``jax.lax.ragged_dot``, which the compiler lowers itself)."""
    if program_name.startswith("prefill_"):
        return ["flash_fwd", "rms_norm"]
    if program_name == "train_step":
        raise BenchError("the Granite family has no training path yet")
    return ["decode_attention", "rms_norm", "ssd_update"]


def routed(config: Dict[str, Any]) -> bool:
    """True since PR 59: judged by reference_check.routed_report under
    the margin, the floor and the limits of its own configuration file
    (``check.limits``; ``check.calibration`` has the readings). By the
    constants it could not be: at a router margin of 0.04 next to none
    of the tokens is decided over ten layers of 72 logits and ten picks,
    and ``logits_scaling`` 16 shrinks every difference sixteenfold under
    limits made for logits of unit spread."""
    return True


def expert_zeroed(engine) -> None:
    """The first held expert's output projection is zero in every
    layer: the program drops what that expert would add to the tokens
    routed to it."""
    import jax

    zero = jax.jit(lambda w: w.at[:, 0].set(0))
    p = engine.params
    engine.params = {**p, **{kind: {**p[kind],
                                    "w_out_e": zero(p[kind]["w_out_e"])}
                             for kind in ("mamba", "attn")}}


def controls(config: Dict[str, Any]) -> Dict[str, Any]:
    """``expert_zeroed``: a part of the model left out (one of the 36
    held experts). ``state_bf16``: the precision under the float32 that
    the configuration's ``assumed.recurrence`` states for the state."""
    return {"expert_zeroed": expert_zeroed, "state_bf16": state_bf16}
