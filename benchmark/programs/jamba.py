"""The Jamba family (ray_tpu.models.jamba): a trunk of Mamba-1 mixers
with an attention layer every ``attn_layer_period``, dense SwiGLU
feed-forwards, a tied embedding. The AI21-Jamba2-3B configuration file
names it. Serving only: the selective scan has no backward."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import BenchError
from benchmark.programs.controls import state_bf16

# what the CPU rehearsal runs in place of the published sizes
# (JambaConfig.tiny's): four layers of which the third is attention, so
# both kinds of layer and both kinds of cache are there
_REHEARSAL = dict(vocab_size=512, dim=64, n_layers=4, n_heads=4,
                  n_kv_heads=1, hidden_dim=128, attn_layer_period=4,
                  attn_layer_offset=2, mamba_dt_rank=4,
                  attention="reference")


def _model_kwargs(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The published keys, as JambaConfig names them."""
    if config.get("sliding_window") is not None:
        raise BenchError("the program has no sliding-window attention")
    if config["num_experts"] != 1:
        raise BenchError("the program's Jamba has dense feed-forwards "
                         "only (num_experts 1)")
    if not config["tie_word_embeddings"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"]:
        raise BenchError("the program's Jamba ties its embedding, has a "
                         "bias on the convolution and none on the "
                         "mixer's projections")
    kw = dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        hidden_dim=config["intermediate_size"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_dt_rank=config["mamba_dt_rank"],
        norm_eps=float(config["rms_norm_eps"]), attention="flash")
    if rehearse:
        import jax.numpy as jnp
        kw.update(_REHEARSAL, dtype=jnp.float32)
    return kw


def serving_model(config: Dict[str, Any], max_seq: int, rehearse: bool):
    try:
        from ray_tpu.models.jamba import JambaConfig
    except ImportError as exc:
        raise BenchError(f"the program has no Jamba family: {exc}") from exc
    return JambaConfig(max_seq_len=max_seq,
                       **_model_kwargs(config, rehearse))


def training(config: Dict[str, Any], sizes: Dict[str, Any],
             rehearse: bool) -> Dict[str, Any]:
    raise BenchError("the Jamba family has no training path yet: the "
                     "selective scan has no backward")


def vocab_size(config: Dict[str, Any], rehearse: bool) -> int:
    return _REHEARSAL["vocab_size"] if rehearse else config["vocab_size"]


def kernels(program_name: str) -> List[str]:
    """What the engine's programs hold on a TPU: a prefill program its
    bucket's scan kernel (26 Mamba layers), flash attention (2 layers)
    and rms_norm; the decode programs rms_norm (the state update and
    the product over the stored cache are plain XLA)."""
    if program_name.startswith("prefill_"):
        bucket = program_name[len("prefill_"):]
        return [f"selective_scan_{bucket}", "flash_fwd", "rms_norm"]
    if program_name == "train_step":
        raise BenchError("the Jamba family has no training path yet")
    return ["rms_norm"]


def routed(config: Dict[str, Any]) -> bool:
    return False


def controls(config: Dict[str, Any]) -> Dict[str, Any]:
    """``state_bf16``: the precision under the float32 that the
    configuration's ``assumed.recurrence`` states for the state."""
    return {"state_bf16": state_bf16}
