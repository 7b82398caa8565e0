"""The Llama-shaped family (ray_tpu.models.llama): a trunk of
grouped-query rotary attention and SwiGLU, dense or with routed
experts. Mistral-7B and Mixtral-8x7B configuration files name it."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import BenchError

# what the CPU rehearsal runs in place of the published sizes
# (LlamaConfig.tiny's): still grouped-query, still dense or experts
_REHEARSAL = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, hidden_dim=128, attention="reference")


def _model_kwargs(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The published keys, as LlamaConfig names them."""
    if config.get("sliding_window") is not None:
        raise BenchError("the program has no sliding-window attention")
    kw = dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        hidden_dim=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        moe_experts=int(config.get("num_local_experts", 0)),
        moe_top_k=int(config.get("num_experts_per_tok", 2)),
        attention="flash")
    if rehearse:
        import jax.numpy as jnp
        kw.update(_REHEARSAL, moe_experts=4 if kw["moe_experts"] else 0,
                  dtype=jnp.float32)
    return kw


def serving_model(config: Dict[str, Any], max_seq: int, rehearse: bool):
    from ray_tpu.models.llama import LlamaConfig

    kw = _model_kwargs(config, rehearse)
    if rehearse:
        kw.update(remat=False)
    return LlamaConfig(max_seq_len=max_seq, **kw)


def training(config: Dict[str, Any], sizes: Dict[str, Any],
             rehearse: bool) -> Dict[str, Any]:
    from ray_tpu.models.llama import (LlamaConfig, llama_init, llama_loss,
                                      llama_sharding_rules)

    model = LlamaConfig(
        max_seq_len=sizes["seq"], remat=sizes["remat"],
        ce_chunk_tokens=sizes["ce_chunk_tokens"],
        **_model_kwargs(config, rehearse))
    return {
        "model": model,
        "init": lambda key: llama_init(key, model),
        "loss": lambda params, tokens, targets, mesh: llama_loss(
            params, tokens, targets, model, mesh),
        "sharding_rules": llama_sharding_rules("fsdp")}


def vocab_size(config: Dict[str, Any], rehearse: bool) -> int:
    return _REHEARSAL["vocab_size"] if rehearse else config["vocab_size"]


def kernels(program_name: str) -> List[str]:
    """What attention="flash" asks for on a TPU."""
    if program_name == "train_step":
        return ["flash_fwd", "flash_dq", "flash_dkv", "rms_norm"]
    if program_name.startswith("prefill"):
        return ["flash_fwd", "rms_norm"]
    return ["rms_norm"]


def routed(config: Dict[str, Any]) -> bool:
    return int(config.get("num_local_experts", 0)) > 0
