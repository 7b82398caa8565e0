"""The LFM2-MoE family (ray_tpu.models.lfm2; ``model_type`` lfm2_moe):
a trunk of gated short-convolution mixers with an attention layer where
``layer_types`` says so (QK-norm, then rotary), ``num_dense_layers``
leading dense feed-forwards and a routed one in every later layer, its
router a sigmoid with a selection bias; embedding and head two
matrices. The lfm2-8b-a1b configuration file names it. Serving only:
the expert layer's serving form holds no training batch."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import BenchError

# what the CPU rehearsal runs in place of the published sizes
# (Lfm2Config.tiny's): one leading dense layer, then both mixers under
# routed layers, 8 experts top-3, a scale that is not 1, a bias that
# moves picks
_REHEARSAL = dict(
    vocab_size=512, dim=64,
    layer_types=("conv", "full_attention", "conv", "conv",
                 "full_attention"),
    n_dense_layers=1, n_heads=4, n_kv_heads=2, dense_dim=96, n_experts=8,
    top_k=3, expert_dim=32, routed_scaling=1.5, router_bias_std=0.2,
    attention="reference")


def _model_kwargs(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The published keys, as Lfm2Config names them."""
    if (config["conv_bias"] or not config["norm_topk_prob"]
            or not config["use_expert_bias"]):
        raise BenchError("the program's LFM2 has no bias on the "
                         "convolution, renormalises the picked scores and "
                         "adds a selection bias for the choice")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise BenchError("layer_types does not name num_hidden_layers "
                         "layers")
    kw = dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_dense_layers=config["num_dense_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        conv_taps=config["conv_L_cache"],
        dense_dim=config["intermediate_size"],
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        routed_scaling=float(config["routed_scaling_factor"]),
        router_bias_std=float(config["router_bias_std"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]), attention="flash")
    if rehearse:
        import jax.numpy as jnp
        kw.update(_REHEARSAL, dtype=jnp.float32)
    return kw


def serving_model(config: Dict[str, Any], max_seq: int, rehearse: bool):
    try:
        from ray_tpu.models.lfm2 import Lfm2Config
    except ImportError as exc:
        raise BenchError(f"the program has no LFM2 family: {exc}") from exc
    return Lfm2Config(max_seq_len=max_seq, **_model_kwargs(config, rehearse))


def training(config: Dict[str, Any], sizes: Dict[str, Any],
             rehearse: bool) -> Dict[str, Any]:
    raise BenchError("the LFM2 family has no training path yet: the "
                     "expert layer's serving form holds no training batch")


def vocab_size(config: Dict[str, Any], rehearse: bool) -> int:
    return _REHEARSAL["vocab_size"] if rehearse else config["vocab_size"]


def kernels(program_name: str) -> List[str]:
    """What the engine's programs hold on a TPU: a prefill program flash
    attention (heads of 64, padded to the kernel's 128 lanes) and
    rms_norm; the decode programs decode_attention (over the cache's
    packed rows) and rms_norm. The short convolution, the per-head norms
    and the expert layer are plain XLA (the grouped matmul is
    ``jax.lax.ragged_dot``, which the compiler lowers itself)."""
    if program_name.startswith("prefill_"):
        return ["flash_fwd", "rms_norm"]
    if program_name == "train_step":
        raise BenchError("the LFM2 family has no training path yet")
    return ["decode_attention", "rms_norm"]


def routed(config: Dict[str, Any]) -> bool:
    """Judged by reference_check.routed_report under the margin, the
    floor and the limits of its own configuration file (``check.limits``;
    ``check.calibration`` has the readings). The margin is of the
    selection scores ``sigmoid(l) + b``, what this router picks by."""
    return True


def _changed(engine, stack: str, name: str, change) -> None:
    import jax

    p = engine.params
    engine.params = {**p, stack: {**p[stack],
                                  name: jax.jit(change)(p[stack][name])}}


def expert_zeroed(engine) -> None:
    """The first expert's output projection is zero in every routed
    layer: the program drops what that expert would add to the tokens
    routed to it."""
    _changed(engine, "moe", "w_out_e", lambda w: w.at[:, 0].set(0))


def bias_dropped(engine) -> None:
    """The selection bias is zero in every routed layer: the program
    picks by the scores alone."""
    _changed(engine, "moe", "router_bias", lambda b: b * 0)


def conv_tap_zeroed(engine) -> None:
    """The oldest tap is zero in every convolution layer: the program
    mixes two columns where the model mixes three."""
    _changed(engine, "conv", "conv_w", lambda w: w.at[:, 0].set(0))


def controls(config: Dict[str, Any]) -> Dict[str, Any]:
    """Each leaves out one thing this family adds: one of the 32
    experts, the router's selection bias, one of the convolution's
    three taps."""
    return {"expert_zeroed": expert_zeroed, "bias_dropped": bias_dropped,
            "conv_tap_zeroed": conv_tap_zeroed}
