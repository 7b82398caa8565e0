"""Parameters and bytes of the LFM2-MoE family's work, from shapes: what
the algorithm needs, counted by the benchmark and never by the program.
``config`` is a configuration file's published keys.

Counting rules
- one whole-batch decode step has to READ, whatever the implementation:
  every mixer, dense feed-forward, norm and router once, the head once,
  of the routed experts those that at least one live row picked (an
  expert nobody picked adds nothing, so no floor is owed for it: a
  program that reads all 32 reads more than this floor and shows a lower
  share for it), and the attention layers' K and V rows up to each live
  slot's position. All in bf16 but the selection bias (float32).
- left out, each under a thousandth of the rest: the embedding rows of
  the step's tokens, the convolution's state (8 KiB a slot and layer),
  activations, the sampler.
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2
F32 = 4


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    attn = config["layer_types"].count("full_attention")
    n = len(config["layer_types"])
    dense = min(config["num_dense_layers"], n)
    return {"attn": attn, "conv": n - attn, "dense": dense,
            "moe": n - dense}


def params(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one sublayer of each kind, of one expert, of a
    router, and of the embedding and the head."""
    h = config["hidden_size"]
    hd = h // config["num_attention_heads"]
    return {
        "conv": h + 3 * h * h + config["conv_L_cache"] * h + h * h,
        "attn": (h + 2 * h * h + 2 * h * config["num_key_value_heads"] * hd
                 + 2 * hd),
        "dense": h + 3 * h * config["intermediate_size"],
        "expert": 3 * h * config["moe_intermediate_size"],
        "router": h + h * config["num_experts"],    # with the layer's norm
        "embedding": config["vocab_size"] * h,
        "head": h + h * config["vocab_size"]}       # with the final norm


def model_params(config: Dict[str, Any]) -> int:
    p, n = params(config), layer_counts(config)
    return (n["conv"] * p["conv"] + n["attn"] * p["attn"]
            + n["dense"] * p["dense"]
            + n["moe"] * (p["router"] + config["num_experts"]
                          * (p["expert"] + 1))       # + the bias
            + p["embedding"] + p["head"])


def kv_bytes_per_row(config: Dict[str, Any]) -> int:
    """K and V of one position of ONE attention layer."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * hd * BF16


def decode_floor_bytes(config: Dict[str, Any], experts_hit: float,
                       kv_rows: float) -> Dict[str, float]:
    """The bytes one decode step has to read: ``experts_hit`` experts
    over all routed layers (a mean over steps may be fractional),
    ``kv_rows`` rows of each attention layer's cache."""
    p, n = params(config), layer_counts(config)
    return {
        "always": float(
            (n["conv"] * p["conv"] + n["attn"] * p["attn"]
             + n["dense"] * p["dense"] + n["moe"] * p["router"]
             + p["head"]) * BF16 + n["moe"] * config["num_experts"] * F32),
        "experts": float(experts_hit * p["expert"] * BF16),
        "kv": float(kv_rows * n["attn"] * kv_bytes_per_row(config))}
