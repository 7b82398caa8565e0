"""Parameters, bytes and operations of the latent-attention family's
work (``kimi_k2``: the DeepSeek-V3 block), from shapes: what the
algorithm needs, counted by the benchmark and never by the program.
``config`` is a configuration file's published keys, with
``n_routed_experts`` the experts HELD here and ``vocab_size`` the rows
held.

Counting rules
- one whole-batch decode step has to READ, whatever the implementation:
  every attention layer, shared expert, router, dense feed-forward and
  norm once, the head once, of the held routed experts those that at
  least one live row picked (an expert nobody picked adds nothing, so
  no floor is owed for it), and of each layer's latent cache the rows up
  to each live slot's position: the published row of ``kv_lora_rank +
  qk_rope_head_dim`` values, each ONCE (a row is the key and, in its
  first lanes, the value; a program that keeps it in more lanes or reads
  it twice reads more than this floor). All in bf16 but the selection
  bias (float32).
- a prefill has to COMPUTE, for the real tokens of its prompt: two
  operations a parameter that every token meets outside the routed
  experts and the head, the head for its one row, and causal attention
  at the true widths (keys of ``nope + rope``, values of ``v``). The
  routed experts' products are left out (which rows a held expert gets
  is the router's business), so the count is a lower bound.
- left out, each under a thousandth of the rest: the embedding rows of
  the step's tokens, activations, the sampler.
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2
F32 = 4


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    n = config["num_hidden_layers"]
    dense = min(config["first_k_dense_replace"], n)
    return {"attn": n, "dense": dense, "moe": n - dense}


def params(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one sublayer of each kind, of one expert, of a
    router, and of the embedding and the head."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    expert = 3 * h * config["moe_intermediate_size"]
    return {
        "attn": (h + h * q_rank + q_rank + q_rank * heads * (nope + rope)
                 + h * (kv_rank + rope) + kv_rank
                 + kv_rank * heads * (nope + v) + heads * v * h),
        "dense": h + 3 * h * config["intermediate_size"],
        "expert": expert,
        "shared": config["n_shared_experts"] * expert,
        "router": h + h * config["router_outputs"],  # with the layer's norm
        "embedding": config["vocab_size"] * h,
        "head": h + h * config["vocab_size"]}        # with the final norm


def model_params(config: Dict[str, Any]) -> int:
    p, n = params(config), layer_counts(config)
    return (n["attn"] * p["attn"] + n["dense"] * p["dense"]
            + n["moe"] * (p["router"] + config["router_outputs"]  # the bias
                          + p["shared"]
                          + config["n_routed_experts"] * p["expert"])
            + p["embedding"] + p["head"])


def always_params(config: Dict[str, Any]) -> int:
    """The parameters every token meets outside the routed experts and
    the head: attention, dense feed-forwards, shared experts, routers."""
    p, n = params(config), layer_counts(config)
    return (n["attn"] * p["attn"] + n["dense"] * p["dense"]
            + n["moe"] * (p["router"] + p["shared"]))


def latent_bytes_per_row(config: Dict[str, Any]) -> int:
    """The latent row of one position of ONE layer, as published."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * BF16


def decode_floor_bytes(config: Dict[str, Any], experts_hit: float,
                       latent_rows: float) -> Dict[str, float]:
    """The bytes one decode step has to read: ``experts_hit`` held
    experts over all routed layers (a mean over steps may be
    fractional), ``latent_rows`` rows of each layer's cache."""
    p, n = params(config), layer_counts(config)
    return {
        "always": float((always_params(config) + p["head"]) * BF16
                        + n["moe"] * config["router_outputs"] * F32),
        "experts": float(experts_hit * p["expert"] * BF16),
        "latent": float(latent_rows * n["attn"]
                        * latent_bytes_per_row(config))}


def prefill_floor_ops(config: Dict[str, Any], tokens: float,
                      prompts: float) -> float:
    """The operations the prefills of ``prompts`` prompts with
    ``tokens`` real tokens together need at least: causal attention
    costs a prompt of ``n_i`` tokens ``n_i ** 2 (nope + rope + v)`` a
    head and layer, and ``sum n_i ** 2`` is at least ``tokens ** 2 /
    prompts``."""
    p, n = params(config), layer_counts(config)
    per_pair = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                + config["v_head_dim"])
    return (2.0 * tokens * always_params(config)
            + 2.0 * prompts * p["head"]
            + n["attn"] * config["num_attention_heads"] * per_pair
            * tokens ** 2 / prompts)
