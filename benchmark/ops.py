"""Operations and bytes from shapes: what the algorithm needs, counted
by the benchmark and never by the program. ``config`` is a
configuration file's published keys (hidden_size, ...).

Counting rules
- a matmul of [m, k] x [k, n] is 2 m k n operations;
- training: forward plus backward is three times the forward's
  matmuls, so 6 operations per parameter per token. Counted: the
  layers' projection and feed-forward matrices (for experts, only the
  num_experts_per_tok a token reaches, plus the router) and the output
  head. Not counted: the embedding look-up (a gather), norms,
  rotations, and anything recomputed under remat;
- causal attention, forward: scores and weighted values are 2 x 2 x S
  x S x D per head, half of it masked away: 4 S^2 D / 2 per head per
  sequence;
- for the model's utilization (MFU) attention's backward counts twice
  its forward, like every other matmul (the PaLM paper's convention):
  6 x layers x S x hidden per token;
- for a kernel's roofline share each call counts the matmuls that call
  has to do: flash_dq recomputes the scores and dP and forms dQ (three
  against the forward's two: 1.5 x), flash_dkv recomputes both and
  forms dV and dK (four: 2.0 x).
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2


def head_dim(config: Dict[str, Any]) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


def layer_matmul_params(config: Dict[str, Any], active: bool = True) -> int:
    """Matrix parameters of one layer; with ``active`` an expert layer
    counts the experts one token reaches."""
    d, hd = config["hidden_size"], head_dim(config)
    attn = (2 * d * config["num_attention_heads"] * hd
            + 2 * d * config["num_key_value_heads"] * hd)
    ffn = 3 * d * config["intermediate_size"]
    experts = config.get("num_local_experts", 0)
    if experts:
        per_token = config["num_experts_per_tok"] if active else experts
        return attn + per_token * ffn + d * experts
    return attn + ffn


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token in a sequence of ``seq``."""
    matmuls = 6.0 * (config["num_hidden_layers"]
                     * layer_matmul_params(config) + head_params(config))
    # 3 x (4 S^2 D / 2) per head per layer per sequence, over S tokens
    attention = (6.0 * config["num_hidden_layers"] * seq
                 * config["num_attention_heads"] * head_dim(config))
    return matmuls + attention


def flash_call(kernel: str, batch: int, heads: int, seq: int, dim: int
               ) -> Dict[str, float]:
    """Operations and bytes of ONE call of a flash-attention kernel on
    q, k, v of [batch, seq, heads, dim] in bf16 (the program expands
    K and V to the query's heads before the kernel). Bytes are each
    operand read once and each result written once."""
    fwd = 4.0 * batch * heads * seq * seq * dim / 2
    tensor = batch * seq * heads * dim * BF16
    rows = batch * heads * seq * 4          # a float32 per query row
    if kernel == "flash_fwd":               # q k v -> o, lse
        return {"flops": fwd, "bytes": 4 * tensor + rows}
    if kernel == "flash_dq":                # q k v do lse delta -> dq
        return {"flops": 1.5 * fwd, "bytes": 5 * tensor + 2 * rows}
    if kernel == "flash_dkv":               # q k v do lse delta -> dk dv
        return {"flops": 2.0 * fwd, "bytes": 6 * tensor + 2 * rows}
    raise ValueError(f"no count for kernel {kernel!r}")


def least_seconds(call: Dict[str, float], peaks: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """The least time the chip could take for a call, and which of the
    two peaks bounds it."""
    compute = call["flops"] / peaks["bf16_flops_per_s"]
    memory = call["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
