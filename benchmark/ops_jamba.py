"""Operations and bytes of the Jamba family's own work, from shapes:
what the algorithm needs, counted by the benchmark and never by the
program. ``config`` is a configuration file's published keys.

Counting rules
- one ``selective_scan_<L>`` call is the recurrence of one Mamba layer
  over one prompt's bucket of L positions: d_inner channels of
  ``mamba_d_state`` states each. Per channel, state and token it needs 9
  operations: the product dt * A, the exponential, the decay h * dA, the
  input's dt * x (shared by a channel's states, counted with them), its
  product with B, the sum into h, the product with C, the sum over the
  states into y, and D * x with its sum (shared likewise). The
  exponential counts as one operation.
- bytes, as the algorithm needs them and not as one program chose:
  ``x`` and ``dt`` read and ``y`` written once in bf16 (a program that
  keeps ``dt`` and ``y`` in float32, as ray_tpu/models/jamba.py does
  for its precision, moves more than this floor and reads a lower
  share for it); ``B`` and ``C`` ([L, N]), ``A`` ([N, d_inner]), ``D``
  and both ``h`` ([N, d_inner], the initial and the final one) once in
  float32. The state stays on the chip across time. 31 KB a token and
  layer, where a scan through HBM would move 64 bytes per channel,
  state and token, several times.
- ``positions`` of a call are the prompt's own: a kernel that skips the
  padding of a bucket is owed no floor for it. The reader takes them
  from the engine's count of real and padded prefill positions.
- the kernel is bound by the vector unit, for which the chip's table
  (peaks.json) has no peak: bf16_flops_per_s is the matrix unit's. The
  floor these counts give is therefore the memory floor, and a roofline
  share read from it says how far the kernel is from being bound by
  memory, which is the least any implementation of it could be.
- one decode step reads every weight once (the tied embedding once, as
  the output head; the rows it looks up are noise) and reads and writes
  every slot's recurrent state and convolution inputs, and reads the
  attention layers' rows up to max_seq.
"""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2
F32 = 4
SCAN_OPS = 9     # per channel, state and token (see above)


def d_inner(config: Dict[str, Any]) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def layer_kinds(config: Dict[str, Any]) -> Dict[str, int]:
    attn = sum(
        1 for i in range(config["num_hidden_layers"])
        if i % config["attn_layer_period"] == config["attn_layer_offset"])
    return {"attn": attn, "mamba": config["num_hidden_layers"] - attn}


def scan_call(config: Dict[str, Any], positions: float) -> Dict[str, float]:
    """Operations and bytes of ONE selective_scan call over
    ``positions`` real positions (a mean over calls may be fractional)."""
    d, n = d_inner(config), config["mamba_d_state"]
    return {
        "flops": float(SCAN_OPS * positions * d * n),
        "bytes": float(positions * d * 3 * BF16         # x, dt, y
                       + 2 * positions * n * F32        # B, C
                       + (n * d + d + 2 * n * d) * F32)}   # A, D, h, h


def memory_seconds(call: Dict[str, float], peaks: Dict[str, Any]) -> float:
    """The least time the chip could take for a call that the vector
    unit computes: its bytes at the memory's peak."""
    return call["bytes"] / peaks["hbm_bytes_per_s"]


def params(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer of each kind, and of the embedding."""
    h, d, n = config["hidden_size"], d_inner(config), config["mamba_d_state"]
    r = config["mamba_dt_rank"]
    hd = h // config["num_attention_heads"]
    ffn = 3 * h * config["intermediate_size"] + h
    mamba = (h + h * 2 * d + (config["mamba_d_conv"] + 1) * d
             + d * (r + 2 * n) + r + 2 * n + r * d + d + n * d + d + d * h)
    attn = (h + 2 * h * config["num_attention_heads"] * hd
            + 2 * h * config["num_key_value_heads"] * hd)
    return {"mamba_layer": mamba + ffn, "attn_layer": attn + ffn,
            "embedding": config["vocab_size"] * h + h}


def model_params(config: Dict[str, Any]) -> int:
    p, kinds = params(config), layer_kinds(config)
    return (kinds["mamba"] * p["mamba_layer"]
            + kinds["attn"] * p["attn_layer"] + p["embedding"])


def state_bytes_per_slot(config: Dict[str, Any]) -> int:
    """Recurrent state one slot holds: h in float32 and the
    convolution's last inputs in bf16, over the Mamba layers."""
    d = d_inner(config)
    return layer_kinds(config)["mamba"] * (
        d * config["mamba_d_state"] * F32
        + d * (config["mamba_d_conv"] - 1) * BF16)


def kv_bytes_per_token(config: Dict[str, Any]) -> int:
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (layer_kinds(config)["attn"] * 2
            * config["num_key_value_heads"] * hd * BF16)


def decode_step_bytes(config: Dict[str, Any], slots: int, max_seq: int
                      ) -> Dict[str, float]:
    """What one whole-batch decode step has to move: the weights once,
    every slot's state read and written, the attention rows read."""
    return {"weights": float(model_params(config) * BF16),
            "state": float(2 * slots * state_bytes_per_slot(config)),
            "kv": float(slots * max_seq * kv_bytes_per_token(config))}
