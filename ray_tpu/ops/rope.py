"""Rotary position embeddings (RoPE).

Pure elementwise math — XLA fuses it into the surrounding projections,
so no Pallas kernel is needed; a hand kernel would only pin a layout
the compiler might beat."""

from __future__ import annotations

import math

import jax.numpy as jnp


def _inv_freq(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def rope_frequencies(head_dim: int, max_seq_len: int,
                     theta: float = 10000.0):
    """Precompute cos/sin tables: [max_seq_len, head_dim//2]."""
    inv_freq = _inv_freq(head_dim, theta)
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's inverse frequencies [head_dim//2] (``rope_scaling.type:
    yarn``): a pair that turns more than ``beta_fast`` times over the
    ``original_max`` positions the model was trained on keeps its
    frequency, one that turns less than ``beta_slow`` times has it
    divided by ``factor``, and between the two pair indices a linear
    ramp blends them."""
    def pair_of(turns: float) -> float:
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    freq = _inv_freq(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 where
    nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_at(positions, head_dim: int, theta: float = 10000.0,
            inv_freq=None):
    """cos/sin at ``positions`` [n] alone: [n, head_dim//2] each, for a
    caller whose rows each stand at a position of their own.
    ``inv_freq`` [head_dim//2]: a family's own frequencies
    (``yarn_inv_freq``) in place of ``theta``'s."""
    positions = positions.astype(jnp.float32)
    if inv_freq is None:
        inv_freq = _inv_freq(head_dim, theta)
    freqs = jnp.outer(positions, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, positions=None):
    """Rotate pairs of channels. x: [B, S, H, D]; cos/sin: [S_max, D//2];
    positions: [B, S] optional absolute positions (default arange)."""
    b, s, h, d = x.shape
    if positions is None:
        cos_sel = cos[:s][None, :, None, :]       # [1, S, 1, D/2]
        sin_sel = sin[:s][None, :, None, :]
    else:
        cos_sel = cos[positions][:, :, None, :]   # [B, S, 1, D/2]
        sin_sel = sin[positions][:, :, None, :]
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    out1 = x1 * cos_sel - x2 * sin_sel
    out2 = x2 * cos_sel + x1 * sin_sel
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
