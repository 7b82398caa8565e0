"""Rotary position embeddings (RoPE).

Pure elementwise math — XLA fuses it into the surrounding projections,
so no Pallas kernel is needed; a hand kernel would only pin a layout
the compiler might beat."""

from __future__ import annotations

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


def rope_at(positions, head_dim: int, theta: float = 10000.0):
    """cos/sin at ``positions`` [n] alone: [n, head_dim//2] each, for a
    caller whose rows each stand at a position of their own."""
    freqs = jnp.outer(positions.astype(jnp.float32),
                      _inv_freq(head_dim, theta))
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
