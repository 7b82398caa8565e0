"""The serving families' matmul against bf16 weights, with the few-rows
split: what lies between two matmuls stays in the accumulators'
float32, and a matmul of few rows carries its input's low half through.

``few_rows`` is the split alone, for a caller whose product is not a
plain ``x @ w`` (an expert layer's batched ones, parallel/moe.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# ``mm`` carries the low half of its input through when it has at most
# this many rows: a replica's 32 slots. It is not free: one decode step
# of the Jamba cell alone takes 10.74 ms with it and 10.39 without at 32
# slots, 12.97 / 12.30 at 64, 18.26 / 16.51 at 128 (one v5e chip,
# PERF.md, PR 34), so an engine of more slots rounds its input as a
# prefill does.
SPLIT_ROWS = 32


def few_rows(x, dtype):
    """x [..., rows, K] -> (the rows as a matmul against ``dtype``
    weights takes them, ``merge`` for that matmul's result [..., rows',
    F]).

    Many rows (a prefill): x rounded to ``dtype``, ``merge`` the
    identity. Few rows (a decode step, which reading the weights
    bounds): the rounding is taken back. x = hi + lo, both bf16, go
    through ONE matmul as [hi; lo] and ``merge`` adds the two halves of
    the result, so the weights are read once. ``hi`` comes from
    ``reduce_precision``, an operation XLA keeps: a float32 -> bf16 ->
    float32 pair of converts it may drop (excess precision), and ``lo``
    would be 0."""
    rows = x.shape[-2]
    if x.dtype == dtype or rows > SPLIT_ROWS:
        return x.astype(dtype), lambda out: out
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    both = jnp.concatenate([hi, x - hi], axis=-2).astype(dtype)
    return both, lambda out: out[..., :rows, :] + out[..., rows:, :]


def mm(x, w):
    """x [rows, K] @ w on the matrix unit, the result left in the
    accumulator's float32. Whatever lies between two matmuls (the
    residual stream, gates, the convolution, norms) stays float32: it
    is [tokens, features], next to nothing beside the weights a step
    reads, and every rounding saved is noise the sublayers in series do
    not add up (see PERF.md, PR 34).

    The few-rows split (``few_rows``) took the Jamba engine's distance
    from the float32 reference over 192 tokens from 0.075-0.129 at
    worst (limit 0.15) and 0.022-0.029 on average to 0.027-0.091 and
    0.008-0.012, for 3% of a decode step."""
    rows, merge = few_rows(x, w.dtype)
    return merge(jnp.dot(rows, w, preferred_element_type=jnp.float32))
