"""Selective scan: the recurrence of a Mamba-1 mixer over a sequence.

For one sequence, with ``h`` of [N, D] (N states for each of D
channels), in float32::

    h_t = exp(dt_t[None, :] * A) * h_{t-1} + (dt_t * x_t)[None, :] * B_t[:, None]
    y_t = sum_n(h_t * C_t[:, None]) + D_skip * x_t

A position at or past ``length`` passes ``h`` through unchanged (its
``dt`` counts as 0), so a prompt padded to a bucket leaves the state of
its true last token; ``y`` there is 0.

On a TPU this is one Pallas kernel per call. ``jax.lax.associative_scan``
over [L, D, N] float32 moves 64 bytes per channel, state and token
through HBM several times; the kernel keeps ``h`` in VMEM across time
and reads ``x`` and ``dt`` and writes ``y`` once (``dt`` and, where the
caller asks, ``y`` in float32: see models/jamba.py on what rounding
them costs). The grid walks time in
chunks; inside a chunk the channels are walked in blocks of lanes whose
``h`` stays in registers over the chunk's steps. ``B_t`` and ``C_t``
arrive as [L, N, 1] so that a step's column is one load with the states
on sublanes (a [N, 1] tile broadcasts along lanes for free; a row of
[L, N] would need a transpose a step). Chunks wholly past ``length`` do
no work and fetch nothing new. The kernel's name carries its length,
``selective_scan_<L>``, as the engine's prefill programs carry their
bucket, so that a trace alone tells what each call had to do.

Off the TPU, and for shapes the kernel does not cover, the same
recurrence runs as a sequential ``lax.scan`` (the kernel's test oracle);
on a TPU each such shape is noted in ``kernel_fallbacks``.

Forward only: there is no backward, so nothing here trains (a custom
VJP over the chunks is a PR of its own).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.accelerators import jax_backend

# Run the kernel in interpreter mode (CPU testing); toggled by tests.
_INTERPRET = False
# time steps a grid step walks, and lanes of channels whose h stays in
# registers over them ([16, 512] float32 is 8 vector registers)
_CHUNK = 64
_LANES = 512
_UNROLL = 2
# Shapes for which the scan was asked for on a TPU and the kernel does
# not cover them, one "x[L,D] n[N]" entry per trace; engine.stats()
# reads it as it reads flash attention's.
kernel_fallbacks: list = []


def _scan_reference(x, dt, b, c, a, d_skip, h0, length, out_dtype=None):
    """The recurrence as written above, one step at a time."""
    f32 = jnp.float32
    live = (jnp.arange(x.shape[0]) < length)[:, None]
    dt = jnp.where(live, dt.astype(f32), 0.0)

    def step(h, inp):
        x_t, dt_t, b_t, c_t, live_t = inp
        h = (jnp.exp(dt_t[None, :] * a) * h
             + (dt_t * x_t)[None, :] * b_t[:, None])
        y = jnp.sum(h * c_t[:, None], axis=0) + d_skip * x_t
        return h, jnp.where(live_t, y, 0.0)

    h, y = jax.lax.scan(step, h0.astype(f32), (
        x.astype(f32), dt, b.astype(f32), c.astype(f32), live))
    return y.astype(out_dtype or x.dtype), h


def _scan_kernel(len_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                 h0_ref, y_ref, h_ref, dt_s, dtx_s, y_s, *, chunk: int,
                 lanes: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    start = i * chunk
    length = len_ref[0]

    @pl.when(i == 0)
    def _first():
        h_ref[:] = h0_ref[:]

    @pl.when(start < length)
    def _live():
        x = x_ref[:].astype(jnp.float32)
        t = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        dt = jnp.where(t < length, dt_ref[:].astype(jnp.float32), 0.0)
        dt_s[:] = dt
        dtx_s[:] = dt * x
        for lo in range(0, x.shape[1], lanes):
            block = slice(lo, lo + lanes)
            a = a_ref[:, block]

            def step(t, h, block=block, a=a):
                row = pl.ds(t, 1)
                h = (jnp.exp(dt_s[row, block] * a) * h
                     + dtx_s[row, block] * b_ref[t])
                y_s[row, block] = jnp.sum(h * c_ref[t], axis=0,
                                          keepdims=True)
                return h

            def steps(i, h, step=step):
                # unrolled by hand (the loop primitive unrolls all or
                # nothing): a step's chain through h is one multiply
                # and one add, the exponential and the sum over the
                # states before and after it are long, and only steps
                # laid side by side let the scheduler overlap them
                for j in range(_UNROLL):
                    h = step(i * _UNROLL + j, h)
                return h

            h_ref[:, block] = jax.lax.fori_loop(
                0, chunk // _UNROLL, steps, h_ref[:, block])
        y_ref[:] = jnp.where(t < length, y_s[:] + d_ref[:] * x,
                             0.0).astype(y_ref.dtype)

    @pl.when(start >= length)
    def _dead():
        y_ref[:] = jnp.zeros_like(y_ref)


def _plan(seq: int, channels: int, states: int):
    """(chunk, lanes) if the kernel covers these shapes, else None."""
    if not (_INTERPRET or jax_backend.on_tpu()):
        return None
    chunk = min(_CHUNK, seq)
    lanes = min(_LANES, channels)
    if (seq % chunk or chunk % 8 or (chunk < seq and chunk % 16)
            or channels % lanes or lanes % 128 or states % 8):
        return None
    return chunk, lanes


def _scan_pallas(x, dt, b, c, a, d_skip, h0, length, chunk: int,
                 lanes: int, out_dtype=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    seq, channels = x.shape
    states = a.shape[0]
    f32 = jnp.float32

    def last_live(i, len_ref):
        # a chunk past the length asks for the last live one again:
        # the pipeline sees a repeated index and fetches nothing
        return jnp.minimum(i, jnp.maximum(len_ref[0] - 1, 0) // chunk)

    rows = pl.BlockSpec((chunk, channels),
                        lambda i, len_ref: (last_live(i, len_ref), 0))
    cols = pl.BlockSpec((chunk, states, 1),
                        lambda i, len_ref: (last_live(i, len_ref), 0, 0))
    whole = pl.BlockSpec((states, channels), lambda i, len_ref: (0, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(seq // chunk,),
            in_specs=[rows, rows, cols, cols, whole,
                      pl.BlockSpec((1, channels),
                                   lambda i, len_ref: (0, 0)),
                      whole],
            out_specs=[pl.BlockSpec((chunk, channels),
                                    lambda i, len_ref: (i, 0)),
                       whole],
            scratch_shapes=[pltpu.VMEM((chunk, channels), f32)] * 3),
        out_shape=[jax.ShapeDtypeStruct((seq, channels),
                                        out_dtype or x.dtype),
                   jax.ShapeDtypeStruct((states, channels), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET,
        name=f"selective_scan_{seq}",
    )(jnp.reshape(length, (1,)).astype(jnp.int32), x, dt,
      b.astype(f32)[:, :, None], c.astype(f32)[:, :, None],
      a.astype(f32), d_skip.astype(f32)[None, :], h0.astype(f32))
    return y, h


def selective_scan(x, dt, b, c, a, d_skip, h0, length, out_dtype=None):
    """x: [L, D]; dt: [L, D] (float32 from the model); b, c: [L, N];
    a: [N, D] float32 (negative);
    d_skip: [D]; h0: [N, D] float32; length: int32 scalar, traced or
    not. -> (y [L, D] in ``out_dtype`` (x's by default), h [N, D]
    float32 after position ``length - 1``)."""
    plan = _plan(x.shape[0], x.shape[1], a.shape[0])
    if plan is None:
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"x{list(x.shape)} n[{a.shape[0]}] {x.dtype}")
        return _scan_reference(x, dt, b, c, a, d_skip, h0, length,
                               out_dtype)
    return _scan_pallas(x, dt, b, c, a, d_skip, h0, length, *plan,
                        out_dtype=out_dtype)
