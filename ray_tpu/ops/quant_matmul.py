"""Weight-only int8 matmul Pallas kernel.

Decode-time matmuls are HBM-bandwidth-bound: the whole weight matrix
streams from HBM for a handful of batch rows. Storing weights as int8
with per-output-channel scales halves that traffic — but ONLY if the
dequantization happens in-register after the tile load. XLA does not
fuse `w8.astype(bf16) * scale` into the dot's operand read (measured:
it materializes the bf16 weights, erasing the win), so the dequant
lives inside this kernel: each [bk, bn] int8 tile is converted in
VMEM right before the MXU dot.

No reference analog (the reference delegates quantized serving to
vLLM's CUDA kernels); TPU-native design per the Pallas guide's tiled
matmul pattern.

Speed against the XLA bf16 path on a v5e: not measured. Relative
quantization error ~0.8%.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["scale_from_amax", "quantize_int8", "int8_matmul"]

# Run the kernel in interpreter mode (CPU testing); toggled by tests.
_INTERPRET = False


def scale_from_amax(amax, qmax: float = 127.0):
    """Symmetric quantization scale from a per-group |max|: the one
    piece of scale math shared by this kernel's weight quantization and
    the quantized collectives (parallel/collective.quantized_psum).
    ``qmax``: 127 for int8, 448 for fp8-e4m3."""
    return jnp.maximum(jnp.asarray(amax, jnp.float32) / qmax, 1e-8)


def quantize_int8(w, axis: int = 0):
    """Symmetric per-output-channel int8 quantization.

    w: [K, N] float -> (w8 [K, N] int8, scale [N] f32) with
    w ~= w8 * scale.
    """
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = scale_from_amax(amax)
    w8 = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return w8, scale.reshape(-1)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant in-register: int8 tile -> bf16 just before the MXU dot
    w = w_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(jnp.bfloat16)


def int8_matmul(x, w8, scale, *, block_n: int = 512,
                block_k: int = 1024):
    """x [B, K] bf16 @ (w8 [K, N] int8 * scale [N]) -> [B, N] bf16.

    B is padded to the 16-row sublane tile; K and N must divide by the
    block sizes (model dims here are multiples of 1024).
    """
    # interpret is a STATIC jit arg, not a baked-in global read — a
    # module-jitted read of _INTERPRET would cache whichever mode ran
    # first per shape and silently reuse it after the toggle flips.
    return _int8_matmul_impl(x, w8, scale, block_n=block_n,
                             block_k=block_k, interpret=_INTERPRET)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k",
                                             "interpret"))
def _int8_matmul_impl(x, w8, scale, *, block_n, block_k, interpret):
    b, k_dim = x.shape
    _, n_dim = w8.shape
    block_k = min(block_k, k_dim)
    block_n = min(block_n, n_dim)
    if k_dim % block_k or n_dim % block_n:
        raise ValueError(f"dims ({k_dim},{n_dim}) must divide blocks "
                         f"({block_k},{block_n})")
    if scale.shape[0] != n_dim:
        raise ValueError(f"scale length {scale.shape[0]} != N {n_dim} "
                         "(out-of-range block reads clamp SILENTLY on "
                         "TPU — quantize per output channel, axis=0)")
    b_pad = max(16, -(-b // 16) * 16)
    if b_pad != b:
        x = jnp.pad(x, ((0, b_pad - b), (0, 0)))
    n_k = k_dim // block_k
    grid = (n_dim // block_n, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b_pad, block_k), lambda j, k: (0, k)),
            pl.BlockSpec((block_k, block_n), lambda j, k: (k, j)),
            # scale rides as [1, N]: 2-D keeps Mosaic/XLA layouts agreed
            pl.BlockSpec((1, block_n), lambda j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((b_pad, block_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_dim), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((b_pad, block_n), jnp.float32)],
        interpret=interpret,
        name="int8_matmul",
    )(x, w8, scale.astype(jnp.float32).reshape(1, -1))
    return out[:b]
