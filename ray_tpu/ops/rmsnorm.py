"""Fused RMSNorm.

On TPU the win is fusing the reduction + rescale into one VMEM pass so
the activation is read from HBM once. XLA usually fuses this pattern by
itself; the Pallas kernel exists to guarantee it on the hot path and to
serve as the template for further fused epilogues.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.accelerators import jax_backend
from ray_tpu.parallel.mesh import mesh_axes

# Run the kernel in interpreter mode (CPU testing); toggled by tests.
_INTERPRET = False

# Mosaic's scoped VMEM limit on v5e is 16 MiB. The row block is the
# largest that keeps the pipeline's buffers (input and output blocks,
# each double-buffered) plus the kernel's float32 copy of the block
# under it: at [8192, 4096] bf16 a 512-row block asks for 16.01 MiB and
# does not compile, 256 rows fit (compile-only check, v5e:2x2).
_VMEM_BUDGET = 16 * 1024 * 1024


def _rms_norm_reference(x, weight, eps: float):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (normed * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _block_rows(rows: int, d: int, itemsize: int) -> Optional[int]:
    """Row block for a [rows, d] input, or None when the kernel does
    not cover the shape: d not lane-aligned, fewer than 8 rows, or no
    whole-sublane block that both fits VMEM and tiles `rows`."""
    if d % 128 or rows < 8:
        return None
    cap = _VMEM_BUDGET // (4 * d * itemsize + 4 * d)
    if rows <= cap:
        return rows
    sublane = 8 * max(1, 4 // itemsize)
    block = sublane
    while block * 2 <= cap:
        block *= 2
    while block >= sublane and rows % block:
        block //= 2
    return block if sublane <= block <= cap else None


def _rms_pallas(x, weight, eps: float, block_rows: int):
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=_INTERPRET,
        name="rms_norm",
    )(x2, weight)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm(x, weight, eps: float):
    d = x.shape[-1]
    block_rows = (_block_rows(x.size // d, d, x.dtype.itemsize)
                  if _INTERPRET or jax_backend.on_tpu() else None)
    if block_rows is None:
        return _rms_norm_reference(x, weight, eps)
    return _rms_pallas(x, weight, eps, block_rows)


def _fwd(x, weight, eps):
    return _rms_norm(x, weight, eps), (x, weight)


def _bwd(eps, res, g):
    x, weight = res
    _, vjp = jax.vjp(lambda x_, w_: _rms_norm_reference(x_, w_, eps),
                     x, weight)
    return vjp(g)


_rms_norm.defvjp(_fwd, _bwd)


def rms_norm(x, weight, eps: float = 1e-6, mesh=None):
    """RMSNorm over the last axis: x * rsqrt(mean(x^2)+eps) * weight.

    With a ``mesh`` of more than one device, x is [batch, seq, dim] and
    the op runs per shard under ``jax.shard_map`` (a Mosaic kernel
    cannot be partitioned by GSPMD): batch over (data, fsdp), seq over
    seq, dim whole, since the reduction runs over it."""
    if mesh is None or mesh.size == 1:
        return _rms_norm(x, weight, eps)
    batch, n_batch = mesh_axes(mesh, "data", "fsdp")
    seq, n_seq = mesh_axes(mesh, "seq")
    spec = P(batch, seq, None)
    if x.ndim != 3 or x.shape[0] % n_batch or x.shape[1] % n_seq:
        raise ValueError(
            f"rms_norm under a mesh shards x as {spec} over "
            f"{dict(mesh.shape)}; shape {x.shape} does not divide")
    return jax.shard_map(
        lambda x_, w_: _rms_norm(x_, w_, eps), mesh=mesh,
        in_specs=(spec, P()), out_specs=spec, check_vma=False)(x, weight)
