"""One decode step of a Mamba-2 mixer's recurrence, for the slots that
hold a request, in place in the stacked cache.

For one slot and head, with ``h`` of [P, N] (N states for each of the
head's P rows), in float32::

    h = dA * h + (dt x)[:, None] * B[None, :]
    y = sum_n(h * C[None, :]) + D x

``ssm`` is the serving cache's whole stack [M, B, H, P, N]; the step
moves layer ``layer`` of the slots whose ``live`` is set and leaves
every other byte of the stack as it was: the other layers, and a parked
slot's state, which is neither read nor written (its ``y`` is 0).

On a TPU this is one Pallas kernel, ``ssd_update``: the stack is
aliased from input to output, so inside a donated program it is
updated where it lies; a grid step owns one slot's block of heads
([heads, P, N] float32, the states on the lanes), reads it once, forms
the new ``h``, writes it, and adds ``h C`` up along the states on the
matrix unit (against ones, in float32). The pass costs the live slots'
bytes once in and once out, where the ``jax.numpy`` form below reads every
slot's state twice and writes it once. The grid walks the live slots first; its
steps past them ask for the last live block again, so the pipeline
fetches nothing for them and writes nothing back. What varies along a
head's rows (``dt x``, ``D x``, ``y``) crosses the kernel's boundary
as [B, P, H], rows on the sublanes as the state has them and all of a
slot's heads on the lanes, so that a head's column broadcasts along
the states with no transpose in the kernel; ``dA``, one number a slot
and head, is read from scalar memory.

Off the TPU, and for shapes the kernel does not cover (H no multiple
of 128 or of the block, P none of 8, N none of 128), the ``jax.numpy``
form runs, which is also what the kernel is tested against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.accelerators import jax_backend

# Run the kernel in interpreter mode (CPU testing); toggled by tests.
_INTERPRET = False
# heads of one slot a grid step owns: 32 of [64, 128] float32 are 1 MiB
_HEADS = 32
# heads a trip of the kernel's loop over a block unrolls
_GROUP = 16


def _update_reference(ssm, layer, live, da, dtx, b, c, dx):
    """The step as written above, in ``jax.numpy``: the layer sliced
    out of the stack, every slot's state moved and read a second time
    for ``y``, a parked slot's put back as it was."""
    h0 = jax.lax.dynamic_index_in_dim(ssm, layer, keepdims=False)
    h = da[:, :, None, None] * h0 + dtx[..., None] * b[:, None, None, :]
    y = jnp.sum(h * c[:, None, None, :], axis=-1) + dx
    h = jnp.where(live[:, None, None, None], h, h0)
    y = jnp.where(live[:, None, None], y, 0.0)
    return jax.lax.dynamic_update_index_in_dim(ssm, h, layer, 0), y


def head_block(heads: int, rows: int, states: int) -> Optional[int]:
    """Heads a grid step of the kernel owns for a state of [heads, rows,
    states] a slot, or None where the kernel does not cover the shape
    or the backend and the ``jax.numpy`` form runs."""
    if not (_INTERPRET or jax_backend.on_tpu()):
        return None
    if (heads % 128 or heads % _HEADS or _HEADS % _GROUP or rows % 8
            or states % 128):
        return None
    return _HEADS


def _update_kernel(layer_ref, order_ref, n_ref, da_ref, dtx_ref, dx_ref,
                   b_ref, c_ref, h_ref, o_ref, y_ref, *, block: int):
    """One grid step a slot and block of heads: step ``i`` of the first
    axis has slot ``order_ref[i]``, live while ``i < n_ref[0]``.
    ``dtx_ref``, ``dx_ref`` and ``y_ref`` hold the slot's [P, H]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, j = pl.program_id(0), pl.program_id(1)
    heads = dtx_ref.shape[2]
    first = j * block

    @pl.when(jnp.logical_and(n_ref[0] == 0, jnp.logical_and(i == 0, j == 0)))
    def _keep():
        # no slot is live: the one block the index maps ask for goes
        # back as it came
        o_ref[...] = h_ref[...]

    @pl.when(i < n_ref[0])
    def _move():
        slot = order_ref[i]
        b_row, c_row = b_ref[0], c_ref[0]                     # [1, N]
        rows, states = h_ref.shape[-2:]
        ones = jnp.ones((states, heads), jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, heads), 1)

        def heads_of(g, carry):
            # the group's heads are at the first lanes of ``dtx``, so
            # that every lane index is static
            dtx, acc = carry
            at = g * _GROUP
            for k in range(_GROUP):
                h = (da_ref[slot, first + at + k] * h_ref[0, 0, at + k]
                     + dtx[:, k:k + 1] * b_row)
                o_ref[0, 0, at + k] = h
                # the matrix unit adds a row's states up, in float32,
                # and leaves the sum in every lane of the row
                y = jnp.dot(h * c_row, ones,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
                acc = jax.lax.select(lane == first + at + k, y, acc)
            return pltpu.roll(dtx, heads - _GROUP, 1), acc

        _, acc = jax.lax.fori_loop(
            0, block // _GROUP, heads_of,
            (pltpu.roll(dtx_ref[0], (heads - first) % heads, 1),
             jnp.zeros((rows, heads), jnp.float32)))
        y_ref[0] = jnp.where(j == 0, dx_ref[0], y_ref[0]) + acc


def _update_pallas(ssm, layer, live, da, dtx, b, c, dx, block: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, slots, heads, rows, states = ssm.shape
    n_blocks = heads // block
    f32 = jnp.float32
    # the live slots first, in their order; the grid's steps past them
    # ask for the last live slot's last block again, so the pipeline
    # fetches nothing for them and writes nothing back
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)

    def slot_of(i, order_ref, n_ref):
        # slot 0 where none is live
        return order_ref[jnp.maximum(jnp.minimum(i, n_ref[0] - 1), 0)]

    def state(i, j, layer_ref, order_ref, n_ref):
        return (layer_ref[0], slot_of(i, order_ref, n_ref),
                jnp.where(i < n_ref[0], j, n_blocks - 1), 0, 0)

    def of_slot(i, j, layer_ref, order_ref, n_ref):
        return (slot_of(i, order_ref, n_ref), 0, 0)

    per_row = pl.BlockSpec((1, rows, heads), of_slot)
    per_state = pl.BlockSpec((1, 1, states), of_slot)
    tile = pl.BlockSpec((1, 1, block, rows, states), state)
    new, y = pl.pallas_call(
        functools.partial(_update_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, n_blocks),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      per_row, per_row, per_state, per_state, tile],
            out_specs=[tile, per_row]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, f32),
                   jax.ShapeDtypeStruct((slots, rows, heads), f32)],
        # operand 8 (after the three prefetched scalars and da, dtx, dx,
        # b, c) is the stack; output 0 is the stack
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_INTERPRET,
        name="ssd_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n_live,
      da.astype(f32), dtx.astype(f32).transpose(0, 2, 1),
      dx.astype(f32).transpose(0, 2, 1), b.astype(f32)[:, None, :],
      c.astype(f32)[:, None, :], ssm)
    # a parked slot's rows of ``y`` were never written
    return new, jnp.where(live[:, None, None], y.transpose(0, 2, 1), 0.0)


def ssd_update(ssm, layer, live, da, dtx, b, c, dx):
    """ssm: the STACKED states [M, B, H, P, N] float32; ``layer`` (an
    int, traced or not) the one to move; live: [B], which slots hold a
    request; da: [B, H] (``exp(dt A)``); dtx, dx: [B, H, P] (``dt x``
    and ``D x``); b, c: [B, N]; all float32. -> (the stack with layer
    ``layer`` of the live slots moved one step, y [B, H, P] float32,
    zero for a parked slot).

    On a TPU (and in the tests' interpret mode), where ``head_block``
    covers the shape, one Pallas kernel moves the live slots' state in
    place; it needs the program's stack donated (or XLA copies it for
    every caller, kernel or not) and the program on one device.
    Everything else takes ``_update_reference``."""
    live = live.astype(bool)
    block = head_block(*ssm.shape[2:])
    if block is None or ssm.dtype != jnp.float32:
        return _update_reference(ssm, layer, live, da, dtx, b, c, dx)
    return _update_pallas(ssm, layer, live, da, dtx, b, c, dx, block)
