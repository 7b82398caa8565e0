"""Pipeline parallelism over the mesh's `pipe` axis.

The reference treats pipeline parallelism as configuration passed to
external engines (SURVEY.md §2.3 X4 — vLLM TP/PP passthrough,
vllm_models.py:214); here it is an in-tree transform. The schedule is
the classic GPipe rotation expressed inside `shard_map` (MPMD-over-SPMD,
cf. arXiv 2412.14374): device i holds stage i's parameters; microbatches
enter at stage 0, activations hop to the ICI neighbor each tick, and
outputs drain from the last stage. Total ticks = n_micro + n_stages - 1,
bubble fraction (n_stages-1)/(n_micro+n_stages-1).

Memory layout (round-2 rework): the microbatch stack is SHARDED over
the pipe axis in a strided layout (device d holds microbatches d, d+S,
d+2S, ...), not replicated. Each round of S ticks all-gathers exactly
one microbatch per device for injection, and each drained output is
ppermuted from the last stage straight to its home device — so
per-device memory is O(batch/S) for inputs + outputs plus an O(S)
round buffer, and per-tick interconnect traffic stays at ~2 microbatch
activations (one ring hop, one gather/scatter share).

``remat=True`` wraps the stage function in jax.checkpoint so training
recomputes within-stage activations in the backward pass — the
activation-memory motivation behind 1F1B, in scan-compatible form.
(A literal 1F1B interleaving of forward/backward ticks requires a
hand-written custom_vjp schedule; under jax.grad the scan's backward
already runs ticks in reverse, and what remains live per tick is the
carried activation, which remat keeps to one microbatch per stage.)

Contract: f(stage_params, x) -> y with x and y of identical shape (the
transformer-block contract). Put shape-changing embed/unembed layers
outside the pipelined region.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def _pipeline_local(params, x_local, *, fn, axis_name: str,
                    n_stages: int):
    """Per-device pipeline loop.

    params: stage-local pytree (leading stage axis of size 1);
    x_local: [R, 1, mb, ...] — this device's strided share of the
    microbatch stack (R = n_micro / n_stages rounds).
    """
    S = n_stages
    stage = lax.axis_index(axis_name)
    params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), params)
    x_local = jnp.squeeze(x_local, axis=1)          # [R, mb, ...]
    R = x_local.shape[0]
    ring = [(i, (i + 1) % S) for i in range(S)]

    def tick(state, out_local, inject, s, slot, valid):
        """One pipeline tick at static in-round offset ``s``: stage 0
        consumes ``inject``; the drained microbatch (if ``valid``) is
        ppermuted from the last stage to its home device and written at
        ``slot``."""
        inp = jnp.where(stage == 0, inject, state)
        out = fn(params, inp)
        home = (s + 1) % S  # drained microbatch m has m % S == home
        piece = lax.ppermute(out, axis_name, [(S - 1, home)])
        write = jnp.logical_and(valid, stage == home)
        out_local2 = jnp.where(
            write,
            lax.dynamic_update_index_in_dim(out_local, piece, slot, axis=0),
            out_local)
        state = lax.ppermute(out, axis_name, ring)
        return state, out_local2

    def round_body(carry, r):
        state, out_local = carry
        # one microbatch per device for this round: [S, mb, ...]
        round_buf = lax.all_gather(
            lax.dynamic_index_in_dim(x_local, r, 0, keepdims=False),
            axis_name, axis=0, tiled=False)
        for s in range(S):  # S is static: unrolled, ppermute perms static
            slot = r - 1 + (s + 1) // S
            valid = jnp.logical_or(r > 0, s == S - 1)
            state, out_local = tick(state, out_local, round_buf[s],
                                    s, slot, valid)
        return (state, out_local), None

    state0 = jnp.zeros_like(x_local[0])
    out0 = jnp.zeros_like(x_local)
    (state, out_local), _ = lax.scan(
        round_body, (state0, out0), jnp.arange(R))
    # drain: S-1 ticks with dummy injection; outputs land in slot R-1
    for k in range(S - 1):
        state, out_local = tick(state, out_local, state0, k,
                                R - 1, jnp.bool_(True))
    return out_local[:, None]                        # [R, 1, mb, ...]


def pipeline(fn: Callable[[Any, jax.Array], jax.Array], stage_params: Any,
             x: jax.Array, mesh: Mesh, *, num_microbatches: int,
             axis_name: str = "pipe", remat: bool = False) -> jax.Array:
    """Run ``x`` through all pipeline stages.

    stage_params: pytree whose leaves have a leading ``n_stages`` axis
    (sharded over ``pipe``); x: [batch, ...] — split internally into
    ``num_microbatches`` (must be a multiple of the pipe size so the
    strided input sharding is even). ``remat``: checkpoint the stage fn
    for training (backward recomputes within-stage activations).
    """
    n_stages = mesh.shape[axis_name]
    if x.shape[0] % num_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by num_microbatches "
            f"{num_microbatches}")
    if num_microbatches % n_stages:
        raise ValueError(
            f"num_microbatches {num_microbatches} not divisible by the "
            f"pipe size {n_stages} (required for the strided input "
            "sharding)")
    mb = x.shape[0] // num_microbatches
    rounds = num_microbatches // n_stages
    x_mb = x.reshape(rounds, n_stages, mb, *x.shape[1:])
    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    body = jax.checkpoint(fn) if remat else fn
    local = functools.partial(_pipeline_local, fn=body,
                              axis_name=axis_name, n_stages=n_stages)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )(stage_params, x_mb)
    return out.reshape(x.shape[0], *out.shape[3:])
