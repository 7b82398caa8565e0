"""Fused causal flash attention — streaming Pallas TPU kernels.

Forward: online-softmax accumulation (FlashAttention algorithm) with a
(batch, head, q-block, major) grid. A grid step owns one block of
queries and walks, INSIDE the kernel, the tiles of K and V that its
rows can see: the trip counts come from the block's index
(``_key_tiles``), so a head's grid has no step above the causal
diagonal, the tiles wholly under it run a body with no mask, and only
the tiles the diagonal crosses run the masked one. K and V of a head
stay resident in VMEM up to ``_MAJOR`` rows; a longer sequence is walked
in several "major" grid steps (those past the diagonal clamp their block
index, so their DMA is elided), which keeps VMEM bounded at any sequence
length. The [S, S] score matrix never touches HBM.

Scores are held keys x queries in flash_fwd and flash_dkv, so every
per-query statistic (running max and sum, lse, delta) is a row along
the lanes, reduced and broadcast along sublanes: no cross-lane
reduction, no lane broadcast and no transposed product in either.
flash_dq holds them queries x keys (its dq needs no statistic reduced)
and turns its two rows into columns once a grid step.

Backward: fused dq and dk/dv kernels using the saved logsumexp and the
precomputed delta = rowsum(dO * O), the same walk (flash_dkv owns a
block of keys and walks the query tiles, ``_query_tiles``) — no
score-matrix materialization in the backward either, which is where the
naive VJP loses (a [B, H, S, S] f32 tensor per layer is HBM-bandwidth
death at seq 2048+).

The schedule is decided at trace time from (sq, sk, causal) alone
(``tile_schedule`` is what the tests read). Where a grid axis has one
step its index is the Python 0 and the schedule folds while tracing: a
prefill bucket of one block is a kernel of one masked tile with no
loop, branch or scratch. That is for the serving cells' warm start,
which compiles nothing and lowers a prefill program eleven times (the
reference check's three, the replica's four, and those four once more
for ``engine.stats()``): with loops at every length it was 4 s dearer in
the Jamba cell (PERF.md section 6, PR 56).

All matmuls run with bf16 inputs and f32 accumulation
(preferred_element_type) — the MXU's native mode; softmax statistics
stay f32.

Measured on a v5e (PERF.md section 6, PR 56; ms a call at [4, 2048, 32,
128], device time in a trace): flash_fwd 1.81, flash_dq 1.85, flash_dkv
2.44, against 4.06 / 3.23 / 3.70 for the (q-block, k-block) grid of 256
x 512 tiles they replace, and 1.75 / 2.05 / 2.64 for the best block
sizes of jax's splash attention; a prefill bucket's flash_fwd at [1, S,
32, 128], S = 128 / 256 / 512 / 1024: 0.020 / 0.025 / 0.038 / 0.153
against 0.023 / 0.041 / 0.093 / 0.285.

Reference analog: the reference has no in-tree attention kernels (it
delegates to vLLM/torch, SURVEY.md §5.7); this is the TPU-native
equivalent the blueprint commits to.

Layout: [batch, seq, heads, head_dim] (GQA supported by repeating K/V
heads upstream in the model).

Decode (``decode_attention``): one query position a slot against the
serving cache as it is stored, [layers, slots, rows, kv_heads,
head_dim]. One kernel takes the stacked cache in HBM, the layer index
and the slots' positions as scalar prefetch, and fetches of each slot
only the blocks that hold a visible row; the XLA form it replaced
stays as the reference and the path of uncovered shapes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.accelerators import jax_backend
from ray_tpu.parallel.mesh import mesh_axes, mesh_axis_size

NEG_INF = -1e30

# The kernels' three sizes, each shrunk to what divides the sequence
# (128-multiples keep every product MXU-aligned). _BLOCK: the rows whose
# accumulators one grid step holds (queries in flash_fwd and flash_dq,
# keys in flash_dkv). _TILE: the rows of the walked operand one score
# tile takes, so a tile is _BLOCK x _TILE float32. _MAJOR: the rows of
# the walked operand resident in VMEM at once (K and V, or Q and dO: 1
# MiB of bf16 a pair at 2048, double-buffered).
# The diagonal is met in squares of _BLOCK, so _BLOCK / S of S^2 / 2 is
# computed above it: 25% at 2048. Measured on a v5e at [4, 2048, 32,
# 128] (PR 55's sweep, wall clock a call; PERF.md section 6, PR 56): a
# tile step costs a fixed 0.4-0.5 us beside its scores, so 512 x 512
# beats 256 x 512 (flash_fwd 2.14 against 3.18 ms), 512 x 256 (2.42)
# and 512 x 1024 (2.36), and a tile narrow enough for the vector
# registers, 256 x 128, is the slowest (7.07).
_BLOCK = 512
_TILE = 512
_MAJOR = 2048
# Run kernels in interpreter mode (CPU testing); toggled by tests.
_INTERPRET = False
# Shapes for which flash attention was asked for on a TPU and the
# kernels do not cover them, so the O(S^2) reference ran instead: one
# "q[b,sq,h,d] k[sk]" entry per trace. Callers that must not run the
# slow path (chip_smoke, engine.stats()) read it.
kernel_fallbacks: list = []
# jax.ad_checkpoint names of the two residuals that only the forward
# kernel produces. A caller's jax.checkpoint policy that lists them
# (models/llama.py REMAT_SAVED) keeps both, and the backward pass then
# does not run the forward kernel a second time; with no such policy
# the names do nothing.
SAVED_OUT = "attn_out"   # kernel layout [B, H, S, D], as _bwd wants it
SAVED_LSE = "attn_lse"   # [B, H, S, 1] float32


def _block_size(pref: int, dim: int) -> Optional[int]:
    """Largest 128-multiple block <= pref that tiles `dim` exactly."""
    for cand in (pref, 256, 128):
        if cand <= min(pref, dim) and dim % cand == 0:
            return cand
    return None


def _attention_reference(q, k, v, causal: bool,
                         sm_scale: Optional[float] = None):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / (d ** 0.5) if sm_scale is None else s * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), sk - sq)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# --- the schedule: which tiles a grid step walks, and which of them the
# diagonal crosses. The kernels' loops, the index maps and the tests
# read the same functions (Python ints or traced scalars alike). -------

class _Plan(NamedTuple):
    """Sizes of the three kernels for one (sq, sk). flash_fwd and
    flash_dq give a grid step ``block_q`` queries and walk the keys in
    tiles of ``tile_k``, ``major_k`` of them resident; flash_dkv gives
    it ``block_k`` keys and walks the queries in tiles of ``tile_q``,
    ``major_q`` resident."""
    block_q: int
    block_k: int
    tile_q: int
    tile_k: int
    major_q: int
    major_k: int


def _clip(x, lo, hi):
    if all(isinstance(i, int) for i in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _key_tiles(q0, block_q: int, tile_k: int, offset: int, causal: bool,
               first, end):
    """(split, stop) for the queries [q0, q0 + block_q), query ``r``
    seeing the keys ``c <= r + offset``: of the key tiles [first, end),
    [first, split) lie wholly under the diagonal and need no mask,
    [split, stop) cross it, and the tiles from ``stop`` on are wholly
    masked and never visited."""
    if not causal:
        return end, end
    return (_clip((q0 + offset + 1) // tile_k, first, end),
            _clip((q0 + block_q - 1 + offset) // tile_k + 1, first, end))


def _query_tiles(k0, block_k: int, tile_q: int, offset: int, causal: bool,
                 first, end):
    """(start, split) for the keys [k0, k0 + block_k): of the query
    tiles [first, end), those before ``start`` see none of the keys and
    are never visited, [start, split) cross the diagonal, [split, end)
    see them all."""
    if not causal:
        return first, first
    return (_clip((k0 - offset) // tile_q, first, end),
            _clip((k0 + block_k - 2 - offset) // tile_q + 1, first, end))


def tile_schedule(sq: int, sk: int, causal: bool, plan: _Plan):
    """What the kernels visit for one head, as the tests and PERF.md
    read it: ``{"by_query": tiles, "by_key": tiles, "dead_steps": n}``.
    A tile is (q0, k0, rows, columns, masked); ``by_query`` is the walk
    of flash_fwd and flash_dq, ``by_key`` flash_dkv's; ``dead_steps``
    counts the grid steps of all three that visit no tile (none while
    the walked operand fits one major block)."""
    offset = sk - sq
    by_query, by_key, dead = [], [], 0
    n, per_major = sk // plan.tile_k, plan.major_k // plan.tile_k
    for q0 in range(0, sq, plan.block_q):
        split, stop = _key_tiles(q0, plan.block_q, plan.tile_k, offset,
                                 causal, 0, n)
        by_query += [(q0, j * plan.tile_k, plan.block_q, plan.tile_k,
                      j >= split) for j in range(stop)]
        dead += 2 * (n // per_major - -(-stop // per_major))
    n, per_major = sq // plan.tile_q, plan.major_q // plan.tile_q
    for k0 in range(0, sk, plan.block_k):
        start, split = _query_tiles(k0, plan.block_k, plan.tile_q, offset,
                                    causal, 0, n)
        by_key += [(j * plan.tile_q, k0, plan.tile_q, plan.block_k,
                    j < split) for j in range(start, n)]
        dead += start // per_major
    return {"by_query": by_query, "by_key": by_key, "dead_steps": dead}


def _grid_step(axis: int, steps: int):
    """This grid step's index along ``axis``; the Python 0 where the
    axis has one step, so that the schedule of a short sequence folds
    at trace time: a prefill bucket of one block and one tile is a
    kernel with no loop, no branch and no scratch, which costs a warm
    start less to trace and lower than the loops would."""
    from jax.experimental import pallas as pl
    return pl.program_id(axis) if steps > 1 else 0


def _walk(step, carry, unmasked, masked):
    """Run ``step(j, carry, masked=...)`` over the two half-open tile
    ranges (``masked`` is None where nothing is masked); a range whose
    end is not past its start runs nothing, and one known at trace time
    to hold a single tile runs it without a loop."""
    for bounds, with_mask in ((unmasked, False), (masked, True)):
        if bounds is None:
            continue
        lo, hi = bounds
        body = functools.partial(step, masked=with_mask)
        if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
            carry = body(lo, carry) if hi > lo else carry
        else:
            carry = jax.lax.fori_loop(lo, hi, body, carry)
    return carry


def _across_majors(n_major: int, scratch, init, walk, finish):
    """``finish(walk(... walk(init())))``, one ``walk`` a major grid
    step (the innermost grid axis): the carry crosses the steps in the
    ``scratch`` refs, which a walk of one major block does not have."""
    from jax.experimental import pallas as pl
    if n_major == 1:
        finish(*walk(init()))
        return
    major = pl.program_id(3)

    @pl.when(major == 0)
    def _init():
        for ref, value in zip(scratch, init()):
            ref[...] = value

    for ref, value in zip(scratch, walk(tuple(ref[...] for ref in scratch))):
        ref[...] = value

    @pl.when(major == n_major - 1)
    def _finish():
        finish(*(ref[...] for ref in scratch))


def _query_less_key(shape, query_axis: int):
    """index along the queries' axis less index along the keys', for a
    score tile of ``shape``; the same for every tile, so made once a
    grid step."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, query_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_axis))


def _mask_above_diagonal(s, query_less_key, q0, k0, offset: int):
    """NEG_INF where the tile's key k0 + j lies past what its query
    q0 + i sees (j <= i + offset): a compare and a select an element."""
    return jnp.where(query_less_key >= k0 - q0 - offset, s, NEG_INF)


def _tile_rows(j, first, tile: int):
    """The rows of global tile ``j`` inside the resident major block
    that starts at tile ``first``."""
    from jax.experimental import pallas as pl
    start = (j - first) * tile
    return pl.ds(start if isinstance(start, int)
                 else pl.multiple_of(start, tile), tile)


def _as_column(row):
    """[1, n] -> [n, 1], through a sublane broadcast and one transpose:
    once a grid step, never a tile."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b
_TN = (((0,), (0,)), ((), ()))    # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                causal: bool, sm_scale: float, tile_k: int, offset: int,
                steps: Tuple[int, int]):
    """A grid step owns ``block_q`` queries and walks the key tiles of
    the resident major block. Scores are held keys x queries, [tile_k,
    block_q]: the running max and sum are rows [1, block_q], reduced
    along sublanes and broadcast along them, and the output accumulates
    transposed, [d, block_q], until the block's last tile."""
    block_q, d = q_ref.shape[2:]
    per_major = k_ref.shape[2] // tile_k
    q0 = _grid_step(2, steps[0]) * block_q
    first = _grid_step(3, steps[1]) * per_major

    q = q_ref[0, 0]                                       # [bq, d] bf16
    diagonal = _query_less_key((tile_k, block_q), 1) if causal else None

    def step(j, carry, masked):
        m, l, acc = carry                      # [1, bq] x2, [d, bq] f32
        rows = _tile_rows(j, first, tile_k)
        s = _dot(k_ref[0, 0, rows, :], q, _NT) * sm_scale  # [tile, bq]
        if masked:
            s = _mask_above_diagonal(s, diagonal, q0, j * tile_k, offset)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
        v = v_ref[0, 0, rows, :]                           # [tile, d]
        acc = alpha * acc + _dot(v, p.astype(v.dtype), _TN)
        return m_new, l, acc

    def init():
        return (jnp.full((1, block_q), NEG_INF, jnp.float32),
                jnp.zeros((1, block_q), jnp.float32),
                jnp.zeros((d, block_q), jnp.float32))

    def walk(carry):
        split, stop = _key_tiles(q0, block_q, tile_k, offset, causal,
                                 first, first + per_major)
        return _walk(step, carry, (first, split),
                     (split, stop) if causal else None)

    def finish(m, l, acc):
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked row guard
        o_ref[0, 0] = (acc / l).T.astype(o_ref.dtype)
        lse_ref[0, 0] = _as_column(m + jnp.log(l))         # [bq, 1]

    _across_majors(steps[1], scratch, init, walk, finish)


def _last_live_major(causal: bool, block_q: int, tile_k: int, n_tiles: int,
                     offset: int, per_major: int):
    """Index map of the walked K and V: a major block past the last one
    that holds a live tile clamps to that one, so Mosaic's pipeline sees
    a repeated index and elides the DMA of a dead step."""
    def index(bi, hi, qi, major):
        if n_tiles == per_major:
            return (bi, hi, 0, 0)
        _, stop = _key_tiles(qi * block_q, block_q, tile_k, offset, causal,
                             0, n_tiles)
        return (bi, hi,
                jnp.minimum(major, jnp.maximum(stop - 1, 0) // per_major), 0)
    return index


def _call_params(n_major: int, scratch_shapes):
    """What the three calls share: the innermost grid axis is the walk
    over major blocks, whose carry (a walk of several) lives in the
    scratch."""
    from jax.experimental.pallas import tpu as pltpu
    return dict(
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                        for shape in scratch_shapes] if n_major > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_INTERPRET)


def _flash_forward(q, k, v, causal: bool, plan: _Plan,
                   sm_scale: Optional[float] = None):
    """q,k,v: [B, H, S, D] -> (o [B, H, Sq, D], lse [B, H, Sq, 1] f32).
    ``sm_scale``: the scores' scale where it is not ``D ** -0.5``."""
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    sk = k.shape[2]
    offset = sk - sq
    block_q, tile_k, major_k = plan.block_q, plan.tile_k, plan.major_k
    steps = (sq // block_q, sk // major_k)

    q_index = lambda bi, hi, qi, major: (bi, hi, qi, 0)  # noqa: E731
    kv_index = _last_live_major(causal, block_q, tile_k, sk // tile_k,
                                offset, major_k // tile_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                          tile_k=tile_k, offset=offset, steps=steps),
        grid=(b, h, *steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, major_k, d), kv_index),
            pl.BlockSpec((1, 1, major_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            # trailing dim of 1 satisfies the (8, 128) tile rule via
            # the block-equals-array-dim escape hatch
            pl.BlockSpec((1, 1, block_q, 1), q_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        name="flash_fwd",
        # running max, running sum, output accumulator
        **_call_params(steps[1], [(1, block_q), (1, block_q), (d, block_q)]),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch, causal: bool, sm_scale: float, tile_k: int,
               offset: int, steps: Tuple[int, int]):
    """The forward's walk, scores held queries x keys, [block_q,
    tile_k]: lse and delta arrive as rows [1, block_q] and become
    columns once a grid step."""
    block_q, d = q_ref.shape[2:]
    per_major = k_ref.shape[2] // tile_k
    q0 = _grid_step(2, steps[0]) * block_q
    first = _grid_step(3, steps[1]) * per_major

    q, do = q_ref[0, 0], do_ref[0, 0]                      # [bq, d] bf16
    lse, delta = _as_column(lse_ref[0, 0]), _as_column(delta_ref[0, 0])
    diagonal = _query_less_key((block_q, tile_k), 0) if causal else None

    def step(j, carry, masked):
        rows = _tile_rows(j, first, tile_k)
        k = k_ref[0, 0, rows, :]
        s = _dot(q, k, _NT) * sm_scale                     # [bq, tile]
        if masked:
            s = _mask_above_diagonal(s, diagonal, q0, j * tile_k, offset)
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v_ref[0, 0, rows, :], _NT) - delta)
        return (carry[0] + _dot(ds.astype(k.dtype), k, _NN),)

    def walk(carry):
        split, stop = _key_tiles(q0, block_q, tile_k, offset, causal,
                                 first, first + per_major)
        return _walk(step, carry, (first, split),
                     (split, stop) if causal else None)

    def finish(dq):
        # ds's scale, once a block on the float32 sum
        dq_ref[0, 0] = (dq * sm_scale).astype(dq_ref.dtype)

    _across_majors(steps[1], scratch,
                   lambda: (jnp.zeros((block_q, d), jnp.float32),),
                   walk, finish)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *scratch, causal: bool, sm_scale: float,
                tile_q: int, offset: int, steps: Tuple[int, int]):
    """A grid step owns ``block_k`` keys and walks the query tiles of
    the resident major block, scores held keys x queries, [block_k,
    tile_q]: lse and delta are rows [1, tile_q] broadcast along
    sublanes, and neither product takes a transposed operand."""
    block_k, d = k_ref.shape[2:]
    per_major = q_ref.shape[2] // tile_q
    k0 = _grid_step(2, steps[0]) * block_k
    first = _grid_step(3, steps[1]) * per_major

    k, v = k_ref[0, 0], v_ref[0, 0]                        # [bk, d] bf16
    diagonal = _query_less_key((block_k, tile_q), 1) if causal else None

    def step(j, carry, masked):
        dk, dv = carry                                     # [bk, d] f32
        rows = _tile_rows(j, first, tile_q)
        q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
        s = _dot(k, q, _NT) * sm_scale                     # [bk, tile]
        if masked:
            s = _mask_above_diagonal(s, diagonal, j * tile_q, k0, offset)
        p = jnp.exp(s - lse_ref[0, 0, :, rows])
        dv = dv + _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta_ref[0, 0, :, rows])
        dk = dk + _dot(ds.astype(q.dtype), q, _NN)
        return dk, dv

    def walk(carry):
        end = first + per_major
        start, split = _query_tiles(k0, block_k, tile_q, offset, causal,
                                    first, end)
        return _walk(step, carry, (split, end),
                     (start, split) if causal else None)

    def finish(dk, dv):
        dk_ref[0, 0] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    _across_majors(steps[1], scratch,
                   lambda: (jnp.zeros((block_k, d), jnp.float32),) * 2,
                   walk, finish)


def _flash_backward(q, k, v, o, lse, do, causal: bool, plan: _Plan):
    """All tensors [B, H, S, D] (lse [B, H, S, 1]); returns dq/dk/dv."""
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    offset = sk - sq
    sm_scale = d ** -0.5
    # both kernels take the rows' statistics along the lanes, [B, H, 1,
    # Sq]: a [.., Sq, 1] operand is laid out 128 x padded in HBM
    lse = lse.reshape(b, h, 1, sq)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    block_q, tile_k, major_k = plan.block_q, plan.tile_k, plan.major_k
    steps = (sq // block_q, sk // major_k)
    q_index = lambda bi, hi, qi, major: (bi, hi, qi, 0)  # noqa: E731
    q_row = lambda bi, hi, qi, major: (bi, hi, 0, qi)  # noqa: E731
    kv_index = _last_live_major(causal, block_q, tile_k, sk // tile_k,
                                offset, major_k // tile_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, sm_scale=sm_scale,
                          tile_k=tile_k, offset=offset, steps=steps),
        grid=(b, h, *steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, major_k, d), kv_index),
            pl.BlockSpec((1, 1, major_k, d), kv_index),
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, 1, block_q), q_row),
            pl.BlockSpec((1, 1, 1, block_q), q_row),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        name="flash_dq",
        **_call_params(steps[1], [(block_q, d)]),
    )(q, k, v, do, lse, delta)

    # dk/dv: a grid step holds a block of keys and walks the queries;
    # the major blocks before the first live one clamp forward to it.
    block_k, tile_q, major_q = plan.block_k, plan.tile_q, plan.major_q
    steps = (sk // block_k, sq // major_q)
    per_major = major_q // tile_q

    def first_live_major(ki, major):
        if steps[1] == 1:
            return 0
        start, _ = _query_tiles(ki * block_k, block_k, tile_q, offset,
                                causal, 0, sq // tile_q)
        return jnp.maximum(major, start // per_major)

    def walked(bi, hi, ki, major):
        return (bi, hi, first_live_major(ki, major), 0)

    def walked_row(bi, hi, ki, major):
        return (bi, hi, 0, first_live_major(ki, major))

    k_index = lambda bi, hi, ki, major: (bi, hi, ki, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, sm_scale=sm_scale,
                          tile_q=tile_q, offset=offset, steps=steps),
        grid=(b, h, *steps),
        in_specs=[
            pl.BlockSpec((1, 1, major_q, d), walked),
            pl.BlockSpec((1, 1, block_k, d), k_index),
            pl.BlockSpec((1, 1, block_k, d), k_index),
            pl.BlockSpec((1, 1, major_q, d), walked),
            pl.BlockSpec((1, 1, 1, major_q), walked_row),
            pl.BlockSpec((1, 1, 1, major_q), walked_row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), k_index),
            pl.BlockSpec((1, 1, block_k, d), k_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        name="flash_dkv",
        **_call_params(steps[1], [(block_k, d), (block_k, d)]),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------

def _major_size(dim: int, tile: int) -> int:
    """The most rows <= _MAJOR, in whole tiles, that divide ``dim``."""
    return max(m for m in range(tile, min(dim, _MAJOR) + 1, tile)
               if dim % m == 0)


def _kernel_plan(q, k) -> Optional[_Plan]:
    """The kernels' sizes if they cover these shapes, else None."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if not (_INTERPRET or jax_backend.on_tpu()):
        return None
    if d % 128 or sq % 128 or sk % 128:
        return None
    tile_q, tile_k = _block_size(_TILE, sq), _block_size(_TILE, sk)
    return _Plan(block_q=_block_size(_BLOCK, sq),
                 block_k=_block_size(_BLOCK, sk),
                 tile_q=tile_q, tile_k=tile_k,
                 major_q=_major_size(sq, tile_q),
                 major_k=_major_size(sk, tile_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, causal: bool):
    plan = _kernel_plan(q, k)
    if plan is None:
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"q{list(q.shape)} k[{k.shape[1]}] {q.dtype}")
        return _attention_reference(q, k, v, causal)
    # Kernel layout is [B, H, S, D] so the tiled (second-to-last, last)
    # dims are (seq, head_dim) — the MXU-friendly orientation. XLA fuses
    # the transposes into the surrounding projections.
    out, _ = _flash_forward(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal, plan)
    return out.transpose(0, 2, 1, 3)


def _fwd(q, k, v, causal):
    plan = _kernel_plan(q, k)
    if plan is None:
        out = checkpoint_name(_flash(q, k, v, causal), SAVED_OUT)
        return out, (q, k, v, None, None)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _flash_forward(qt, kt, vt, causal, plan)
    out = checkpoint_name(out, SAVED_OUT)
    lse = checkpoint_name(lse, SAVED_LSE)
    return out.transpose(0, 2, 1, 3), (q, k, v, out, lse)


def _bwd(causal, res, g):
    q, k, v, out, lse = res
    plan = _kernel_plan(q, k)
    if plan is None or out is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _attention_reference(q_, k_, v_, causal),
            q, k, v)
        return vjp(g)
    # `out` was saved in kernel layout [B, H, S, D] by _fwd.
    dq, dk, dv = _flash_backward(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), out, lse,
        g.transpose(0, 2, 1, 3), causal, plan)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


_flash.defvjp(_fwd, _bwd)

# the lanes of a tile: the head size the kernels are written for
_LANES = 128


def _narrow(d: int) -> bool:
    """Whether heads of ``d`` reach the kernels through 128 lanes: heads
    of 64, half a tile's lanes (narrower ones stay uncovered: padding
    wastes three quarters of the matrix unit and more)."""
    return 2 * d == _LANES and (_INTERPRET or jax_backend.on_tpu())


def _pad_lanes(x, lanes: int = _LANES):
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, lanes - x.shape[-1]),))


def _flash_padded(q, k, v, causal: bool, sm_scale: Optional[float] = None):
    """Heads that are not whole tiles of 128 lanes, or whose values are
    narrower than their keys (64: LFM2's; keys of 192 over values of
    128: latent attention's expanded form), FORWARD ONLY (the serving
    path's prefill): q, k and v zero-padded to the same whole tiles,
    which adds nothing to a score nor to a kept output lane, and the
    forward kernel told the scale (the true key width's ``** -0.5``
    unless the caller has its own). None where the kernel does not
    cover the padded shapes."""
    d, dv = q.shape[-1], v.shape[-1]
    lanes = -(-max(d, dv) // _LANES) * _LANES
    q, k, v = (_pad_lanes(x, lanes) for x in (q, k, v))
    plan = _kernel_plan(q, k)
    if plan is None:
        return None
    out, _ = _flash_forward(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                            causal, plan,
                            sm_scale=d ** -0.5 if sm_scale is None
                            else sm_scale)
    return out.transpose(0, 2, 1, 3)[..., :dv]


def flash_attention(q, k, v, causal: bool = True, mesh=None,
                    sm_scale: Optional[float] = None):
    """Fused causal attention: [B, S, H, D] x3 -> [B, S, H, D].

    K/V head count must equal Q head count (expand GQA groups first).
    Heads of 64 on one device go through ``_flash_padded`` (forward
    only), and so does a caller whose values are narrower than its keys
    (v [B, S, H, DV] -> [B, S, H, DV]) or whose scores are not scaled by
    ``D ** -0.5`` (``sm_scale``); where the kernel does not cover those
    the plain form computes them, on a TPU with an entry in
    ``kernel_fallbacks``.
    With a ``mesh`` of more than one device the op runs per shard under
    ``jax.shard_map`` (a Mosaic kernel cannot be partitioned by GSPMD):
    batch over (data, fsdp), heads over model, sequence and head_dim
    whole. A sequence-sharded mesh needs ring/ulysses attention."""
    own = sm_scale is not None or v.shape[-1] != q.shape[-1]
    if mesh is None or mesh.size == 1:
        if own or _narrow(q.shape[-1]):
            out = _flash_padded(q, k, v, causal, sm_scale)
            if out is not None:
                return out
        if not own:
            return _flash(q, k, v, causal)
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"q{list(q.shape)} k[{k.shape[1]}] v[{v.shape[-1]}] "
                f"{q.dtype}")
        return _attention_reference(q, k, v, causal, sm_scale)
    if own:
        raise ValueError("flash_attention with a scale of the caller's or "
                         "values narrower than the keys runs on one device")
    batch, n_batch = mesh_axes(mesh, "data", "fsdp")
    heads, n_heads = mesh_axes(mesh, "model")
    spec = P(batch, None, heads, None)
    if (mesh_axis_size(mesh, "seq") > 1 or q.shape[0] % n_batch
            or q.shape[2] % n_heads):
        raise ValueError(
            f"flash_attention under a mesh shards q/k/v as {spec} over "
            f"{dict(mesh.shape)}; shape {q.shape} does not divide, or "
            "the sequence is sharded (use attention=\"ring\" or "
            "\"ulysses\")")
    return jax.shard_map(
        lambda q_, k_, v_: _flash(q_, k_, v_, causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Decode attention: one query position a slot, over the cache as stored
# ---------------------------------------------------------------------------

def _decode_attention_reference(q, cache_k, cache_v, layer, pos, dtype,
                                sm_scale: Optional[float] = None):
    """The plain XLA form of ``decode_attention``: the layer sliced out
    of the stacked cache, every one of its S rows scored and the rows
    above ``pos`` masked. What the kernel is tested against, and the
    path of shapes and devices it does not cover."""
    k = jax.lax.dynamic_index_in_dim(cache_k, layer, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache_v, layer, keepdims=False)
    visible = jnp.arange(k.shape[1])[None, :] <= pos[:, None]    # [B, S]
    scores = jnp.einsum("bgrd,bsgd->bgrs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5 if sm_scale is None
                       else sm_scale)
    scores = jnp.where(visible[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bgrs,bsgd->bgrd", weights, v)


def decode_block_rows(s: int, kvh: int, hd: int) -> Optional[int]:
    """Positions one block of the decode kernel holds for a cache of
    ``s`` rows, ``kvh`` KV heads of ``hd``: a slot at position ``p``
    has rows ``[0, (p // block + 1) * block)`` read and no other. None
    where the kernel does not cover the shape or the backend, and the
    reference reads all ``s`` rows. About 256 KiB of bf16 a block for 8
    KV heads (1024 cache rows of 128 lanes); never under 128 positions,
    so one KV head gets 512. ``kvh`` and ``hd`` are the cache's own
    last two axes: a cache of packed rows (``cache_row_shape``) is
    asked about as it is stored."""
    if not (_INTERPRET or jax_backend.on_tpu()):
        return None
    block = min(s, max(128, 512 // kvh))
    if hd % 128 or s % block or (block * kvh) % 128:
        return None
    return block


def _decode_kernel(layer_ref, pos_ref, q_ref, at_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, seen_ref, *, block: int, kvh: int,
                   sm_scale: float):
    """One grid step a slot. The slot's live blocks (those that hold a
    row <= pos) come from the stacked cache in HBM by DMA, two buffers
    deep, the next slot's first block started under this slot's last;
    ``seen_ref`` counts the blocks so far, whose parity is the buffer.
    A cache row is (position, KV head), heads fastest: all the query
    heads meet all of a block's rows in one product, and ``at_ref``
    ([heads, rows] int32) holds a row's position for the query heads
    of its KV head and a number past any position for the others."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    rows = block * kvh
    pos = pos_ref[b]
    n_live = pos // block + 1

    def copies(slot, j, buf):
        start = pl.multiple_of(j * rows, rows)
        return [pltpu.make_async_copy(
            hbm.at[layer, slot, pl.ds(start, rows)], vmem.at[buf],
            sems.at[i, buf])
            for i, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf)))]

    def start(slot, j, buf):
        for copy in copies(slot, j, buf):
            copy.start()

    @pl.when(b == 0)
    def _first():
        seen_ref[0] = 0
        start(0, 0, 0)

    seen = seen_ref[0]
    q = q_ref[0]                                          # [H, HD]
    at = at_ref[...]                                      # [H, rows]
    heads, hd = q.shape

    def body(j, carry):
        m, l, acc = carry
        buf = (seen + j) % 2

        @pl.when(j + 1 < n_live)
        def _next_block():
            start(b, j + 1, 1 - buf)

        @pl.when(jnp.logical_and(j + 1 == n_live, b + 1 < n_slots))
        def _next_slot():
            start(b + 1, 0, 1 - buf)

        for copy in copies(b, j, buf):
            copy.wait()
        k = k_buf[buf]                                    # [rows, HD]
        v = v_buf[buf]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(at <= pos - j * block, s, NEG_INF)  # [H, rows]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_live, body,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, hd), jnp.float32)))
    seen_ref[0] = seen + n_live
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _decode_pallas(q, cache_k, cache_v, layer, pos, dtype, block: int,
                   sm_scale: Optional[float] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kvh, n_rep, hd = q.shape
    sm_scale = hd ** -0.5 if sm_scale is None else sm_scale
    n_layers, _, s = cache_k.shape[:3]
    n_heads = kvh * n_rep
    # whole sublane tiles of query heads; a padded head is a zero query
    # of the last KV head, computed and dropped
    heads = -(-n_heads // 16) * 16
    rows = block * kvh
    qh = jnp.pad(q.reshape(b, n_heads, hd),
                 ((0, 0), (0, heads - n_heads), (0, 0)))
    head_kv = np.minimum(np.arange(heads) // n_rep, kvh - 1)[:, None]
    row = np.arange(rows)[None, :]
    at = np.where(row % kvh == head_kv, row // kvh,
                  np.iinfo(np.int32).max).astype(np.int32)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, kvh=kvh,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, hd), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((heads, rows), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, hd), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, hd), cache_k.dtype),
                pltpu.VMEM((2, rows, hd), cache_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, hd), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET,
        name="decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32),
      qh, at,
      # rows of (position, KV head): the stored bytes in their order
      cache_k.reshape(n_layers, b, s * kvh, hd),
      cache_v.reshape(n_layers, b, s * kvh, hd))
    return out[:, :n_heads].reshape(b, kvh, n_rep, hd)


def cache_row_shape(kvh: int, hd: int) -> Tuple[int, int]:
    """The last two axes of a serving cache whose layers have ``kvh`` KV
    heads of ``hd``: ``(kvh, hd)``, but for heads of 64, half a tile's
    128 lanes, ``(kvh / 2, 128)``: two neighbouring KV heads side by
    side in one row of 128 lanes (LFM2: 8 heads of 64 are 4 rows). A TPU
    pads a minor axis of 64 to 128 lanes, so ``[.., 8, 64]`` would take
    twice its bytes and could not be seen as 128 wide without a copy;
    the packed rows are what ``decode_attention`` reads in place."""
    if 2 * hd == _LANES and kvh % 2 == 0:
        return kvh // 2, _LANES
    return kvh, hd


def _decode_packed(q, cache_k, cache_v, layer, pos, dtype):
    """``decode_attention`` over a cache of packed rows
    (``cache_row_shape``: [L, B, S, G, 128], ``f = 128 / HD`` KV heads a
    row), through the kernel written for 128 lanes: it sees G KV heads
    of 128, each with the queries of its f heads; a query is zero in the
    lanes that are not its own head's, so its scores are its own head's,
    and of its output the lanes of its own head are kept. None where
    the kernel does not cover the shapes."""
    b, kvh, n_rep, hd = q.shape
    rows, lanes = cache_k.shape[-2:]
    f = lanes // hd
    block = decode_block_rows(cache_k.shape[2], rows, lanes)
    if block is None or not (q.dtype == cache_k.dtype == cache_v.dtype):
        return None
    q = q.reshape(b, rows, f, n_rep, hd)
    q = jnp.stack([jnp.pad(q[:, :, j], ((0, 0),) * 3
                           + ((j * hd, lanes - (j + 1) * hd),))
                   for j in range(f)], axis=2)
    out = _decode_pallas(q.reshape(b, rows, f * n_rep, lanes), cache_k,
                         cache_v, layer, pos, dtype, block,
                         sm_scale=hd ** -0.5)
    out = out.reshape(b, rows, f, n_rep, f, hd)
    return jnp.stack([out[:, :, j, :, j] for j in range(f)],
                     axis=2).reshape(b, kvh, n_rep, hd)


def decode_attention(q, cache_k, cache_v, layer, pos, dtype,
                     sm_scale: Optional[float] = None):
    """One query position for every slot against the cache as it is
    stored. q: [B, KVH, n_rep, HD], the query heads grouped by the KV
    head they share (n_rep == 1: groups of one); cache_k, cache_v: the
    STACKED caches [L, B, S, KVH, HD] with this step's row already
    written; ``layer`` (an int, traced or not) the one to attend; pos:
    [B] int32, slot ``b`` sees rows ``t <= pos[b]``. -> [B, KVH, n_rep,
    HD] in ``dtype``. No K or V is expanded to the query's heads and no
    layer is sliced out of the stack. The scores are scaled by ``HD **
    -0.5`` unless the caller has a scale of its own (``sm_scale``), and
    ``cache_k`` and ``cache_v`` may be ONE array: a cache of latent rows
    (models/mla.py) whose row is the key and, in its first lanes, the
    value; the kernel then reads each row twice, once as either.

    On a TPU (and in the tests' interpret mode), where ``HD % 128 ==
    0`` (or the cache's rows are packed: ``cache_row_shape``), S divides
    into ``decode_block_rows`` and q has the caches' dtype, one Pallas
    kernel reads layer ``layer`` in place, and of each
    slot only the blocks that hold a visible row: the decode programs
    of every model family share it. It needs every ``pos`` in ``[0, S -
    1]``, the program's caches donated (or XLA copies them for every
    caller, kernel or not) and the program on one device (a Mosaic
    kernel cannot be partitioned, as ``rms_norm`` beside it cannot).
    Everything else takes ``_decode_attention_reference``, on a TPU
    with an entry in ``kernel_fallbacks``."""
    _, kvh, _, hd = q.shape
    if cache_k.shape[-1] != hd:
        # packed rows (cache_row_shape), whose scale is their head's
        if sm_scale is not None:
            raise ValueError("a cache of packed rows takes no sm_scale")
        out = _decode_packed(q, cache_k, cache_v, layer, pos, dtype)
        if out is not None:
            return out
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"decode q{list(q.shape)} {q.dtype} "
                f"cache{list(cache_k.shape)} {cache_k.dtype}")
        unpacked = cache_k.shape[:3] + (kvh, hd)
        return _decode_attention_reference(
            q, cache_k.reshape(unpacked), cache_v.reshape(unpacked), layer,
            pos, dtype)
    block = decode_block_rows(cache_k.shape[2], kvh, hd)
    if block is None or not (q.dtype == cache_k.dtype == cache_v.dtype):
        if jax_backend.on_tpu():
            kernel_fallbacks.append(
                f"decode q{list(q.shape)} {q.dtype} "
                f"cache{list(cache_k.shape)} {cache_k.dtype}")
        return _decode_attention_reference(q, cache_k, cache_v, layer, pos,
                                           dtype, sm_scale)
    return _decode_pallas(q, cache_k, cache_v, layer, pos, dtype, block,
                          sm_scale)
