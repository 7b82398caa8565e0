"""Mixture-of-Experts with expert parallelism over the mesh.

The reference has no in-tree MoE (SURVEY §2.3 X4: TP/PP/EP appear only
as config passthrough to vLLM/DeepSpeed); here expert parallelism is a
first-class library component, TPU-first: expert weights are sharded on
the ``expert`` mesh axis and dispatch/combine are einsums over one-hot
routing masks — under jit, GSPMD partitions the token and expert
dimensions and inserts the all-to-all collectives over ICI (the
Mesh-TensorFlow / Switch-Transformer formulation, which is how MoE is
idiomatically expressed for XLA rather than hand-written sends).

Components:
- ``top_k_gating``: softmax router → top-k experts per token with
  renormalized weights and a Switch-style load-balancing aux loss.
- ``moe_dispatch``/``moe_combine``: capacity-bounded one-hot routing.
- ``moe_ffn``: the full layer — gate → dispatch → per-expert SwiGLU
  FFN (batched over the expert axis) → combine. The trainer's form.
- ``held_experts_ffn``: the serving form, one device of an expert-
  parallel group: every row routed over ALL the experts, the experts
  this device holds computed, nothing dropped (no capacity, no factor).
  ``gated_ffn`` is the shared expert beside it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.matmul import SPLIT_ROWS, few_rows, mm

# jax.named_scope names of held_experts_ffn's two parts
SCOPE_ROUTER = "moe.router"
SCOPE_EXPERTS = "moe.experts"
# what held_experts_ffn counts of its live rows, in this order
EXPERT_COUNTS = ("picks_held", "picks_absent", "picks_computed",
                 "slots_hit", "slots_idle", "pairs_walked")
# and, after those, where the router adds a selection bias: the live
# rows' picks that are not among the k largest scores alone, and the rest
BIAS_COUNTS = ("picks_bias_moved", "picks_bias_kept")


@dataclass(frozen=True)
class Scoring:
    """How a router's logits become scores and gates; a family's.

    ``softmax``: scores a softmax over all experts, the k largest
    picked, gates those scores over their sum. ``sigmoid``: scores a
    sigmoid of each logit, the k largest of ``score + bias`` picked (the
    bias, a buffer of the layer and no weight, is for the choice
    alone), gates the picked SCORES over ``their sum + eps``, times
    ``scale``."""
    kind: str = "softmax"
    eps: float = 0.0
    scale: float = 1.0


SOFTMAX = Scoring()
# places of the sorted (row, pick) order that one trip of
# held_experts_ffn's many-rows walk serves
WALK_CHUNK = 256


def _gates(logits: jax.Array, k: int, scoring: Scoring = SOFTMAX,
           bias: Optional[jax.Array] = None
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router logits [T, E] -> (gates [T, E], topk_idx [T, k], scores
    [T, E]) under ``scoring``; gates are zero outside the picks.
    ``bias`` [E]: a sigmoid router's selection bias."""
    if scoring.kind == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, topk_idx = jax.lax.top_k(
            probs if bias is None else probs + bias, k)          # [T, k]
        topk_vals = jnp.take_along_axis(probs, topk_idx, axis=-1)
        topk_vals = topk_vals / (topk_vals.sum(axis=-1, keepdims=True)
                                 + scoring.eps) * scoring.scale
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topk_vals, topk_idx = jax.lax.top_k(probs, k)            # [T, k]
        topk_vals = topk_vals / jnp.maximum(
            topk_vals.sum(axis=-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(logits.shape[0])[:, None], topk_idx].set(topk_vals)
    return gates, topk_idx, probs


def top_k_gating(x: jax.Array, router: jax.Array, k: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Route tokens: returns (gates [T,E], topk_idx [T,k], aux_loss).

    ``x``: [T, D] tokens; ``router``: [D, E]. Gates are zero outside
    the top-k and renormalized over the selected experts. The aux loss
    is the Switch load-balancing term E * sum_e(frac_tokens_e *
    mean_prob_e), minimized at uniform routing.
    """
    gates, topk_idx, probs = _gates(
        jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32)), k)
    num_experts = router.shape[-1]
    # load-balancing aux (Switch Transformer eq. 4-6)
    top1 = jax.nn.one_hot(topk_idx[:, 0], num_experts)
    frac_tokens = top1.mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)
    return gates, topk_idx, aux


def moe_dispatch(gates: jax.Array, topk_idx: jax.Array,
                 num_experts: int, capacity: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Build routing masks: (dispatch [T,E,C] one-hot, combine [T,E,C]).

    Each expert accepts at most ``capacity`` tokens; overflow tokens are
    dropped for that expert (their residual path still carries them —
    standard capacity-factor semantics).
    """
    num_tokens, k = topk_idx.shape
    dispatch = jnp.zeros((num_tokens, num_experts, capacity),
                         dtype=gates.dtype)
    # fill k slots sequentially so earlier (higher-gate) choices claim
    # capacity first
    occupancy = jnp.zeros((num_experts,), dtype=jnp.int32)
    for slot in range(k):
        expert = topk_idx[:, slot]                           # [T]
        onehot = jax.nn.one_hot(expert, num_experts,
                                dtype=jnp.int32)             # [T, E]
        # position of each token within its chosen expert's buffer
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1
                         + occupancy[None, :])               # [T, E]
        pos = jnp.take_along_axis(
            pos_in_expert, expert[:, None], axis=1)[:, 0]    # [T]
        keep = pos < capacity
        pos_clamped = jnp.clip(pos, 0, capacity - 1)
        pos_onehot = jax.nn.one_hot(pos_clamped, capacity,
                                    dtype=gates.dtype)       # [T, C]
        slot_dispatch = (onehot.astype(gates.dtype)[:, :, None]
                         * pos_onehot[:, None, :]
                         * keep.astype(gates.dtype)[:, None, None])
        dispatch = dispatch + slot_dispatch
        occupancy = occupancy + onehot.sum(axis=0)
    combine = dispatch * gates[:, :, None]
    return dispatch, combine


def moe_ffn(x: jax.Array, router: jax.Array, w1: jax.Array,
            w3: jax.Array, w2: jax.Array, *, top_k: int = 2,
            capacity_factor: float = 2.0
            ) -> Tuple[jax.Array, jax.Array]:
    """Full MoE SwiGLU layer.

    ``x``: [B, S, D]; ``router``: [D, E]; expert weights stacked on a
    leading expert axis — ``w1``/``w3``: [E, D, H], ``w2``: [E, H, D].
    Shard the expert axis (PartitionSpec("expert", ...)) and GSPMD
    turns the dispatch/combine einsums into all-to-alls over ICI.
    Returns (y [B, S, D], aux_loss).
    """
    b, s, d = x.shape
    num_experts = router.shape[-1]
    tokens = x.reshape(b * s, d)
    gates, topk_idx, aux = top_k_gating(tokens, router, top_k)
    capacity = max(1, int(capacity_factor * top_k * (b * s) / num_experts))
    dispatch, combine = moe_dispatch(gates, topk_idx, num_experts,
                                     capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)
    # [T,E,C] x [T,D] -> [E,C,D]: the all-to-all (tokens -> experts)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)
    # per-expert SwiGLU, batched over the (sharded) expert axis
    gate = jax.nn.silu(jnp.einsum("ecd,edh->ech", expert_in, w1))
    up = jnp.einsum("ecd,edh->ech", expert_in, w3)
    expert_out = jnp.einsum("ech,ehd->ecd", gate * up, w2)
    # [T,E,C] x [E,C,D] -> [T,D]: the all-to-all back (experts -> tokens)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.reshape(b, s, d), aux


def gated_ffn(x: jax.Array, w_in: jax.Array, w_out: jax.Array) -> jax.Array:
    """A gated feed-forward whose two input projections are one matrix:
    ``[a | b] = x w_in`` ([D, 2I], the gated half first), ``(silu(a) *
    b) w_out``. x [T, D] -> [T, D] float32. A shared expert is this,
    beside the routed ones and counted once."""
    ab = mm(x, w_in)
    half = ab.shape[-1] // 2
    return mm(jax.nn.silu(ab[..., :half]) * ab[..., half:], w_out)


def held_experts_ffn(x: jax.Array, router: jax.Array, w_in: jax.Array,
                     w_out: jax.Array, first: int, *, layer, top_k: int,
                     live: Optional[jax.Array] = None,
                     scoring: Scoring = SOFTMAX,
                     bias: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The part of a routed layer that the experts THIS device holds
    add: one rank of an expert-parallel group.

    ``x`` [T, D] rows; ``router`` [D, E] over all E experts; ``w_in``
    [L, H, D, 2I] and ``w_out`` [L, H, I, D], the stacked weights of the
    held experts ``first .. first + H - 1`` (each a ``gated_ffn``) of L
    layers, of which this is layer ``layer`` (an index, traced or not):
    a caller that walks a stack of layers hands the stack over and no
    layer's experts are sliced out into a copy (453 + 226 MB a layer at
    granite-4.0-h-small's widths, 2.07 ms of every prefill's layer on a
    v5e: PERF.md, PR 45). Every row picks its ``top_k`` of all E by the
    router (its product at the highest precision: a TPU rounds float32
    inputs to bf16 by default, and a rounded logit changes a pick),
    scored and gated as the family's ``scoring`` says (``bias`` [E]
    float32: a sigmoid router's selection bias); a pick of a held expert adds
    ``gate * expert(row)``, a pick of an absent one adds nothing HERE
    (the rank that holds it adds it; the ranks' parts sum to the whole
    layer). Nothing is dropped: there is no capacity and no factor,
    every pick of a held expert is computed whatever the router's skew.

    By the static row count, as ``ops.matmul.few_rows`` decides:
    - few rows (a decode step): every held expert on every row, weighted
      by the gate, which is zero where the row did not pick it. Exact,
      and bound by reading the experts' weights either way.
    - many rows (a prefill): the (row, pick) pairs sorted by expert,
      the held experts' first, and grouped matmuls (``ragged_dot``)
      over their rows, the groups all L x H experts of the stack with
      every other layer's of size zero. Where this rank holds HALF the
      experts or more (a static fact, ``2 H >= E``) all ``T x top_k``
      pairs go through at once: gathered out, multiplied, gathered back
      into (row, pick) order and masked; the absent experts' pairs lie
      past the last group. Where it holds fewer, that order is WALKED
      in chunks of ``WALK_CHUNK`` places (all pairs where they are
      fewer) as far as the held experts' pairs reach: ``ceil(n /
      chunk)`` trips of a loop whose count is read on the device, ``n``
      the counted pairs, whatever the router's skew (every pair, if
      every pair is a held expert's). A trip gathers its rows of ``x``,
      multiplies over the chunk's part of each group, gates, and adds
      the result to its rows of ``y``; a place past ``n`` in the last
      chunk adds nothing. The absent experts' pairs are never gathered
      or multiplied and no array over all the pairs but the sort's is
      made: the cost follows the count, a thirty-second of the pairs
      where a rank holds 12 of 384 experts. Only the order in which a
      row's picks are summed differs between the two. The rule and
      the chunk are by measurement (one layer's call on a v5e, PERF.md
      PR 70; one-shot -> walk): 12 of 384 held, top-8, 7168 wide, 4096
      / 2048 / 1024 rows 14.0 -> 8.0, 8.3 -> 4.1, 5.9 -> 3.7 ms (8.3,
      6.5, 5.0 at a chunk of 512); 36 of 72, top-10, 4096 wide, 1024 /
      512 / 256 rows 5.3 -> 4.8, 3.5 -> 3.5, 2.7 -> 2.8 (chunk 512);
      32 of 32, top-4, 2048 wide 3.3 -> 4.1, 2.8 -> 2.9, 2.5 -> 2.6:
      the add back to ``y`` is a scatter-add, which XLA prices by
      shape (0.4-3.3 us a row of a 117 MB ``y``) and which the walk
      wins back only where most pairs are absent.

    -> (y [T, D] float32, EXPERT_COUNTS [6] uint32 of the ``live`` rows
    ([T] bool, default every row): their picks that fell on held
    experts, those that fell on absent ones, the held picks whose
    product was computed (every one in the first regime; in the second
    those whose place in the sorted order lay inside the groups), how
    many held experts got a live row and how many got none, and the
    places of the sorted order that were gathered and multiplied, of
    EVERY row and padding's too (none in the first regime; all ``T x
    top_k`` at once or the walk's whole chunks in the second); with a
    ``bias`` two more, BIAS_COUNTS:
    the live rows' picks that are not among their ``top_k`` largest
    scores alone, and those that are)."""
    rows, dtype = x.shape[0], w_in.dtype
    n_experts, (held, inner, _) = router.shape[-1], w_out.shape[-3:]
    live = jnp.ones((rows,), bool) if live is None else live.astype(bool)
    with jax.named_scope(SCOPE_ROUTER):
        gates, idx, scores = _gates(
            jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST), top_k, scoring,
            bias)
        # expert e as (e - first) mod E, which is < H exactly for the
        # held; a row picks an expert at most once
        rel = (idx - first) % n_experts                       # [T, k]
        picked = jnp.any(rel[:, :, None] == jnp.arange(held), axis=1)
        picked_live = picked & live[:, None]
        n_held = jnp.sum(picked_live)
        n_hit = jnp.sum(jnp.any(picked_live, axis=0))
        by_bias = []
        if bias is not None:
            unbiased = jax.lax.top_k(scores, top_k)[1]
            kept = jnp.any(idx[:, :, None] == unbiased[:, None, :], axis=2)
            n_kept = jnp.sum(kept & live[:, None])
            by_bias = [jnp.sum(live) * top_k - n_kept, n_kept]

    def counts(n_computed, n_walked=0):
        return jnp.stack([n_held, jnp.sum(live) * top_k - n_held,
                          n_computed, n_hit, held - n_hit, n_walked,
                          *by_bias]).astype(jnp.uint32)

    with jax.named_scope(SCOPE_EXPERTS):
        if rows <= SPLIT_ROWS:
            # a slice XLA reads through, inside the product
            w_in, w_out = (jax.lax.dynamic_index_in_dim(
                w, layer, keepdims=False) for w in (w_in, w_out))
            both, merge = few_rows(x, dtype)
            ab = merge(jnp.einsum("td,edf->etf", both, w_in,
                                  preferred_element_type=jnp.float32))
            mine = gates[:, first:first + held]   # 0 where not picked
            act = (jax.nn.silu(ab[..., :inner]) * ab[..., inner:]
                   * mine.T[:, :, None])                      # [H, T, I]
            both, merge = few_rows(act, dtype)
            return merge(jnp.einsum(
                "eti,eid->td", both, w_out,
                preferred_element_type=jnp.float32)), counts(n_held)
        # the pairs by expert, the held experts' first
        pairs = rows * top_k
        order = jnp.argsort(rel.reshape(-1))
        n_layers = w_in.shape[0]
        sizes = jnp.sum(picked, axis=0, dtype=jnp.int32)      # [H]
        xd = x.astype(dtype)
        w_in, w_out = (w.reshape((n_layers * held,) + w.shape[2:])
                       for w in (w_in, w_out))
        gate = jnp.take_along_axis(gates, idx, axis=1)        # [T, k]
        mine = rel < held

        def experts(xs, sizes):
            """The held experts on rows ``xs`` that lie expert by
            expert, ``sizes`` [H] of them each: the groups are all L x H
            experts of the stack, every other layer's of size zero."""
            group_sizes = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((n_layers * held,), jnp.int32), sizes,
                layer * held, 0)
            ab = jax.lax.ragged_dot(xs, w_in, group_sizes,
                                    preferred_element_type=jnp.float32)
            act = jax.nn.silu(ab[:, :inner]) * ab[:, inner:]
            return jax.lax.ragged_dot(act.astype(dtype), w_out, group_sizes,
                                      preferred_element_type=jnp.float32)

        if 2 * held >= n_experts:
            # all pairs at once; back in (row, pick) order by a gather,
            # then the gates; a pair of an absent expert lies past the
            # last group, where the product wrote nothing
            place = jnp.argsort(order)
            out = experts(xd[order // top_k], sizes)[place]
            computed = (place < jnp.sum(sizes)).reshape(rows, top_k)
            return jnp.sum(jnp.where(
                mine[:, :, None],
                out.reshape(rows, top_k, -1) * gate[:, :, None], 0.0),
                axis=1), counts(
                    jnp.sum(mine & computed & live[:, None]), pairs)
        chunk = min(WALK_CHUNK, pairs)
        order = jnp.pad(order, (0, -pairs % chunk))
        ends = jnp.cumsum(sizes)
        n = ends[-1]
        gate = gate.reshape(-1)
        counted = (mine & live[:, None]).reshape(-1)          # [T k]

        def walk(c, carry):
            y, n_computed = carry
            lo = c * chunk
            pair = jax.lax.dynamic_slice_in_dim(order, lo, chunk)
            row = pair // top_k
            # the chunk's part of each group: the groups' running ends
            # clipped to the chunk
            out = experts(xd[row], jnp.clip(ends, lo, lo + chunk)
                          - jnp.clip(ends - sizes, lo, lo + chunk))
            # a place at or past n lies past the chunk's last group,
            # where the product wrote nothing: it adds nothing
            inside = lo + jnp.arange(chunk) < n
            out = jnp.where(inside[:, None], out * gate[pair][:, None], 0.0)
            return y.at[row].add(out), n_computed + jnp.sum(
                inside & counted[pair])

        n_chunks = (n + chunk - 1) // chunk
        y, n_computed = jax.lax.fori_loop(
            0, n_chunks, walk,
            (jnp.zeros((rows, x.shape[1]), jnp.float32), jnp.int32(0)))
        return y, counts(n_computed, n_chunks * chunk)
