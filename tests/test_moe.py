"""Mixture-of-Experts: gating, dispatch/combine, Llama-MoE, EP sharding.

No reference analog (the reference outsources MoE to vLLM/DeepSpeed);
tested against the dense FFN as ground truth and on the virtual
8-device mesh per SURVEY §7.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import (
    LlamaConfig,
    llama_init,
    llama_loss,
    llama_sharding_rules,
)
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.moe import (BIAS_COUNTS, EXPERT_COUNTS, SOFTMAX,
                                  WALK_CHUNK, Scoring, _gates, gated_ffn,
                                  held_experts_ffn, moe_dispatch, moe_ffn,
                                  top_k_gating)
from ray_tpu.parallel.sharding import shard_pytree


def _dense_swiglu(x, w1, w3, w2):
    gate = jax.nn.silu(x @ w1)
    return (gate * (x @ w3)) @ w2


def test_top_k_gating_shapes_and_normalization():
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    gates, idx, aux = top_k_gating(x, router, k=2)
    assert gates.shape == (32, 4) and idx.shape == (32, 2)
    # gates nonzero only at the top-k experts, summing to 1 per token
    np.testing.assert_allclose(np.asarray(gates.sum(axis=-1)), 1.0,
                               rtol=1e-5)
    assert float(aux) >= 1.0 - 1e-5  # minimized at 1.0 (uniform)


def test_dispatch_respects_capacity():
    # 8 tokens all routed to expert 0, capacity 4: half are dropped
    gates = jnp.zeros((8, 2)).at[:, 0].set(1.0)
    idx = jnp.zeros((8, 1), dtype=jnp.int32)
    dispatch, combine = moe_dispatch(gates, idx, num_experts=2, capacity=4)
    assert float(dispatch.sum()) == 4.0  # only 4 slots filled
    # each filled slot occupied exactly once
    assert float(dispatch[:, 0, :].sum(axis=0).max()) == 1.0


def test_moe_equals_dense_with_identical_experts():
    """top-1 routing into experts with IDENTICAL weights must reproduce
    the dense FFN exactly (ample capacity)."""
    rng = jax.random.PRNGKey(0)
    d, h, e = 16, 32, 4
    x = jax.random.normal(rng, (2, 8, d))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (d, h)) * 0.1
    w3 = jax.random.normal(jax.random.PRNGKey(2), (d, h)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(3), (h, d)) * 0.1
    router = jax.random.normal(jax.random.PRNGKey(4), (d, e))
    ew1 = jnp.stack([w1] * e)
    ew3 = jnp.stack([w3] * e)
    ew2 = jnp.stack([w2] * e)
    y, aux = moe_ffn(x, router, ew1, ew3, ew2, top_k=1,
                     capacity_factor=float(e))  # capacity = all tokens
    expected = _dense_swiglu(x, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_moe_top2_mixes_experts():
    """With distinct experts and top-2 routing, the output is the
    gate-weighted mixture of the two selected experts' outputs."""
    d, h, e = 8, 16, 2
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, d))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (e, d, h)) * 0.1
    w3 = jax.random.normal(jax.random.PRNGKey(2), (e, d, h)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(3), (e, h, d)) * 0.1
    router = jax.random.normal(jax.random.PRNGKey(4), (d, e))
    y, _ = moe_ffn(x, router, w1, w3, w2, top_k=2, capacity_factor=4.0)
    tokens = x.reshape(-1, d)
    gates, _, _ = top_k_gating(tokens, router, 2)
    expected = sum(
        gates[:, i][:, None] * _dense_swiglu(tokens, w1[i], w3[i], w2[i])
        for i in range(e))
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)),
                               np.asarray(expected), rtol=2e-4, atol=2e-5)


def test_llama_moe_trains(cpu_mesh8):
    cfg = LlamaConfig.tiny(moe_experts=4, moe_top_k=2)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    assert params["layers"]["w1"].shape == (
        cfg.n_layers, 4, cfg.dim, cfg.hidden_dim)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                 cfg.vocab_size)

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(
            lambda q: llama_loss(q, tokens, targets, cfg))(p)
        return loss, grads

    loss, grads = step(params)
    assert bool(jnp.isfinite(loss))
    # gradients flow into expert weights and the router
    assert float(jnp.abs(grads["layers"]["w1"]).sum()) > 0
    assert float(jnp.abs(grads["layers"]["router"]).sum()) > 0


def test_llama_moe_expert_parallel_matches_replicated(cpu_mesh8):
    """EP over the virtual mesh: loss with expert-sharded weights equals
    the unsharded loss (GSPMD inserts the all-to-alls; math unchanged)."""
    devices = cpu_mesh8
    mesh = make_mesh(MeshSpec(data=2, expert=4), devices)
    cfg = LlamaConfig.tiny(moe_experts=4, moe_top_k=2)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                 cfg.vocab_size)
    baseline = float(llama_loss(params, tokens, targets, cfg))

    sharded = shard_pytree(params, mesh, llama_sharding_rules("ep"))
    tok_sharded = jax.device_put(tokens, NamedSharding(mesh, P("data")))
    tgt_sharded = jax.device_put(targets, NamedSharding(mesh, P("data")))

    @jax.jit
    def loss_fn(p, t, y):
        return llama_loss(p, t, y, cfg)

    ep_loss = float(loss_fn(sharded, tok_sharded, tgt_sharded))
    assert ep_loss == pytest.approx(baseline, rel=1e-4)


# --- the serving form: the experts one device of an expert-parallel
# group holds (held_experts_ffn), float32, 8 experts, top-3 -----------

_D, _E, _I, _K = 16, 8, 12, 3


def _expert_layer(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        router=jax.random.normal(keys[0], (_D, _E)),
        w_in=jax.random.normal(keys[1], (_E, _D, 2 * _I)) * 0.3,
        w_out=jax.random.normal(keys[2], (_E, _I, _D)) * 0.3,
        w_in_s=jax.random.normal(keys[3], (_D, 4 * _I)) * 0.3,
        w_out_s=jax.random.normal(keys[4], (2 * _I, _D)) * 0.3)


def _uncut_layer(x, layer, router=None):
    """The whole layer, plainly: every expert on every row, weighted by
    the gate (zero where not picked), and the shared expert."""
    router = layer["router"] if router is None else router
    gates, _, _ = top_k_gating(x, router, _K)
    routed = sum(
        gates[:, e:e + 1] * gated_ffn(x, layer["w_in"][e],
                                      layer["w_out"][e])
        for e in range(_E))
    return routed + gated_ffn(x, layer["w_in_s"], layer["w_out_s"])


def _share(x, layer, first, count, router=None, live=None):
    router = layer["router"] if router is None else router
    # a stack of one layer, and its index
    return jax.jit(lambda x: held_experts_ffn(
        x, router, layer["w_in"][None, first:first + count],
        layer["w_out"][None, first:first + count], first, layer=0,
        top_k=_K, live=live))(x)


@pytest.mark.parametrize("rows", [8, 100])
def test_the_shares_parts_and_the_shared_expert_add_up_to_the_layer(rows):
    """Two ranks of an expert-parallel pair (experts 0-3 and 4-7) route
    over all 8 and compute their own: their parts, plus the shared
    expert counted once, are the uncut layer; few rows (a decode step's
    form) and many (a prefill's grouped matmul)."""
    layer = _expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, _D))
    with jax.default_matmul_precision("highest"):
        want = _uncut_layer(x, layer)
        low, counts_low = _share(x, layer, 0, 4)
        high, counts_high = _share(x, layer, 4, 4)
        shared = gated_ffn(x, layer["w_in_s"], layer["w_out_s"])
    np.testing.assert_allclose(np.asarray(low + high + shared),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(low).max()) > 0 and float(jnp.abs(high).max()) > 0
    # every pick is on one rank or the other: held here, absent there
    assert int(counts_low[0]) == int(counts_high[1])
    assert int(counts_low[0] + counts_low[1]) == rows * _K
    # and every held pick was computed
    assert int(counts_low[2]) == int(counts_low[0])
    assert int(counts_high[2]) == int(counts_high[0])


@pytest.mark.parametrize("rows", [8, 100])
def test_a_skewed_router_loses_no_row(rows):
    """A router under which EVERY row picks expert 5 first (and 6, 7
    after it): a capacity of 2.0 x k x T / E rows an expert would drop
    five rows in eight; this layer has none and computes them all."""
    layer = _expert_layer(1)
    # x's first feature is 1, so the router's first row is a bias
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, _D)) * 0.01
    x = x.at[:, 0].set(1.0)
    router = jnp.zeros((_D, _E)).at[0].set(
        jnp.array([0., 0., 0., 0., 0., 9., 6., 3.]))
    with jax.default_matmul_precision("highest"):
        _, idx, _ = top_k_gating(x, router, _K)
        want = _uncut_layer(x, layer, router) \
            - gated_ffn(x, layer["w_in_s"], layer["w_out_s"])
        got, counts = _share(x, layer, 4, 4, router)
        none, counts_none = _share(x, layer, 0, 4, router)
    assert np.asarray(idx).tolist() == [[5, 6, 7]] * rows
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # every row's output is there: none was dropped
    assert float(jnp.abs(got).sum(axis=1).min()) > 0
    # EXPERT_COUNTS: held, absent, computed, hit, idle, walked (the
    # many-rows form's places: a rank that holds half the experts
    # takes all 300 pairs at once, whatever fell on it)
    walked = rows * _K if rows > 32 else 0
    assert counts.tolist() == [rows * _K, 0, rows * _K, 3, 1, walked]
    assert counts_none.tolist() == [0, rows * _K, 0, 0, 4, walked]
    assert float(jnp.abs(none).max()) == 0.0


def test_the_two_regimes_agree_on_the_same_input():
    """The few-rows form (every held expert on every row) and the many-
    rows form (sorted pairs, grouped matmul) are one function: a batch
    of 100 rows through the grouped matmul equals its rows through the
    few-rows form 20 at a time; ``live`` masks the counts and nothing
    else."""
    layer = _expert_layer(2)
    x = jax.random.normal(jax.random.PRNGKey(11), (100, _D))
    live = jnp.arange(100) < 60
    with jax.default_matmul_precision("highest"):
        many, counts = _share(x, layer, 0, 4, live=live)
        few = [_share(x[i:i + 20], layer, 0, 4, live=live[i:i + 20])
               for i in range(0, 100, 20)]
    np.testing.assert_allclose(
        np.asarray(many), np.concatenate([np.asarray(y) for y, _ in few]),
        rtol=1e-4, atol=1e-5)
    assert int(counts[0]) == sum(int(c[0]) for _, c in few)
    assert int(counts[0] + counts[1]) == 60 * _K
    assert int(counts[2]) == int(counts[0])
    # the many-rows form serves padding's places too; the few-rows
    # form has none
    assert int(counts[5]) == 100 * _K > int(counts[0])
    assert all(int(c[5]) == 0 for _, c in few)


@pytest.mark.parametrize("rows", [8, 100])
def test_a_stack_of_layers_and_an_index_is_the_layer_itself(rows):
    """The experts' weights as whole stacks [L, H, ...] of three layers
    and a traced index (a caller that walks a stack of layers, so that
    no layer's experts are sliced out into a copy) give what a stack of
    that layer alone gives, in both regimes."""
    layers = [_expert_layer(seed) for seed in (3, 4, 5)]
    stack_in = jnp.stack([l["w_in"][:4] for l in layers])
    stack_out = jnp.stack([l["w_out"][:4] for l in layers])
    x = jax.random.normal(jax.random.PRNGKey(13), (rows, _D))
    stacked = jax.jit(lambda x, i: held_experts_ffn(
        x, layers[1]["router"], stack_in, stack_out, 0, top_k=_K, layer=i))
    with jax.default_matmul_precision("highest"):
        want, want_counts = _share(x, layers[1], 0, 4)
        got, counts = stacked(x, jnp.int32(1))
        other, _ = stacked(x, jnp.int32(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert counts.tolist() == want_counts.tolist()
    assert float(jnp.abs(other - got).max()) > 1e-3


# --- the router's scoring: a family's (Scoring) ---------------------------

def _softmax_gates_as_before(logits, k):
    """``_gates`` as it stood while softmax was the module's constant."""
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    vals = vals / jnp.maximum(vals.sum(axis=-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(logits.shape[0])[:, None], idx].set(vals)
    return gates, idx, probs


def _sigmoid_gates_in_numpy(logits, k, bias, eps, scale):
    p = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    idx = np.argsort(-(p + bias), axis=-1, kind="stable")[:, :k]
    picked = np.take_along_axis(p, idx, -1)
    vals = picked / (picked.sum(-1, keepdims=True) + eps) * scale
    gates = np.zeros_like(p)
    np.put_along_axis(gates, idx, vals, -1)
    return gates, idx, p


@pytest.mark.parametrize("scoring,k,bias_std", [
    (SOFTMAX, 2, None), (SOFTMAX, 3, None), (Scoring(), 10, None),
    (Scoring("sigmoid", eps=1e-6, scale=1.0), 4, 0.3),
    (Scoring("sigmoid", eps=1e-6, scale=2.5), 4, 0.3),
    (Scoring("sigmoid", eps=1e-2, scale=1.0), 2, None)])
def test_gates_under_both_scorings(scoring, k, bias_std):
    """Softmax, the default: to the bit what it was. Sigmoid against a
    few lines of numpy: the picks by ``p + b``, the gates from ``p``
    alone over ``their sum + eps``, times the scale."""
    logits = jax.random.normal(jax.random.PRNGKey(k), (40, 32)) * 2.0
    bias = (None if bias_std is None else np.asarray(
        jax.random.normal(jax.random.PRNGKey(9), (32,))) * bias_std)
    gates, idx, scores = jax.jit(
        lambda l: _gates(l, k, scoring, None if bias is None
                         else jnp.asarray(bias)))(logits)
    if scoring.kind == "softmax":
        want = jax.jit(lambda l: _softmax_gates_as_before(l, k))(logits)
        for got, old in zip((gates, idx, scores), want):
            assert np.array_equal(np.asarray(got), np.asarray(old))
        return
    want_gates, want_idx, want_p = _sigmoid_gates_in_numpy(
        logits, k, 0.0 if bias is None else bias, scoring.eps,
        scoring.scale)
    assert np.asarray(idx).tolist() == want_idx.tolist()
    np.testing.assert_allclose(np.asarray(scores), want_p, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5,
                               atol=1e-7)
    sums = np.asarray(gates).sum(-1)
    picked = np.take_along_axis(want_p, want_idx, -1).sum(-1)
    np.testing.assert_allclose(sums, scoring.scale * picked
                               / (picked + scoring.eps), rtol=1e-5)
    if bias is not None:
        # the bias moved some picks and left the gates to the scores
        plain = np.argsort(-want_p, axis=-1, kind="stable")[:, :k]
        assert (np.sort(plain) != np.sort(want_idx)).any()


@pytest.mark.parametrize("rows", [8, 100])
def test_two_halves_of_32_experts_add_up_under_the_sigmoid_router(rows):
    """``held_experts_ffn`` with experts 0-15 and 16-31 held, routed by
    a sigmoid with a selection bias over all 32, top-4, scale 1.5:
    their parts sum to the whole layer, in both regimes; the counts
    grow by the two that say what the bias did."""
    e, k = 32, 4
    scoring = Scoring("sigmoid", eps=1e-6, scale=1.5)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    router = jax.random.normal(keys[0], (_D, e))
    w_in = jax.random.normal(keys[1], (e, _D, 2 * _I)) * 0.3
    w_out = jax.random.normal(keys[2], (e, _I, _D)) * 0.3
    bias = jax.random.normal(keys[3], (e,)) * 0.2
    x = jax.random.normal(keys[4], (rows, _D))
    live = jnp.arange(rows) < rows - 2

    def half(first):
        return jax.jit(lambda x: held_experts_ffn(
            x, router, w_in[None, first:first + 16],
            w_out[None, first:first + 16], first, layer=0, top_k=k,
            live=live, scoring=scoring, bias=bias))(x)

    with jax.default_matmul_precision("highest"):
        gates, idx, _ = _sigmoid_gates_in_numpy(
            x @ router, k, np.asarray(bias), 1e-6, 1.5)
        want = sum(gates[:, j:j + 1] * np.asarray(
            gated_ffn(x, w_in[j], w_out[j])) for j in range(e))
        (low, counts_low), (high, counts_high) = half(0), half(16)
    np.testing.assert_allclose(np.asarray(low + high), want, rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(low).max()) > 0 and float(jnp.abs(high).max()) > 0
    assert len(counts_low) == len(EXPERT_COUNTS) + len(BIAS_COUNTS) == 8
    assert EXPERT_COUNTS[-1] == "pairs_walked"
    n_live = rows - 2
    assert int(counts_low[0]) == int(counts_high[1])
    assert int(counts_low[0] + counts_low[1]) == n_live * k
    assert int(counts_low[2]) == int(counts_low[0])
    # what the bias moved, of the live rows' picks: both ranks alike
    p = 1.0 / (1.0 + np.exp(-np.asarray(x @ router, np.float64)))
    plain = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    moved = sum(j not in plain[t] for t in range(n_live) for j in idx[t])
    assert counts_low[6:].tolist() == counts_high[6:].tolist() \
        == [moved, n_live * k - moved]
    assert 0 < moved < n_live * k


@pytest.mark.parametrize("rows", [8, 100])
@pytest.mark.parametrize("router_kind", ["even", "skewed"])
def test_a_rank_of_32_computes_its_two_experts_part_and_drops_none(
        rows, router_kind):
    """``held_experts_ffn`` at a held share of 1/32: experts 10 and 11 of
    64, routed by a sigmoid with a selection bias over all 64, top-4,
    scale 2.827. Under an even router most rows pick no held expert and
    most (row, pick) pairs are an absent expert's; under a skewed one
    EVERY row picks both held experts first. Either way, in both regimes,
    the rank's part is the dense sum over its two experts and every held
    pick is computed: no capacity, nothing dropped."""
    e, k, first, held = 64, 4, 10, 2
    scoring = Scoring("sigmoid", eps=1e-20, scale=2.827)
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    w_in = jax.random.normal(keys[1], (e, _D, 2 * _I)) * 0.3
    w_out = jax.random.normal(keys[2], (e, _I, _D)) * 0.3
    bias = jax.random.normal(keys[3], (e,)) * 0.05
    x = jax.random.normal(keys[4], (rows, _D))
    router = jax.random.normal(keys[0], (_D, e))
    if router_kind == "skewed":
        # x's first feature is 1, so the router's first row is a bias
        x = (x * 0.01).at[:, 0].set(1.0)
        router = jnp.zeros((_D, e)).at[0, first].set(6.0) \
            .at[0, first + 1].set(4.0)
    with jax.default_matmul_precision("highest"):
        gates, idx, _ = _sigmoid_gates_in_numpy(
            x @ router, k, np.asarray(bias), 1e-20, 2.827)
        want = sum(gates[:, j:j + 1] * np.asarray(
            gated_ffn(x, w_in[j], w_out[j]))
            for j in range(first, first + held))
        got, counts = jax.jit(lambda x: held_experts_ffn(
            x, router, w_in[None, first:first + held],
            w_out[None, first:first + held], first, layer=0, top_k=k,
            scoring=scoring, bias=bias))(x)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    n_held = int(((idx >= first) & (idx < first + held)).sum())
    n_held_c, n_absent, computed, hit, idle, walked = counts[:6].tolist()
    assert (n_held_c, n_absent) == (n_held, rows * k - n_held)
    assert computed == n_held and hit + idle == held      # dropped: 0
    # whole chunks as far as the held pairs reach, none where none is
    chunk = min(WALK_CHUNK, rows * k)
    assert walked == (-(-n_held // chunk) * chunk if rows > 32 else 0)
    if router_kind == "skewed":
        assert n_held == 2 * rows and (hit, idle) == (2, 0)
        assert float(jnp.abs(got).sum(axis=1).min()) > 0
    else:
        # 8 rows x 4 picks x 2 / 64: one held pick expected, none drawn
        assert (n_held > 0 or rows == 8) and n_held < rows * k / 8


@pytest.mark.parametrize("both,one,chunks", [
    (0, 0, 0), (WALK_CHUNK // 4, 0, 1), (WALK_CHUNK // 4, 1, 2),
    (WALK_CHUNK // 4 + WALK_CHUNK // 8, 0, 2),
    (700, 0, -(-2800 // WALK_CHUNK))],
    ids=["none", "a_chunk", "a_chunk_and_one", "two_chunks", "every_pair"])
def test_the_walk_follows_the_counted_pairs(both, one, chunks):
    """The many-rows form walks the sorted (row, pick) order in chunks
    of WALK_CHUNK places as far as the held experts' pairs reach: 700
    rows, top-4 of 64, experts 10-13 held; ``both`` rows pick all four
    held experts, ``one`` rows pick expert 10 alone, the rest pick none.
    No held pair: no trip, and zeros. Exactly a chunk: one trip. One
    more: two, the second all but empty. Every pair (2800 of 2800, a
    router no capacity would survive): every chunk. Each time the
    rank's part is the dense sum over its experts and every held pick
    of a live row is computed."""
    rows, e, k, first, held = 700, 64, 4, 10, 4
    scoring = Scoring("sigmoid", eps=1e-20, scale=2.827)
    keys = jax.random.split(jax.random.PRNGKey(70), 4)
    w_in = jax.random.normal(keys[0], (e, _D, 2 * _I)) * 0.3
    w_out = jax.random.normal(keys[1], (e, _I, _D)) * 0.3
    bias = jax.random.normal(keys[2], (e,)) * 0.05
    # feature 0 lifts the four held experts (+1) or sinks them (-1);
    # feature 1 lifts expert 10 back over the rest
    kind = jnp.arange(rows)
    x = (jax.random.normal(keys[3], (rows, _D)) * 0.01) \
        .at[:, 0].set(jnp.where(kind < both, 1.0, -1.0)) \
        .at[:, 1].set(jnp.where((kind >= both) & (kind < both + one),
                                1.0, 0.0))
    router = jnp.zeros((_D, e)) \
        .at[0, first:first + held].set(jnp.array([6., 5., 4., 3.])) \
        .at[1, first].set(12.0)
    live = jnp.arange(rows) % 7 != 3
    with jax.default_matmul_precision("highest"):
        gates, idx, _ = _sigmoid_gates_in_numpy(
            x @ router, k, np.asarray(bias), 1e-20, 2.827)
        want = sum(gates[:, j:j + 1] * np.asarray(
            gated_ffn(x, w_in[j], w_out[j]))
            for j in range(first, first + held))
        got, counts = jax.jit(lambda x: held_experts_ffn(
            x, router, w_in[None, first:first + held],
            w_out[None, first:first + held], first, layer=0, top_k=k,
            live=live, scoring=scoring, bias=bias))(x)
    mine = (idx >= first) & (idx < first + held)
    n = int(mine.sum())
    assert n == held * both + one
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    if both == rows:
        assert float(jnp.abs(got).sum(axis=1).min()) > 0
    n_live = int((mine & np.asarray(live)[:, None]).sum())
    picks_held, _, computed, _, _, walked = counts[:6].tolist()
    assert picks_held == computed == n_live              # dropped: 0
    assert walked == chunks * WALK_CHUNK == -(-n // WALK_CHUNK) * WALK_CHUNK
    if not n:
        assert float(jnp.abs(got).max()) == 0.0
