"""The sampler does only what a step's live slots ask of it, and gives
every slot whose token is read the token the unconditional form gives:
bit for bit, under the same key. CPU, float32, a tiny vocabulary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import (
    ContinuousBatchingEngine, EngineConfig, GenerationRequest)
from ray_tpu.models.llama import LlamaConfig

B, V, MAX_K = 6, 97, 16


def reference(logits, temp, topk, key, bias=None, max_k=MAX_K):
    """The sampler as it was before it branched: everything computed
    for every slot, the arg-max selected last."""
    n_b = logits.shape[0]
    if bias is not None:
        logits = logits + bias
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    keys = jax.random.split(key, n_b)
    full = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    vals, idx = jax.lax.top_k(scaled, max_k)
    mask = (jnp.arange(max_k)[None, :]
            < jnp.clip(topk, 1, max_k)[:, None])
    vals = jnp.where(mask, vals, -jnp.inf)
    choice = jax.vmap(jax.random.categorical)(keys, vals)
    topk_tok = jnp.take_along_axis(
        idx, choice[:, None], axis=1)[:, 0].astype(jnp.int32)
    sampled = jnp.where(topk > 0, topk_tok, full)
    return jnp.where(temp <= 0.0, greedy, sampled)


@pytest.fixture
def branches(monkeypatch):
    """The names of the sampler's expensive operations that RAN (not
    merely were traced): each reports through a host callback from
    inside whichever branch holds it."""
    ran = set()

    def reporting(name, fn):
        def wrapped(*args, **kwargs):
            jax.debug.callback(lambda: ran.add(name))
            return fn(*args, **kwargs)
        return wrapped

    top_k, categorical = jax.lax.top_k, jax.random.categorical

    def draw(key, logits, *args, **kwargs):
        # the draw among the top k sees max_k values, the other V
        name = "top_draw" if logits.shape[-1] == MAX_K else "full_draw"
        return reporting(name, categorical)(key, logits, *args, **kwargs)

    monkeypatch.setattr(jax.lax, "top_k", reporting("sort", top_k))
    monkeypatch.setattr(jax.random, "categorical", draw)
    return ran


# temp, topk, live (None: no mask given), the operations that must run
CASES = {
    "all_greedy": ([0.0] * B, [0, 5, 0, 3, 0, 0], None, set()),
    "all_topk": ([0.7, 1.0, 0.3, 1.5, 0.9, 2.0], [1, 5, 16, 40, 3, 2], None,
                 {"sort", "top_draw"}),
    "all_full": ([0.7, 1.0, 0.3, 1.5, 0.9, 2.0], [0] * B, None,
                 {"full_draw"}),
    "mixed": ([0.0, 1.0, 0.0, 0.8, 0.0, 1.3], [0, 4, 7, 0, 0, 9], None,
              {"sort", "top_draw", "full_draw"}),
    "one_sampler_among_greedy": ([0.0, 0.0, 0.9, 0.0, 0.0, 0.0],
                                 [0, 0, 6, 0, 3, 0], [1] * B,
                                 {"sort", "top_draw"}),
    "parked_sampler_among_greedy": ([0.0, 0.0, 0.9, 0.0, 1.1, 0.0],
                                    [0, 0, 6, 0, 0, 0], [1, 1, 0, 1, 0, 1],
                                    set()),
}


@pytest.mark.parametrize("with_bias", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("case", list(CASES))
def test_sampler_equals_the_unconditional_form(branches, case, with_bias):
    temp, topk, live, must_run = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    logits = jnp.asarray(rng.normal(size=(B, V)) * 3.0, jnp.float32)
    bias = (jnp.asarray(rng.normal(size=(B, V)) * 2.0, jnp.float32)
            if with_bias else None)
    temp = jnp.asarray(temp, jnp.float32)
    topk = jnp.asarray(topk, jnp.int32)
    mask = None if live is None else jnp.asarray(live, jnp.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(7), 11)
    want = np.asarray(jax.jit(reference)(logits, temp, topk, key, bias))
    branches.clear()            # the reference ran all three
    got = jax.jit(lambda *a: engine_mod._sample_tokens(*a, max_k=MAX_K))(
        logits, temp, topk, key, bias, mask)
    got = np.asarray(jax.block_until_ready(got))
    jax.effects_barrier()
    read = np.ones(B, bool) if live is None else np.asarray(live, bool)
    np.testing.assert_array_equal(got[read], want[read])
    assert branches == must_run
    # a slot nobody reads gets its arg-max, whatever it asked for
    greedy = np.asarray(jnp.argmax(
        logits if bias is None else logits + bias, axis=-1))
    np.testing.assert_array_equal(got[~read], greedy[~read])


LLAMA = LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                         attention="reference", remat=False)


def _engine(max_batch):
    return ContinuousBatchingEngine(EngineConfig(
        model=LLAMA, max_batch=max_batch, max_seq=64))


@pytest.mark.parametrize("temp,topk", [(0.0, 0), (0.0, 9), (0.8, 0),
                                       (0.8, 9), (1.4, 300)])
def test_sample_one_draws_a_first_token_as_before(temp, topk):
    """``sample_one``: the sampler over one prompt's [1, V] row."""
    engine = _engine(2)
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(258,)) * 3.0, jnp.float32)
    bias_row = jnp.asarray(rng.normal(size=(258,)), jnp.float32)
    key = jax.random.fold_in(jax.random.PRNGKey(0), 5)
    tok, *_ = engine._sample_one(logits, temp, topk, key, bias_row)
    want = reference(
        logits[None], jnp.full((1,), temp), jnp.full((1,), topk, jnp.int32),
        key, bias_row[None], max_k=256)[0]
    assert int(tok) == int(want)


def _drain(engine, requests):
    for _ in range(200):
        if all(r.done for r in requests):
            return
        engine.step()
    raise AssertionError("requests did not finish")


def test_engine_counts_the_branch_each_step_engaged():
    def greedy_requests(engine):
        return [engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3, 4 + i], max_tokens=12)) for i in range(8)]

    alone = _engine(10)
    baseline = greedy_requests(alone)
    _drain(alone, baseline)
    steps = alone.stats()["sampler_steps"]
    assert steps["greedy"] == alone.decode_steps > 0
    assert steps["topk"] == steps["full"] == 0

    engine = _engine(10)
    requests = greedy_requests(engine)
    for _ in range(4):
        engine.step()
    before = engine.stats()["sampler_steps"]
    assert before == {"greedy": engine.decode_steps, "topk": 0, "full": 0}
    sampled = engine.add_request(GenerationRequest(
        prompt_ids=[9, 8, 7], max_tokens=4, temperature=0.8))
    _drain(engine, [sampled])
    during = engine.stats()["sampler_steps"]
    assert during["full"] == 3 and during["topk"] == 0
    # its last token is its last by length, so the step launched ahead
    # of that token's read has its slot parked: an arg-max step again
    assert during["greedy"] == before["greedy"] + 1
    narrowed = engine.add_request(GenerationRequest(
        prompt_ids=[9, 8, 7], max_tokens=3, temperature=0.8, top_k=5))
    _drain(engine, requests + [narrowed])
    after = engine.stats()["sampler_steps"]
    assert after["topk"] == 2 and after["full"] == 3
    # the sampling slots gone, the steps are plain arg-max ones again
    assert after["greedy"] > before["greedy"]
    assert sum(after.values()) == engine.decode_steps
    assert ([r.output_ids for r in requests]
            == [r.output_ids for r in baseline])
    assert all(0 <= t < 258 for t in sampled.output_ids + narrowed.output_ids)
