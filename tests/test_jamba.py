"""The Jamba family (Mamba-1 mixers beside attention layers): the model,
the selective-scan kernel and the engine's dense path over a cache of
two kinds, held to the plain reference (benchmark/reference/jamba.py)
in float32 at tiny sizes: hidden 64, d_inner 128, 16 states, dt rank
4, four layers of which the third is attention, vocabulary 512."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as reference
from ray_tpu.llm.engine import (ContinuousBatchingEngine, EngineConfig,
                                GenerationRequest)
from ray_tpu.models.jamba import (JambaConfig, jamba_forward, jamba_init,
                                  jamba_init_cache, jamba_prefill)
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import selective_scan as scan_op

CFG = JambaConfig.tiny(dtype=jnp.float32)
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return jax.jit(jamba_init, static_argnums=1)(jax.random.PRNGKey(0), CFG)


def _engine(params, **kw):
    return ContinuousBatchingEngine(
        EngineConfig(model=CFG, max_batch=3, max_seq=128, **kw),
        params=params)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def _reference_logprobs(params, ids, n_out):
    """The reference's log-probability of each of the last ``n_out``
    tokens of ``ids``, from one full forward pass."""
    seq = jnp.asarray(ids, jnp.int32)
    logp = jax.nn.log_softmax(reference.logits(
        params, seq[:-1], **reference.kwargs_from(CFG)), -1)
    at = np.arange(len(ids) - 1 - n_out, len(ids) - 1)
    return np.asarray(logp[at, seq[at + 1]])


def test_config_places_the_attention_layers():
    assert CFG.layer_kinds == ("mamba", "mamba", "attn", "mamba")
    full = JambaConfig()
    assert [i for i, k in enumerate(full.layer_kinds) if k == "attn"] \
        == [7, 21]
    assert (full.n_mamba_layers, full.n_attn_layers) == (26, 2)
    assert full.runs == (("mamba", 0, 7), ("attn", 0, 1), ("mamba", 7, 13),
                         ("attn", 1, 1), ("mamba", 20, 6))


def test_forward_matches_the_reference(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 512)
    got = jax.jit(lambda p, t: jamba_forward(p, t, CFG))(params, tokens)
    for i in range(2):
        want = reference.logits(params, tokens[i],
                                **reference.kwargs_from(CFG))
        assert float(jnp.abs(got[i] - want).max()) < TOL


@pytest.mark.parametrize("length", [5, 37, 64, 100])
def test_engine_prefill_then_decode_matches_the_reference(params, length):
    """A bucketed prefill told the prompt's true length, then whole-
    batch decode steps with two parked slots: every token's
    log-probability against the reference's one full pass. 5, 37 and
    100 are no bucket's length; 64 is."""
    engine = _engine(params)
    ids = _prompt(length, seed=length)
    request = engine.add_request(GenerationRequest(
        prompt_ids=ids, max_tokens=20, logprobs=0))
    while engine.has_work():
        engine.step()
    assert request.error is None and len(request.output_ids) == 20
    got = [e["logprob"] for e in request.logprob_data]
    want = _reference_logprobs(params, ids + request.output_ids, 20)
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert engine._decode._cache_size() == 1


def test_padding_leaves_the_state_of_the_true_last_token(params):
    """The same prompt through two buckets: the cache entry (recurrent
    state, convolution inputs, the K/V rows of the prompt) and the
    logits do not see the padding."""
    ids = _prompt(21, seed=3)
    outs = []
    for bucket in (32, 64):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :21] = ids
        outs.append(jax.jit(lambda p, t, n: jamba_prefill(p, t, n, CFG))(
            params, padded, np.int32(21)))
    (logits_a, a), (logits_b, b) = outs
    assert float(jnp.abs(logits_a - logits_b).max()) < 1e-5
    for leaf in ("ssm", "conv"):
        assert float(jnp.abs(a[leaf] - b[leaf]).max()) < 1e-5
    for leaf in ("k", "v"):
        assert float(jnp.abs(a[leaf][:, :, :21]
                             - b[leaf][:, :, :21]).max()) < 1e-5
    assert float(jnp.abs(a["ssm"]).max()) > 0


def test_requests_admitted_at_different_steps_equal_their_solo_outputs(
        params):
    """Two requests of unequal length share the batch from different
    steps on; a third takes the slot the first one left. Parked slots'
    states are moved by every step and replaced whole at admission."""
    prompts = [_prompt(9, 1), _prompt(40, 2), _prompt(17, 3)]
    lengths = [6, 14, 8]
    solo = []
    for ids, n in zip(prompts, lengths):
        engine = _engine(params)
        solo.append(engine.generate([ids], max_tokens=n)[0])
    engine = _engine(params)
    first = engine.add_request(GenerationRequest(
        prompt_ids=prompts[0], max_tokens=lengths[0]))
    for _ in range(3):
        engine.step()
    second = engine.add_request(GenerationRequest(
        prompt_ids=prompts[1], max_tokens=lengths[1]))
    while not first.done:
        engine.step()
    slot_of_first = 0
    third = engine.add_request(GenerationRequest(
        prompt_ids=prompts[2], max_tokens=lengths[2]))
    engine.step()
    assert engine.slots[slot_of_first].request is third
    while engine.has_work():
        engine.step()
    assert [first.output_ids, second.output_ids, third.output_ids] == solo
    assert engine._decode._cache_size() == 1


@pytest.mark.parametrize("seq,length", [(8, 5), (128, 100), (384, 200)])
def test_scan_kernel_matches_the_sequential_form(monkeypatch, seq, length):
    """The Pallas kernel in interpret mode against the sequential
    lax.scan, with a length shorter than the sequence and a non-zero
    initial state."""
    monkeypatch.setattr(scan_op, "_INTERPRET", True)
    channels, states = 256, 16
    keys = jax.random.split(jax.random.PRNGKey(seq), 5)
    x = jax.random.normal(keys[0], (seq, channels))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (seq, channels)) - 3)
    b = jax.random.normal(keys[2], (seq, states))
    c = jax.random.normal(keys[3], (seq, states))
    a = -jnp.broadcast_to(
        jnp.arange(1, states + 1, dtype=jnp.float32)[:, None],
        (states, channels))
    h0 = jax.random.normal(keys[4], (states, channels))
    args = (x, dt, b, c, a, jnp.ones((channels,)), h0, jnp.int32(length))
    assert scan_op._plan(seq, channels, states) is not None
    y, h = jax.jit(scan_op.selective_scan)(*args)
    want_y, want_h = jax.jit(scan_op._scan_reference)(*args)
    assert float(jnp.abs(y - want_y).max()) < TOL
    assert float(jnp.abs(h - want_h).max()) < TOL
    assert float(jnp.abs(y[length:]).max()) == 0.0
    # the state moved, and past the length it did not
    assert float(jnp.abs(h - h0).max()) > 1e-3
    _, h_short = jax.jit(scan_op._scan_reference)(
        *(v[:length] if v.shape[:1] == (seq,) else v for v in args[:-1]),
        jnp.int32(length))
    assert float(jnp.abs(h - h_short).max()) < TOL


_DRAFT = LlamaConfig.tiny(vocab_size=512)


@pytest.mark.parametrize("option,kwargs", [
    ("draft_model", {"draft_model": _DRAFT}),
    ("multi_step", {"multi_step": 2}),
    ("enable_prefix_caching", {"enable_prefix_caching": True}),
    ("chunked_prefill_tokens", {"chunked_prefill_tokens": 16}),
    ("max_loras", {"max_loras": 2}),
    ("quantization", {"quantization": "int8"}),
    ("adapter", None), ("prefill_only", None), ("add_prefilled", None)])
def test_engine_refuses_what_a_recurrent_cache_cannot_honour(
        params, option, kwargs):
    """Each by name, at construction or, for what a request or a call
    asks, there: never by corrupting a state."""
    if kwargs is not None:
        with pytest.raises(ValueError, match=option):
            _engine(params, **kwargs)
        return
    engine = _engine(params)
    with pytest.raises(ValueError, match=option):
        if option == "adapter":
            engine.add_request(GenerationRequest(
                prompt_ids=[1, 2, 3], adapter="tuned"))
        elif option == "prefill_only":
            engine.prefill_only([1, 2, 3])
        else:
            engine.add_prefilled(
                GenerationRequest(prompt_ids=[1, 2, 3]),
                np.zeros((1, 1, 4, 1, 16), np.float32),
                np.zeros((1, 1, 4, 1, 16), np.float32), 3, 7)
    assert not engine.has_work()


def test_stats_and_series_tell_the_cache_and_the_padding(params):
    from ray_tpu.util import metrics
    engine = _engine(params)
    engine.generate([_prompt(5), _prompt(37)], max_tokens=2)
    stats = engine.stats()
    cache = jamba_init_cache(CFG, 3, 128)
    assert stats["cache_bytes"] == {
        "kv": cache["k"].nbytes + cache["v"].nbytes,
        "recurrent": cache["ssm"].nbytes + cache["conv"].nbytes}
    assert stats["prefill_tokens"] == {"real": 42, "pad": 3 + 27}
    assert stats["scan_fallbacks"] == [] and stats["flash_fallbacks"] == []
    assert sorted(stats["programs"]) == ["decode", "prefill_64",
                                         "prefill_8"]
    text = metrics.prometheus_text()
    assert 'ray_tpu_engine_prefill_tokens_total{kind="pad"}' in text
    assert 'ray_tpu_engine_cache_bytes{kind="recurrent"}' in text
    engine.close()


def test_embed_and_fail_all_go_through_the_family(params):
    engine = _engine(params)
    vector = engine.embed(_prompt(11))
    assert vector.shape == (CFG.dim,) and np.isfinite(vector).all()
    request = engine.add_request(GenerationRequest(
        prompt_ids=_prompt(7), max_tokens=50))
    engine.step()
    engine.fail_all("boom")
    assert request.error == "boom"
    assert [leaf.shape for leaf in engine.cache] == [
        leaf.shape for leaf in jax.tree.leaves(
            jamba_init_cache(CFG, 3, 128))]
    assert float(jnp.abs(engine.cache[2]).max()) == 0.0
    again = engine.generate([_prompt(7)], max_tokens=4)
    assert again == _engine(params).generate([_prompt(7)], max_tokens=4)
