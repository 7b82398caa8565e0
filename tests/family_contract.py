"""What the serving engine's dense path is held to for EVERY model family
behind the seam of ray_tpu/models/family.py, written once: six tests
over a family's ``ROW``, the record its test file keeps beside the
tests that are its own. A family's file gets them with

    from family_contract import *        # the contract, over ROW
    ROW = Row(reference=..., forward=..., variants={"": Variant(CFG)}, ...)

and pytest collects them there, each under the cases the row names (so
the engine tests of five families stay in five files, and no file is
the run's longest). The model is held to the family's plain reference
under benchmark/reference/ in float32 at tiny sizes.
"""

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import (ContinuousBatchingEngine, EngineConfig,
                                GenerationRequest)
from ray_tpu.models.family import family_of
from ray_tpu.models.llama import LlamaConfig

__all__ = [
    "Row", "Variant", "engine_of", "prompt", "pytest_generate_tests",
    "row", "weights",
    "test_forward_matches_the_reference",
    "test_engine_prefill_then_decode_matches_the_reference",
    "test_padding_leaves_the_entry_and_the_counts_of_the_prompt_alone",
    "test_requests_admitted_at_different_steps_equal_their_solo_outputs",
    "test_engine_refuses_what_the_familys_cache_cannot_honour",
    "test_embed_and_fail_all_go_through_the_family"]


@dataclasses.dataclass(frozen=True)
class Variant:
    """One configuration a family's contract runs at."""
    config: Any
    # ops modules whose Pallas kernels run in interpret mode under it
    interpret: Tuple[Any, ...] = ()
    max_seq: int = 128
    new_tokens: int = 20
    # the rows the decode kernel reads at a time, which the engine
    # counts by; None: all of max_seq (no kernel off the TPU)
    kv_block: Optional[int] = None
    # (engine, stats) after the run: what only this variant can assert
    check: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class Row:
    """A family's row: what the contract needs to know of it."""
    # the plain reference: ``logits(params, tokens [L], **kwargs_from(
    # config))``
    reference: Any
    # the family's whole-sequence forward, (params, tokens [B, S],
    # config) -> logits
    forward: Callable
    # by name; "" is the plain one, which every test without cases of
    # its own runs at
    variants: Dict[str, Variant]
    tol: float = 1e-4
    vocab: int = 512
    # test_forward_matches_the_reference: (variant, sequence length)
    forward_cases: Tuple = (("", 37),)
    # test_engine_prefill_then_decode_...: (prompt length, variant);
    # of these 5, 37 and 100 are no bucket's length, 64 is
    decode_cases: Tuple = ((5, ""), (37, ""), (64, ""), (100, ""))
    # test_requests_admitted_at_different_steps_...: variants
    admission_cases: Tuple = ("",)
    # what a refusal for the cache's sake must say of the cache (a
    # regular expression; ``ModelFamily.dense_only`` in other words)
    refusal: str = ""
    # the layers with a routed feed-forward (the counts' arithmetic)
    routed_layers: int = 0
    # (entry of bucket 32, entry of bucket 64), leaves by name: the
    # family's own assertions on a prefill's entry
    check_entry: Optional[Callable] = None
    # {count name: value} of the prompt's 21 positions: the family's own
    check_prefill_counts: Optional[Callable] = None


@pytest.fixture
def row(request) -> Row:
    return request.module.ROW


# the contract test -> the field of the row that holds its cases
ROW_CASES = {
    "test_forward_matches_the_reference": "forward_cases",
    "test_engine_prefill_then_decode_matches_the_reference": "decode_cases",
    "test_requests_admitted_at_different_steps_equal_their_solo_outputs":
        "admission_cases"}


def _case_id(case) -> str:
    parts = case if isinstance(case, tuple) else (case,)
    return "-".join(str(p) if p != "" else "plain" for p in parts)


def pytest_generate_tests(metafunc):
    """A contract test's cases are its module's row's."""
    cases = ROW_CASES.get(metafunc.function.__name__)
    if cases and "case" in metafunc.fixturenames:
        metafunc.parametrize("case", getattr(metafunc.module.ROW, cases),
                             ids=_case_id)


@functools.lru_cache(maxsize=None)
def weights(config):
    """The family's weights from key 0, made once a configuration."""
    return jax.jit(family_of(config).init, static_argnums=1)(
        jax.random.PRNGKey(0), config)


def engine_of(config, max_seq: int = 128, **kw):
    return ContinuousBatchingEngine(
        EngineConfig(model=config, max_batch=3, max_seq=max_seq, **kw),
        params=weights(config))


def prompt(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


def reference_logprobs(row: Row, config, ids, n_out):
    """The reference's log-probability of each of the last ``n_out``
    tokens of ``ids``, from one full forward pass."""
    seq = jnp.asarray(ids, jnp.int32)
    logp = jax.nn.log_softmax(row.reference.logits(
        weights(config), seq[:-1], **row.reference.kwargs_from(config)), -1)
    at = np.arange(len(ids) - 1 - n_out, len(ids) - 1)
    return np.asarray(logp[at, seq[at + 1]])


def _variant(row: Row, name: str, monkeypatch) -> Variant:
    variant = row.variants[name]
    for op in variant.interpret:
        monkeypatch.setattr(op, "_INTERPRET", True)
    return variant


def test_forward_matches_the_reference(row, case, monkeypatch):
    name, seq = case
    cfg = _variant(row, name, monkeypatch).config
    params = weights(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0,
                                row.vocab)
    got = jax.jit(lambda p, t: row.forward(p, t, cfg))(params, tokens)
    for i in range(2):
        want = row.reference.logits(params, tokens[i],
                                    **row.reference.kwargs_from(cfg))
        assert float(jnp.abs(got[i] - want).max()) < row.tol


def test_engine_prefill_then_decode_matches_the_reference(row, case,
                                                          monkeypatch):
    """A bucketed prefill told the prompt's true length, the entry
    handed to the slot, then whole-batch decode steps with two parked
    slots: every token's log-probability against the reference's one
    full pass. The row's lengths fall short of their buckets, fill
    them, and end on and off whatever boundary the family's mixers
    have."""
    length, name = case
    variant = _variant(row, name, monkeypatch)
    cfg, n_out = variant.config, variant.new_tokens
    engine = engine_of(cfg, variant.max_seq)
    # the engine counts the rows the decode kernel reads by the blocks
    # of the cache as this family stores it
    assert engine._kv_block == (variant.kv_block or variant.max_seq)
    ids = prompt(length, seed=length, vocab=row.vocab)
    request = engine.add_request(GenerationRequest(
        prompt_ids=ids, max_tokens=n_out, logprobs=0))
    while engine.has_work():
        engine.step()
    assert request.error is None and len(request.output_ids) == n_out
    got = [e["logprob"] for e in request.logprob_data]
    want = reference_logprobs(row, cfg, ids + request.output_ids, n_out)
    assert np.abs(np.asarray(got) - want).max() < row.tol
    assert engine._decode._cache_size() == 1
    stats = engine.stats()
    if family_of(cfg).expert_counts:
        assert stats["dropped_rows"] == 0
    if variant.check is not None:
        variant.check(engine, stats)
    engine.close()


def test_padding_leaves_the_entry_and_the_counts_of_the_prompt_alone(row):
    """The same prompt through two buckets, by the seam's own call: the
    logits of its last position, the cache entry (a state whole, rows
    up to the prompt's length) and the expert counts do not see the
    padding."""
    cfg = row.variants[""].config
    family, params = family_of(cfg), weights(cfg)
    ids = prompt(21, seed=3, vocab=row.vocab)
    outs = []
    for bucket in (32, 64):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :21] = ids
        outs.append(jax.jit(
            lambda p, t, n: family.prefill(p, t, n, cfg, None))(
                params, padded, np.int32(21)))
    (logits_a, a, counts_a), (logits_b, b, counts_b) = outs
    # the last real row: the only one, or (Llama) one of the bucket's
    last_a, last_b = (x[:, 0] if x.shape[1] == 1 else x[:, 20]
                      for x in (logits_a, logits_b))
    assert float(jnp.abs(last_a - last_b).max()) < 1e-5
    a, b = (e if isinstance(e, dict) else dict(zip("kv", e))
            for e in (a, b))
    assert list(a) == list(b)
    for leaf in a:
        if leaf in ("k", "v", "latent"):        # rows, by position
            assert a[leaf].shape[2] == 32
            assert float(jnp.abs(a[leaf][:, :, :21]
                                 - b[leaf][:, :, :21]).max()) < 1e-5
        else:                                   # a state, whole
            assert float(jnp.abs(a[leaf] - b[leaf]).max()) < 1e-5
            assert float(jnp.abs(a[leaf]).max()) > 0
    if row.check_entry is not None:
        row.check_entry(a, b)
    names = family.expert_counts
    if not names:
        assert counts_a is None and counts_b is None
        return
    count_a, count_b = (dict(zip(names, np.asarray(c).tolist()))
                        for c in (counts_a, counts_b))
    # the places walked alone see the padding: none in the bucket of 32
    # (the few-rows form), one chunk of 64 x top_k a routed layer in
    # the bucket of 64
    walked = (count_a.pop("pairs_walked"), count_b.pop("pairs_walked"))
    assert walked == (0, row.routed_layers * 64 * cfg.top_k)
    assert count_a == count_b
    # 21 positions x the routed layers x top_k picks, wherever the
    # padding ends; every held pick computed; a prefill counts no
    # expert slots
    assert count_a["picks_held"] + count_a["picks_absent"] \
        == 21 * row.routed_layers * cfg.top_k
    assert count_a["picks_computed"] == count_a["picks_held"] > 0
    assert (count_a["slots_hit"], count_a["slots_idle"]) == (0, 0)
    if row.check_prefill_counts is not None:
        row.check_prefill_counts(count_a)


def test_requests_admitted_at_different_steps_equal_their_solo_outputs(
        row, case, monkeypatch):
    """Two requests of unequal length share the batch from different
    steps on; a third takes the slot the first one left, whose state or
    rows are replaced at admission, whatever the parked slot held."""
    cfg = _variant(row, case, monkeypatch).config
    prompts = [prompt(9, 1, row.vocab), prompt(40, 2, row.vocab),
               prompt(17, 3, row.vocab)]
    lengths = [6, 14, 8]
    solo = []
    for ids, n in zip(prompts, lengths):
        solo.append(engine_of(cfg).generate([ids], max_tokens=n)[0])
    engine = engine_of(cfg)
    first = engine.add_request(GenerationRequest(
        prompt_ids=prompts[0], max_tokens=lengths[0]))
    for _ in range(3):
        engine.step()
    second = engine.add_request(GenerationRequest(
        prompt_ids=prompts[1], max_tokens=lengths[1]))
    while not first.done:
        engine.step()
    third = engine.add_request(GenerationRequest(
        prompt_ids=prompts[2], max_tokens=lengths[2]))
    engine.step()
    assert engine.slots[0].request is third
    while engine.has_work():
        engine.step()
    assert [first.output_ids, second.output_ids, third.output_ids] == solo
    assert engine._decode._cache_size() == 1


_DRAFT = LlamaConfig.tiny(vocab_size=512)


@pytest.mark.parametrize("option,kwargs", [
    ("draft_model", {"draft_model": _DRAFT}),
    ("multi_step", {"multi_step": 2}),
    ("enable_prefix_caching", {"enable_prefix_caching": True}),
    ("chunked_prefill_tokens", {"chunked_prefill_tokens": 16}),
    ("max_loras", {"max_loras": 2}),
    ("quantization", {"quantization": "int8"}),
    ("adapter", None), ("prefill_only", None), ("add_prefilled", None)])
def test_engine_refuses_what_the_familys_cache_cannot_honour(
        row, option, kwargs):
    """A family with a word on its cache (``dense_only``) runs the dense
    path only. Each other program by name, at construction or, for what
    a request or a call asks, there: never by corrupting a state. The
    refusal names the option, the family and, where it is for the
    cache's sake, what the cache is."""
    cfg = row.variants[""].config
    assert family_of(cfg).dense_only and row.refusal
    if kwargs is not None:
        with pytest.raises(ValueError, match=option) as refused:
            engine_of(cfg, **kwargs)
        said = str(refused.value)
        assert "Llama family's" in said and type(cfg).__name__ in said
        if option not in ("max_loras", "quantization"):
            assert refused.match(row.refusal)
        return
    engine = engine_of(cfg)
    with pytest.raises(ValueError, match=option) as refused:
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
    assert type(cfg).__name__ in str(refused.value)
    assert not engine.has_work()
    engine.close()


def test_embed_and_fail_all_go_through_the_family(row):
    cfg = row.variants[""].config
    engine = engine_of(cfg)
    vector = engine.embed(prompt(11, vocab=row.vocab))
    assert vector.shape == (cfg.dim,) and np.isfinite(vector).all()
    request = engine.add_request(GenerationRequest(
        prompt_ids=prompt(7, vocab=row.vocab), max_tokens=50))
    engine.step()
    engine.fail_all("boom")
    assert request.error == "boom"
    # a fresh cache of the family's, every leaf of it
    fresh = jax.tree.leaves(family_of(cfg).init_cache(cfg, 3, 128))
    assert [leaf.shape for leaf in engine.cache] == [
        leaf.shape for leaf in fresh]
    for leaf in engine.cache:
        assert float(jnp.abs(leaf).max()) == 0.0
    again = engine.generate([prompt(7, vocab=row.vocab)], max_tokens=4)
    assert again == engine_of(cfg).generate(
        [prompt(7, vocab=row.vocab)], max_tokens=4)
