"""An admitting step keeps the device fed: a prompt's prefill is
launched before the host does the rest of its admission, and a second
waiting prompt is queued on the device before the host waits for the
first one's token. The order of the programs, and so every token, is
what admitting the prompts one at a time gives."""

import jax.numpy as jnp
import pytest

from ray_tpu.llm.engine import (
    ContinuousBatchingEngine, EngineConfig, GenerationRequest)
from ray_tpu.models.jamba import JambaConfig
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.util import flight_recorder
from ray_tpu.util import metrics as metrics_mod

ADMIT_LAUNCH = "ray_tpu_engine_admit_launch_seconds"
LLAMA = LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                         attention="reference", remat=False)
SHARED = [9, 8, 7, 6, 5, 4, 3, 2, 1, 11]     # a prefix worth caching


def _engine(model=LLAMA, max_batch=3, **kw):
    return ContinuousBatchingEngine(EngineConfig(
        model=model, max_batch=max_batch, max_seq=64, **kw))


def _drain(engine, requests):
    for _ in range(200):
        if all(r.done for r in requests):
            return
        engine.step()
    raise AssertionError("requests did not finish")


def _hist_count(overlapped):
    snap = metrics_mod.histogram_snapshot(
        ADMIT_LAUNCH, {"overlapped": overlapped})
    return 0 if snap is None else snap[3]


@pytest.fixture
def recorder():
    was = flight_recorder.RECORDER
    rec = flight_recorder.enable(label="test", capacity=4096)
    yield rec
    flight_recorder.RECORDER = was


def test_second_prompt_is_queued_before_the_first_is_read(recorder):
    engine = _engine(max_batch=2)
    counts0 = [_hist_count("0"), _hist_count("1")]
    first, second = [engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3, i], max_tokens=3, logit_bias={7: -100.0}))
        for i in range(2)]
    engine.step()
    spans = sorted((ev for ev in recorder.snapshot()
                    if ev[3] == "serve" and ev[4].startswith("engine.")),
                   key=lambda ev: ev[1])
    prefills = [ev for ev in spans if ev[4] == "engine.prefill"]
    assert [ev[5]["req"] for ev in prefills] == [first.request_id,
                                                 second.request_id]
    readbacks = [ev for ev in spans if ev[4] == "engine.readback"]
    assert len(readbacks) == 3          # two first tokens, one decode step
    # each admission launches three times (the prefill; the sampling;
    # the bias row and the slot hand-over), and all six, with both
    # rows, are on the device's queue before the host waits at all
    before = [ev[4] for ev in spans if ev[1] < readbacks[0][1]]
    assert before.count("engine.launch") == 6
    assert before.count("engine.bias") == before.count("engine.insert") == 2
    # the second prompt's span opens inside the first's: the spans of
    # the two admissions overlap, as their work does
    a, b = prefills
    assert a[1] < b[1] < readbacks[0][1] < a[1] + a[2] < b[1] + b[2]
    # read and emitted in admission order
    assert first.t_first_token < second.t_first_token
    assert len(first.output_ids) == len(second.output_ids) == 2
    stats = engine.stats()              # flushes the buffer
    assert (stats["admissions"], stats["admissions_overlapped"]) == (2, 1)
    assert [_hist_count("0"), _hist_count("1")] == [counts0[0] + 1,
                                                    counts0[1] + 1]
    # a prompt that arrives alone is ahead of nobody
    _drain(engine, [first, second])
    third = engine.add_request(GenerationRequest(prompt_ids=[4, 3, 2, 1],
                                                 max_tokens=2))
    _drain(engine, [third])
    stats = engine.stats()
    assert (stats["admissions"], stats["admissions_overlapped"]) == (3, 1)


@pytest.mark.parametrize("what", ["a prompt that arrives",
                                  "the slot that an ending frees"])
def test_a_step_admits_what_turns_up_while_it_waits_for_a_token(what):
    """With nothing to queue behind a prefill the host waits for its
    token and then looks again, as it always did: what arrived
    meanwhile, and what fits once the first prompt ended at its first
    token, is admitted in the same step, under nobody's prefill."""
    late = GenerationRequest(prompt_ids=[4, 3, 2, 1], max_tokens=2)
    if what == "a prompt that arrives":
        engine = _engine(max_batch=2)
        first = engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3, 4], max_tokens=2))
        readback = engine._readback

        def arrives_meanwhile(*arrays):
            if late.t_submit is None:
                engine.add_request(late)
            return readback(*arrays)

        engine._readback = arrives_meanwhile
    else:
        engine = _engine(max_batch=1)
        first = engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3, 4], max_tokens=1))
        engine.add_request(late)
    engine.step()
    assert engine._admitted_last_step == 2
    assert (engine.admissions, engine.admissions_overlapped) == (2, 0)
    assert first.done and late.done         # one decode step for both
    assert first.t_first_token < late.t_admit


def _plain(i, max_tokens=5, **kw):
    return GenerationRequest(prompt_ids=[1 + i, 2, 3, 4 + i, 5][: 3 + i],
                             max_tokens=max_tokens, **kw)


def _guided(i):
    from ray_tpu.llm.guided import json_schema_constraint
    from ray_tpu.llm import ByteTokenizer
    schema = {"type": "object", "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"]}
    return _plain(i, max_tokens=24, guided=json_schema_constraint(
        schema, ByteTokenizer().token_strings()))


def _register_lora(engine):
    import jax
    from ray_tpu.models.llama import lora_init
    rng = jax.random.PRNGKey(3)
    lora = lora_init(rng, LLAMA, rank=4)
    # a fresh adapter's B is zero, which is the base model
    lora["B_q"] = 0.5 * jax.random.normal(rng, lora["B_q"].shape,
                                          dtype=LLAMA.dtype)
    engine.register_adapter("ada", lora)


# how the three prompts reach their slots: "together" (one step admits
# all three), "step" (one a step), "admit" (one a call of _admit with no
# decode step between: where the sampler's key decides the token, that
# draws the keys in the order "together" does), "disagg" (prefill_only
# on another engine, then add_prefilled)
CASES = {
    "greedy": dict(make=_plain),
    "seeded temperature with top_k": dict(
        make=lambda i: _plain(i, temperature=0.9, top_k=5), alone="admit"),
    "biased": dict(make=lambda i: _plain(
        i, logit_bias={t: -100.0 for t in range(0, 258, 2 + i)})),
    "first-token logprobs": dict(make=lambda i: _plain(i, logprobs=3)),
    "prefix hit": dict(
        make=lambda i: GenerationRequest(prompt_ids=SHARED + [20 + i, 30],
                                         max_tokens=5),
        engine=dict(enable_prefix_caching=True)),
    "guided": dict(make=_guided, tokens=None),
    "penalties": dict(make=lambda i: _plain(
        i, presence_penalty=0.7, frequency_penalty=0.3)),
    # the second of the three runs the base model beside two adapters
    "LoRA": dict(make=lambda i: _plain(i, adapter=None if i == 1 else "ada"),
                 engine=dict(max_loras=1, lora_rank=4),
                 setup=_register_lora),
    # each ends at its first token, and its slot is free again when
    # the loop looks
    "max_tokens=1": dict(make=lambda i: _plain(i, max_tokens=1), tokens=1),
    "prefill_only then add_prefilled": dict(make=_plain, path="disagg"),
    "tiny Jamba": dict(make=_plain,
                       model=JambaConfig.tiny(dtype=jnp.float32)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_tokens_equal_those_of_prompts_admitted_one_at_a_time(case):
    spec = CASES[case]
    model = spec.get("model", LLAMA)

    def run(path):
        engine = _engine(model, **spec.get("engine", {}))
        spec.get("setup", lambda engine: None)(engine)
        requests = [spec["make"](i) for i in range(3)]
        if path == "disagg":
            # the prefill engine runs the one _run_prefill; its first
            # token and its rows are what a colocated admission gives
            prefiller = _engine(model)
            for r in requests:
                engine.add_prefilled(
                    r, *prefiller.prefill_only(r.prompt_ids))
        for r in requests:
            if path != "disagg":
                engine.add_request(r)
            if path == "admit":
                engine._admit()
            elif path == "step":
                engine.step()
        _drain(engine, requests)
        return engine, requests

    path = spec.get("path", "together")
    engine, got = run(path)
    solo, want = run(spec.get("alone", "step"))
    # adopted prompts are not prefilled here; of three admitted in one
    # step the second and the third are queued under the one before
    assert (engine.admissions, engine.admissions_overlapped) == (
        (0, 0) if path == "disagg" else (3, 2))
    assert (solo.admissions, solo.admissions_overlapped) == (3, 0)
    tokens = spec.get("tokens", 5)
    for g, w in zip(got, want):
        assert g.output_ids == w.output_ids and g.output_ids
        assert g.logprob_data == w.logprob_data
        assert g.finish_reason == w.finish_reason
        if tokens is not None:
            assert len(g.output_ids) == tokens
            assert g.finish_reason == "length"
    if case == "guided":
        assert all(r.finish_reason == "stop" for r in got)
    if case == "first-token logprobs":
        assert all(len(r.logprob_data) == 5 for r in got)
    if case == "prefix hit":
        assert engine.prefix_hits == solo.prefix_hits == 2


def test_mixed_admissions_compile_each_program_once():
    engine = _engine()
    bias = {7: -100.0}
    # together and alone, biased and not, sampled and greedy: one
    # bucket, so one prefill program
    for group in ([bias, None, bias], [None], [bias], [None, bias]):
        requests = [engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3, 4 + i], max_tokens=3, logit_bias=b,
            temperature=0.5 * (i % 2))) for i, b in enumerate(group)]
        _drain(engine, requests)
    assert engine.admissions == 7 and engine.admissions_overlapped == 3
    for program in (engine._prefill, engine._sample_one, engine._set_bias,
                    engine._insert, engine._decode):
        assert program._cache_size() == 1
    assert list(engine.stats()["programs"]) == ["prefill_4", "decode"]


# -- the dense step launched ahead of its read-back (PR 67) ---------------
# A dense step is launched from the state the step in flight returns,
# before that one is read; slots change hands on the device. The order
# is the stepper's own choice (``_may_launch_ahead``); a test that wants
# today's order says so through that predicate, as nothing else can.

def _in_order(engine):
    engine._may_launch_ahead = lambda requests: False
    return engine


def _prompt(n, salt):
    return [3 + (7 * i + 11 * salt) % 240 for i in range(n)]


# ``at``: the call of step() before which the request arrives; ``cancel_at``:
# the call after which another thread cancels it; ``stop_at``: it ends on
# a stop id, the token it emits there without one. Three slots: the later
# ones take slots that an ending by length, the cancel and the stop freed.
_ALIGNED = [
    dict(at=0, n=5, max_tokens=12),
    dict(at=0, n=7, max_tokens=4),
    dict(at=0, n=4, max_tokens=30, cancel_at=4),
    dict(at=5, n=6, max_tokens=15, stop_at=3),
    dict(at=9, n=9, max_tokens=3),
    dict(at=13, n=3, max_tokens=6),
]
# a fourth prompt at the start waits for a slot, and two arrive in one
# call: who gets which step then depends on the order, the tokens of a
# greedy request do not
_CONTENDED = _ALIGNED[:3] + [
    dict(at=0, n=8, max_tokens=5),
    dict(at=6, n=6, max_tokens=15, stop_at=3),
    dict(at=6, n=9, max_tokens=3),
    dict(at=11, n=3, max_tokens=6),
]


def _run_specs(engine, specs, temperature, shift=0):
    """step() by hand. Returns the requests and the slot each took."""
    import queue
    import threading
    requests = [GenerationRequest(
        prompt_ids=_prompt(spec["n"], k), max_tokens=spec["max_tokens"],
        temperature=temperature, top_k=(0, 5, 0, 3, 0, 8, 4)[k]
        if temperature else 0, stop_ids=tuple(spec.get("stop_ids", ())),
        stream_queue=queue.Queue()) for k, spec in enumerate(specs)]
    slots = {}
    prefill_into = engine._prefill_into

    def noting(slot, request, overlapped):
        slots[request.request_id] = slot.index
        return prefill_into(slot, request, overlapped)

    engine._prefill_into = noting
    for i in range(200):
        for spec, request in zip(specs, requests):
            if spec["at"] + (shift if spec["at"] else 0) == i:
                engine.add_request(request)
        engine.step()
        for spec, request in zip(specs, requests):
            if spec.get("cancel_at") == i:
                thread = threading.Thread(target=engine.cancel,
                                          args=(request,))
                thread.start()
                thread.join()
        if all(r.done for r in requests) and not engine.has_work():
            break
    return requests, [slots.get(r.request_id) for r in requests]


def _streamed(request):
    out = []
    while not request.stream_queue.empty():
        out.append(request.stream_queue.get_nowait())
    return out


@pytest.mark.parametrize("family", ["llama", "jamba"])
@pytest.mark.parametrize("traffic", ["greedy", "greedy, contended",
                                     "sampled"])
def test_steps_launched_ahead_emit_what_steps_in_order_emit(
        family, traffic):
    """Token for token, greedy and sampled, over endings by length, by
    a stop id and by a cancel, with admissions into the freed slots. A
    prompt admitted behind a step in flight joins the step after it, so
    the run in order is given each later arrival one call later: the
    device then runs the same programs in the same order, which for a
    sampled token (its key is the sampler's counter split by slot) is
    the whole of the seed. The token of the step launched ahead of a
    stop id is in no output, no stream and no count."""
    model = (LLAMA if family == "llama"
             else JambaConfig.tiny(dtype=jnp.float32))
    temperature = 0.9 if traffic == "sampled" else 0.0
    specs = [dict(s) for s in (_CONTENDED if "contended" in traffic
                               else _ALIGNED)]
    shift = 0 if "contended" in traffic else 1
    stopper = next(k for k, s in enumerate(specs) if "stop_at" in s)
    # learn the stop id from a run in order without one
    learned, _ = _run_specs(_in_order(_engine(model)), specs,
                            temperature, shift)
    stop_id = learned[stopper].output_ids[specs[stopper]["stop_at"]]
    stop_at = learned[stopper].output_ids.index(stop_id)
    specs[stopper]["stop_ids"] = (stop_id,)

    ahead = _engine(model)
    got, got_slots = _run_specs(ahead, specs, temperature)
    in_order = _in_order(_engine(model))
    want, want_slots = _run_specs(in_order, specs, temperature, shift)
    reasons = [r.finish_reason for r in got]
    assert reasons == [r.finish_reason for r in want]
    assert reasons.count("stop") == 1 and reasons.count("abort") == 1
    assert reasons.count("length") == len(specs) - 2
    if shift:
        assert got_slots == want_slots
    for g, w in zip(got, want):
        if g.finish_reason == "abort" and not shift:
            # a cancel ends what a request had got by then
            n = min(len(g.output_ids), len(w.output_ids))
            assert n and g.output_ids[:n] == w.output_ids[:n]
        else:
            assert g.output_ids == w.output_ids and g.output_ids
        assert _streamed(g) == g.output_ids + [None]
    assert len(got[stopper].output_ids) == stop_at + 1
    for engine, requests in ((ahead, got), (in_order, want)):
        assert engine.total_generated == sum(
            len(r.output_ids) for r in requests)
    # the stop id cost the token of the step launched ahead of it; the
    # cancel, seen before the next launch, the one in flight, as in order
    assert ahead.stats()["discarded_tokens"] == 2
    assert in_order.stats()["discarded_tokens"] == 1
    launches = ahead.stats()["decode_launches"]
    # in order: an idle engine's first step and the steps behind an
    # admission, which is read first
    assert launches["ahead"] > launches["in_order"] > 0
    assert in_order.stats()["decode_launches"]["ahead"] == 0
    assert ahead._decode._cache_size() == 1
    assert ahead._seat._cache_size() == ahead._park._cache_size() == 1


@pytest.mark.parametrize("stops", [1, 3])
def test_every_stop_id_costs_one_discarded_token(stops):
    """Requests that end on a stop id before their length: each ran one
    step further on the device than its output shows, and that token
    was dropped; the counter and the series count them."""
    series = "ray_tpu_engine_discarded_tokens_total"
    engine = _engine(max_batch=4)
    engine.flush_metrics()
    def counted():
        for line in metrics_mod.prometheus_text().splitlines():
            if line.startswith(series + " "):
                return float(line.split()[-1])
        return 0.0

    before = counted()
    free = [engine.add_request(GenerationRequest(
        prompt_ids=_prompt(4 + k, k), max_tokens=12)) for k in range(stops)]
    _drain(engine, free)
    stopped = [engine.add_request(GenerationRequest(
        prompt_ids=_prompt(4 + k, k), max_tokens=12,
        stop_ids=(r.output_ids[3 + k],))) for k, r in enumerate(free)]
    plain = engine.add_request(GenerationRequest(
        prompt_ids=_prompt(5, 9), max_tokens=14))
    _drain(engine, stopped + [plain])
    while engine.has_work():
        engine.step()
    for k, (r, f) in enumerate(zip(stopped, free)):
        assert r.finish_reason == "stop"
        assert r.output_ids == f.output_ids[:f.output_ids.index(
            r.stop_ids[0]) + 1]
    assert plain.finish_reason == "length" and len(plain.output_ids) == 14
    stats = engine.stats()
    assert stats["discarded_tokens"] == stops
    assert counted() - before == stops
    assert stats["total_generated"] == sum(
        len(r.output_ids) for r in free + stopped + [plain])


def _ordered(kind, i=7):
    if kind == "guided":
        return _guided(i % 3)
    if kind == "penalties":
        return _plain(1, max_tokens=6, presence_penalty=0.7,
                      frequency_penalty=0.3)
    return _plain(2, max_tokens=6, logprobs=2)


@pytest.mark.parametrize("kind", ["guided", "penalties", "logprobs"])
def test_a_request_the_host_must_follow_puts_the_batch_in_order_and_back(
        kind):
    """While a request lives whose next step needs its last token on
    the host (a grammar's mask, a penalty's row, logprobs), every dense
    step is in order, the plain slots' too; before it and after it they
    are launched ahead. Everyone's tokens are what they get alone."""
    def launches(engine):
        return dict(engine.decode_launches)

    engine = _engine(max_batch=3)
    plain = [engine.add_request(_plain(i, max_tokens=60))
             for i in range(2)]
    for _ in range(6):
        engine.step()
    before = launches(engine)
    assert before["ahead"] >= 6 and before["in_order"] == 1
    special = engine.add_request(_ordered(kind))
    _drain(engine, [special])
    during = launches(engine)
    # at most the step that was in flight when it arrived was ahead
    steps = len(special.output_ids) - 1
    assert during["in_order"] - before["in_order"] >= steps
    assert during["ahead"] - before["ahead"] <= 1
    for _ in range(6):
        engine.step()
    after = launches(engine)
    assert after["ahead"] - during["ahead"] >= 5
    assert after["in_order"] - during["in_order"] <= 1
    _drain(engine, plain)
    for request in plain + [special]:
        alone = _engine(max_batch=3)
        same = GenerationRequest(
            prompt_ids=list(request.prompt_ids),
            max_tokens=request.max_tokens, logprobs=request.logprobs,
            presence_penalty=request.presence_penalty,
            frequency_penalty=request.frequency_penalty,
            guided=request.guided)
        alone.add_request(same)
        _drain(alone, [same])
        assert same.output_ids == request.output_ids
        assert same.finish_reason == request.finish_reason
        if kind == "logprobs" and request is special:
            assert [e["id"] for e in same.logprob_data] == \
                [e["id"] for e in request.logprob_data]
            assert len(request.logprob_data) == 6
