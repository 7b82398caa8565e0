"""LLM engine + serving tests (reference test strategy:
python/ray/llm/tests — engine behavior on tiny models, OpenAI surface
shape checks)."""

import json
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm import (
    ByteTokenizer, ContinuousBatchingEngine, EngineConfig,
    GenerationRequest)
from ray_tpu.models.llama import LlamaConfig


def tiny_engine(max_batch=2, max_seq=64, **kw):
    return ContinuousBatchingEngine(EngineConfig(
        model=LlamaConfig.tiny(max_seq_len=64, attention="reference",
                               remat=False),
        max_batch=max_batch, max_seq=max_seq, **kw))


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("hello, TPU!")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello, TPU!"


def test_decode_matches_full_forward():
    """KV-cache decode must agree with the full forward pass."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import (
        llama_decode_step, llama_forward, llama_init, llama_init_cache,
        llama_prefill)
    cfg = LlamaConfig.tiny(attention="reference", remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.arange(10, dtype=jnp.int32)[None, :]
    logits, ks, vs = llama_prefill(params, toks, cfg)
    ck, cv = llama_init_cache(cfg, 1, 16)
    ck = ck.at[:, :, :10].set(ks)
    cv = cv.at[:, :, :10].set(vs)
    nxt = jnp.array([3], dtype=jnp.int32)
    dlogits, _, _ = llama_decode_step(params, nxt, ck, cv,
                                      jnp.array([10]), cfg)
    full = llama_forward(
        params, jnp.concatenate([toks, nxt[None]], axis=1), cfg)
    np.testing.assert_allclose(np.asarray(dlogits[0]),
                               np.asarray(full[0, -1]),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("n_kv_heads", [4, 2, 1])
def test_decode_steps_attend_over_the_cache_as_stored(n_kv_heads):
    """Three consecutive decode steps in float32 against the full
    forward on the grown sequences, for groups of 1, 2 and 4 query
    heads a KV head: the second and third steps read rows the first
    wrote. Slots parked at row 0 and at the last row sit beside live
    ones, one of which ends on the last row. Rows no step wrote stay
    bit-equal, and the engine's ``decode_lp`` program (one
    ``logprobs=0`` request beside idle slots, as the benchmark's
    reference check runs it) reports the forward's log-probabilities."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import (
        llama_decode_step, llama_forward, llama_init, llama_init_cache,
        llama_prefill)
    cfg = LlamaConfig.tiny(n_kv_heads=n_kv_heads, max_seq_len=64)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    seq, steps = 16, 3
    starts = [0, seq - 1, seq - steps, 5, 1]   # slots 0 and 1 are parked
    parked = (0, 1)
    rng = np.random.default_rng(n_kv_heads)
    shape = llama_init_cache(cfg, len(starts), seq)[0].shape
    # junk in every row: a row past a slot's position must not be read
    ck = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    cv = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    # one right-padded batch: under a causal mask a row's prefix does
    # not see what follows it, so one forward holds every grown
    # sequence's logits and one prefill every prompt's K/V
    grown = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                     size=(len(starts), seq)), jnp.int32)
    full = np.asarray(llama_forward(params, grown, cfg))
    _, ks, vs = llama_prefill(params, grown, cfg)
    live = [slot for slot in range(len(starts)) if slot not in parked]
    for slot in live:
        n = starts[slot]
        ck = ck.at[:, slot, :n].set(ks[:, slot, :n])
        cv = cv.at[:, slot, :n].set(vs[:, slot, :n])
    before_k, before_v = np.asarray(ck), np.asarray(cv)
    step = jax.jit(
        lambda tok, ck, cv, pos: llama_decode_step(params, tok, ck, cv,
                                                   pos, cfg),
        donate_argnums=(1, 2))
    written = np.zeros(shape[1:3], bool)                      # [B, S]
    slots = np.arange(len(starts))
    for i in range(steps):
        pos = np.asarray([n if slot in parked else n + i
                          for slot, n in enumerate(starts)], np.int32)
        logits, ck, cv = step(grown[slots, pos], ck, cv, jnp.asarray(pos))
        written[slots, pos] = True
        np.testing.assert_allclose(
            np.asarray(logits)[live], full[live, pos[live]],
            rtol=1e-4, atol=1e-4)
    after_k, after_v = np.asarray(ck), np.asarray(cv)
    assert written.sum() == 2 + 3 * steps
    for after, before in ((after_k, before_k), (after_v, before_v)):
        np.testing.assert_array_equal(after[:, ~written],
                                      before[:, ~written])
        assert (after[:, written] != before[:, written]).any(
            axis=(-1, -2)).all()

    engine = ContinuousBatchingEngine(EngineConfig(
        model=cfg, max_batch=4, max_seq=64))
    prompt = grown[3, :5].tolist()
    req = engine.add_request(GenerationRequest(
        prompt_ids=prompt, max_tokens=5, temperature=0.0, logprobs=0))
    while not req.done:
        engine.step()
    assert "decode_lp" in engine.stats()["programs"]
    ids = prompt + req.output_ids
    lsm = jax.nn.log_softmax(llama_forward(
        engine.params, jnp.asarray([ids], jnp.int32), cfg)[0], axis=-1)
    want = [float(lsm[len(prompt) - 1 + j, t])
            for j, t in enumerate(req.output_ids)]
    got = [e["logprob"] for e in req.logprob_data]
    assert [e["top"] for e in req.logprob_data] == [[]] * 5
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- the dense step's per-slot state stays on the device -----------------

def _staggered_specs(temperature):
    """Seven requests through three slots: different lengths, two
    admitted together, one that waits for a slot, one cancelled from
    another thread, one that ends on a stop token, later ones that take
    over freed slots."""
    rng = np.random.default_rng(33)

    def prompt(n):
        return rng.integers(3, 250, size=n).tolist()

    specs = [
        dict(at=0, prompt=prompt(5), max_tokens=12),
        dict(at=0, prompt=prompt(9), max_tokens=5),
        dict(at=2, prompt=prompt(7), max_tokens=20, cancel_at=6),
        dict(at=3, prompt=prompt(4), max_tokens=9),
        dict(at=7, prompt=prompt(6), max_tokens=15, stop_at=3),
        dict(at=9, prompt=prompt(11), max_tokens=3),
        dict(at=12, prompt=prompt(3), max_tokens=7),
    ]
    for spec, top_k in zip(specs, [0, 5, 0, 3, 0, 8, 0]):
        spec["temperature"] = temperature
        spec["top_k"] = top_k if temperature > 0 else 0
    return specs


def _request_of(spec):
    return GenerationRequest(
        prompt_ids=list(spec["prompt"]), max_tokens=spec["max_tokens"],
        temperature=spec["temperature"], top_k=spec["top_k"],
        stop_ids=tuple(spec.get("stop_ids", ())))


def _run_schedule(engine, specs, before_step=None):
    """engine.step() by hand: spec ``at`` is the step before which the
    request is added, ``cancel_at`` the step after which another thread
    cancels it. Returns (output_ids, finish_reason) per spec."""
    import threading
    requests = [None] * len(specs)
    for i in range(200):
        for n, spec in enumerate(specs):
            if spec["at"] == i:
                requests[n] = engine.add_request(_request_of(spec))
        if before_step is not None:
            before_step(engine)
        engine.step()
        for n, spec in enumerate(specs):
            if spec.get("cancel_at") == i:
                thread = threading.Thread(target=engine.cancel,
                                          args=(requests[n],))
                thread.start()
                thread.join()
        if all(r is not None and r.done for r in requests):
            break
    return [(r.output_ids, r.finish_reason) for r in requests]


# what the parent commit (1e1f5b6, the five arrays sent every step)
# emitted for _staggered_specs(0.8) on an engine of seed 5
_SAMPLED_ON_THE_PARENT = [
    ([139, 250, 33, 96, 181, 8, 232, 45, 56, 250, 232, 220], "length"),
    ([210, 175, 210, 47, 72], "length"),
    ([46, 245, 90, 16, 74, 88], "abort"),
    ([65, 232, 226, 224, 89, 89, 89, 16, 153], "length"),
    ([215, 88, 161, 199], "stop"),
    ([38, 214, 40], "length"),
    ([65, 152, 234, 37, 129, 72, 234], "length"),
]


def _in_order(engine):
    """The dense step as it was until PR 67: read back before the next
    is launched. Through the stepper's own predicate, not an option."""
    engine._may_launch_ahead = lambda requests: False
    return engine


@pytest.mark.parametrize("temperature,every_state_from_host,ahead", [
    (0.0, False, False), (0.8, False, False), (0.8, True, False),
    (0.0, False, True)])
def test_staggered_traffic_emits_what_each_request_gets_alone(
        temperature, every_state_from_host, ahead):
    """Greedy: token for token what each request gets when generated
    alone, whether the steps are launched ahead or in order. Sampled
    with a seed, in order: what the parent commit emitted (a token's
    key is fold_in(base_key, step_counter) split by slot, so the
    schedule is part of the seed, and a step launched ahead puts an
    admission one step later in it), also when every step is made to
    send its state as the parent did. Whenever a step in order takes
    the state the step before it left on the device, that state equals
    what the host would have gathered from the slots."""
    fed_back = []

    def before_step(engine):
        if ahead:
            return
        if every_state_from_host:
            engine._state_stale = True
        elif not engine._state_stale:
            active = [s for s in engine.slots if s.request is not None]
            engine._step_counter += 1       # as the step is about to
            want = engine._gather_state(active)
            engine._step_counter -= 1
            np.testing.assert_array_equal(np.asarray(engine._state), want)
            fed_back.append(engine._steps)

    def make():
        engine = tiny_engine(max_batch=3, seed=5)
        return engine if ahead else _in_order(engine)

    specs = _staggered_specs(temperature)
    if temperature > 0:
        specs[4]["stop_ids"] = (199,)
    else:
        # the stop token: the fourth the request emits without one
        learn = dict(specs[4], at=0)
        (ids, _), = _run_schedule(make(), [learn])
        specs[4]["stop_ids"] = (ids[specs[4]["stop_at"]],)
    engine = make()
    got = _run_schedule(engine, specs, before_step)
    assert [why for _, why in got] == [
        "length", "length", "abort", "length", "stop", "length", "length"]
    # admitted behind a step in flight, a prompt joins the step after
    assert len(got[2][0]) == (5 if ahead else 6) and len(got[4][0]) == 4
    if temperature > 0:
        assert got == _SAMPLED_ON_THE_PARENT
    else:
        for spec, (ids, why) in zip(specs, got):
            (alone, _), = _run_schedule(make(),
                                        [dict(spec, at=0, cancel_at=None)])
            assert ids == alone[:len(ids)], spec
            assert len(ids) == len(alone) or why == "abort"
    while engine.has_work():
        engine.step()           # a step launched ahead of the last ending
    stats = engine.stats()
    assert stats["decode_steps"] == engine.decode_steps >= 15
    assert sum(stats["decode_launches"].values()) == stats["decode_steps"]
    if ahead:
        # slots changed hands on the device: the state was sent where
        # the engine had run dry; the cancel (seen before the next
        # launch) cost the token in flight, as in order, and the stop id
        # none: its call admitted the prompt that had waited for a slot,
        # so its step was read before the next was launched
        assert stats["state_uploads"] <= 3
        assert stats["decode_launches"]["ahead"] \
            > stats["decode_launches"]["in_order"]
        assert stats["discarded_tokens"] == 1
    elif every_state_from_host:
        assert stats["state_uploads"] == stats["decode_steps"]
        assert stats["decode_launches"]["ahead"] == 0
    else:
        # 7 admissions in 6 steps (two share one), 7 endings; a step
        # that found the state good may still admit and send one
        assert len(fed_back) >= \
            stats["decode_steps"] - stats["state_uploads"] >= 4
        assert stats["state_uploads"] <= 13
        assert stats["discarded_tokens"] == 1       # the cancel's
    assert engine._decode._cache_size() == 1


@pytest.mark.parametrize("scratch", [False, True])
def test_parked_slots_stay_parked_over_fed_back_steps(scratch):
    """50 decode steps, the first with a state from the host and 49 fed
    back on the device: the parked slots stay at ``_dense_park`` (row 0,
    or the last row when the engine keeps a scratch region) with token
    0, the live one advances by one a step, and no step writes a row of
    the cache outside the live slot's own new row and the parked
    slots' park row."""
    from ray_tpu.llm.engine import _LIVE, _POS, _TOKEN
    engine = tiny_engine(max_batch=4, max_seq=64,
                         **({"multi_step": 2} if scratch else {}))
    park = engine._dense_park
    assert park == (63 if scratch else 0)
    # logprobs keep a multi_step engine on the dense step
    request = engine.add_request(GenerationRequest(
        prompt_ids=[5, 6, 7], max_tokens=52, logprobs=0))
    engine.step()
    assert engine.state_uploads == 1
    slot = next(s for s in engine.slots if s.request is request)
    parked = [s.index for s in engine.slots if s.request is None]
    assert len(parked) == 3
    before_k = np.asarray(engine.cache_k)
    before_v = np.asarray(engine.cache_v)
    pos0 = slot.pos
    for i in range(1, 50):
        engine.step()
        state = np.asarray(engine._state)
        assert (state[_POS, parked] == park).all()
        assert not state[_TOKEN, parked].any()
        assert not state[_LIVE, parked].any()
        assert state[_POS, slot.index] == pos0 + i == slot.pos
        assert state[_TOKEN, slot.index] == request.output_ids[-1]
    assert engine.state_uploads == 1 and engine.decode_steps == 50
    assert not request.done
    written = np.zeros(before_k.shape[1:3], bool)            # [B, S]
    written[slot.index, pos0:pos0 + 49] = True
    written[parked, park] = True
    for after, before in ((np.asarray(engine.cache_k), before_k),
                          (np.asarray(engine.cache_v), before_v)):
        np.testing.assert_array_equal(after[:, ~written],
                                      before[:, ~written])
        assert (after[:, slot.index, pos0:pos0 + 49]
                != before[:, slot.index, pos0:pos0 + 49]).any(
                    axis=(-1, -2)).all()


@pytest.mark.parametrize("want_lp", [False, True])
@pytest.mark.parametrize("params_on", [
    "no device", "the default device", "another device", "a mesh"])
def test_decode_is_compiled_once_whichever_way_its_state_comes(
        params_on, want_lp):
    """Steps that took the state from the host (after an admission and
    after an ending) and steps that took it from the device share one
    compiled program: the state is committed either way, as a program's
    results are, and to where the params are: params that are committed
    themselves (a checkpoint put on a device, also another than the
    default one, or sharded over a mesh) change neither that nor the
    tokens."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    config = EngineConfig(
        model=LlamaConfig.tiny(max_seq_len=64, attention="reference",
                               remat=False), max_batch=3, max_seq=64)

    def run(engine):
        lp = 0 if want_lp else None
        first = engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3], max_tokens=6, logprobs=lp))
        for _ in range(3):
            engine.step()
        second = engine.add_request(GenerationRequest(
            prompt_ids=[4, 5, 6], max_tokens=9, logprobs=lp))
        while not (first.done and second.done):
            engine.step()
        return first.output_ids, second.output_ids

    engine = ContinuousBatchingEngine(config)
    devices = {jax.devices()[0]}
    if params_on != "no device":
        want = run(engine)
        if params_on == "a mesh":
            mesh = Mesh(np.array(jax.devices()[2:4]), ("tp",))
            devices = set(mesh.devices.flat)

            def put(x):     # last axis split where it can be
                split = x.ndim >= 2 and x.shape[-1] % 2 == 0
                return jax.device_put(x, NamedSharding(
                    mesh, PartitionSpec(*[None] * (x.ndim - 1), "tp")
                    if split else PartitionSpec()))
            params = jax.tree_util.tree_map(put, engine.params)
        else:
            device = jax.devices()[params_on == "another device"]
            devices = {device}
            params = jax.device_put(engine.params, device)
        engine = ContinuousBatchingEngine(config, params=params)
        assert run(engine) == want
    else:
        run(engine)
    if want_lp:
        # in order: a state from the host after the admission and after
        # the ending
        assert 2 <= engine.state_uploads < engine.decode_steps
    else:
        # launched ahead: the admission is seated and the ending parked
        # on the device, by one program each, and the state they return
        # reaches ``decode`` as its own does
        assert engine.state_uploads == 1
        assert engine.decode_launches["ahead"] >= 6
        assert engine._seat._cache_size() == 1
        assert engine._park._cache_size() == 1
    assert engine._state.sharding.device_set == devices
    assert engine.cache_k.sharding.device_set == devices
    assert engine._decode._cache_size() == 1
    if params_on != "a mesh":
        assert engine._insert._cache_size() == 1
    assert list(engine.stats()["programs"]) == [
        "prefill_4", "decode_lp" if want_lp else "decode"]


@pytest.mark.parametrize("dense_by", ["logprobs", "adapter"])
def test_dense_state_follows_the_slots_it_was_built_for(dense_by):
    """Chunked prefill: a logprobs (or LoRA) request decodes on the dense
    step beside the chunk program while a plain prompt of three chunks
    prefills, so the dense step's state covers that one slot. When the
    prefill is over the dense step takes every slot, with no admission
    or ending in between: it has to notice that its state was built
    for another set of slots. Both requests emit what they emit alone."""
    import jax
    from ray_tpu.models.llama import lora_init
    config = EngineConfig(
        model=LlamaConfig.tiny(max_seq_len=64, attention="reference",
                               remat=False),
        max_batch=3, max_seq=64, chunked_prefill_tokens=4,
        max_loras=1, lora_rank=4)
    special = {"logprobs": 0} if dense_by == "logprobs" \
        else {"adapter": "ada"}
    specs = [dict(prompt_ids=[1, 2, 3], max_tokens=24, **special),
             dict(prompt_ids=list(range(5, 16)), max_tokens=10)]

    def engine_with_adapter():
        engine = ContinuousBatchingEngine(config)
        lora = lora_init(jax.random.PRNGKey(3), config.model, rank=4)
        lora["B_q"] = jax.random.normal(
            jax.random.PRNGKey(4), lora["B_q"].shape,
            dtype=config.model.dtype) * 0.5
        engine.register_adapter("ada", lora)
        return engine

    alone = []
    for spec in specs:
        engine = engine_with_adapter()
        request = engine.add_request(GenerationRequest(**spec))
        while not request.done:
            engine.step()
        alone.append(request.output_ids)
    engine = engine_with_adapter()
    first = engine.add_request(GenerationRequest(**specs[0]))
    for _ in range(4):
        engine.step()
    assert first.output_ids and not first.done
    second = engine.add_request(GenerationRequest(**specs[1]))
    uploads = []
    while not (first.done and second.done):
        engine.step()
        uploads.append(engine.state_uploads)
    assert [first.output_ids, second.output_ids] == alone
    # and the state is fed back again once the set of slots is settled
    assert len(set(uploads)) < len(uploads)


def test_engine_greedy_deterministic():
    engine = tiny_engine()
    out1 = engine.generate([[1, 2, 3]], max_tokens=8)
    engine2 = tiny_engine()
    out2 = engine2.generate([[1, 2, 3]], max_tokens=8)
    assert out1 == out2
    assert len(out1[0]) == 8


def test_engine_continuous_batching_overflow():
    """More requests than slots: all finish via slot recycling."""
    engine = tiny_engine(max_batch=2)
    prompts = [[1, 2], [3, 4, 5], [6], [7, 8, 9, 10]]
    outs = engine.generate(prompts, max_tokens=5)
    assert [len(o) for o in outs] == [5, 5, 5, 5]
    stats = engine.stats()
    assert stats["active"] == 0 and stats["waiting"] == 0
    assert stats["total_generated"] == 20


def test_engine_batch_matches_single():
    """Continuous batching must not change greedy outputs."""
    engine = tiny_engine(max_batch=4)
    batched = engine.generate([[1, 2, 3], [9, 8, 7, 6]], max_tokens=6)
    solo1 = tiny_engine().generate([[1, 2, 3]], max_tokens=6)[0]
    solo2 = tiny_engine().generate([[9, 8, 7, 6]], max_tokens=6)[0]
    assert batched[0] == solo1
    assert batched[1] == solo2


def test_engine_sampling_temperature():
    engine = tiny_engine(seed=0)
    out = engine.generate([[1, 2, 3]], max_tokens=8, temperature=1.0,
                          top_k=50)
    assert len(out[0]) == 8


def test_openai_app_http(ray_start_shared):
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    config = LLMConfig(
        model_id="llama-test",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64),
        max_tokens=8)
    serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(build_openai_app(config=config), name="llm_app",
              route_prefix="/v1")
    try:
        body = json.dumps({"prompt": "hi", "max_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            payload = json.loads(resp.read())
        assert payload["object"] == "text_completion"
        assert payload["choices"][0]["finish_reason"] in ("length", "stop")
        assert payload["usage"]["completion_tokens"] == 4

        body = json.dumps({"messages": [
            {"role": "user", "content": "hello"}], "max_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            payload = json.loads(resp.read())
        assert payload["object"] == "chat.completion"
        assert "content" in payload["choices"][0]["message"]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models", timeout=60) as resp:
            payload = json.loads(resp.read())
        assert payload["data"][0]["id"] == "llama-test"
    finally:
        serve.shutdown()


def test_openai_multi_model_app(ray_start_shared):
    """Two models in one app: routing by the request `model` field via
    the multiplexed replica LRU, 404 model_not_found on unknown ids,
    /v1/models listing both, streaming through the router, and
    per-model counters (reference: serve/llm/__init__.py:178
    multi-model build_openai_app)."""
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    from ray_tpu.util.metrics import prometheus_text

    def cfg(mid, seed):
        return LLMConfig(
            model_id=mid,
            engine=EngineConfig(
                model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                       attention="reference",
                                       remat=False),
                max_batch=2, max_seq=64, seed=seed),
            max_tokens=8)

    serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(build_openai_app([cfg("model-a", 1), cfg("model-b", 2)]),
              name="llm_app", route_prefix="/v1")

    def post(path, payload, timeout=120):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=timeout)

    try:
        # /v1/models lists both ids
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/models", timeout=60) as r:
            ids = {m["id"] for m in json.loads(r.read())["data"]}
        assert ids == {"model-a", "model-b"}

        # each model answers under its own id (different seeds =>
        # independently initialized engines)
        outs = {}
        for mid in ("model-a", "model-b"):
            with post("/v1/completions",
                      {"model": mid, "prompt": "route me",
                       "max_tokens": 6, "temperature": 0.0}) as r:
                payload = json.loads(r.read())
            assert payload["model"] == mid
            outs[mid] = payload["choices"][0]["text"]
        assert outs["model-a"] != outs["model-b"]

        # unknown model -> HTTP 404 with OpenAI error shape
        try:
            post("/v1/completions", {"model": "nope", "prompt": "x"})
            raise AssertionError("unknown model must 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
            err = json.loads(e.read())["error"]
            assert err["code"] == "model_not_found"

        # streaming routes by model too
        with post("/v1/completions",
                  {"model": "model-b", "prompt": "stream",
                   "max_tokens": 4, "stream": True}) as r:
            assert r.headers["Content-Type"].startswith(
                "text/event-stream")
            events = r.read().decode()
        assert "data: [DONE]" in events
        assert '"model": "model-b"' in events

        # per-model counters reached the metrics registry
        text = prometheus_text()
        assert 'serve_llm_requests' in text
        assert 'model="model-a"' in text
        assert 'model="model-b"' in text
    finally:
        serve.shutdown()


def test_multiplex_eviction_stops_engine(ray_start_shared):
    """LRU eviction must stop the evicted model's stepper thread (the
    multiplex loader calls model.stop())."""
    from ray_tpu.serve.llm import LLMConfig, MultiplexLLMServer

    def cfg(mid):
        return LLMConfig(
            model_id=mid,
            engine=EngineConfig(
                model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                       attention="reference",
                                       remat=False),
                max_batch=2, max_seq=64),
            max_tokens=4)

    server = MultiplexLLMServer([cfg("m1"), cfg("m2")],
                                max_models_per_replica=1)
    s1 = server._load("m1")
    assert not s1._stopped
    server._load("m2")  # evicts m1 (LRU size 1)
    assert s1._stopped
    s1._stepper.join(timeout=10)
    assert not s1._stepper.is_alive()


def test_batch_inference_processor(ray_start_shared):
    """End-to-end batch inference over Data: Dataset of prompts ->
    tokenize -> engine actors -> detokenize -> Dataset, with greedy
    output matching a directly-driven engine (reference:
    batch/processor/base.py Processor e2e)."""
    from ray_tpu import data as rd
    from ray_tpu.llm import (ProcessorConfig, build_llm_processor,
                             throughput_summary)

    engine_cfg = EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64),
        max_batch=4, max_seq=64, seed=7)
    config = ProcessorConfig(engine=engine_cfg, batch_size=4,
                             concurrency=2, max_tokens=8)
    processor = build_llm_processor(
        config,
        preprocess=lambda row: {"prompt": row["question"]},
        postprocess=lambda row: {**row, "answered": True})

    questions = [f"Q{i}: what is {i}+{i}?" for i in range(10)]
    ds = rd.from_items([{"question": q} for q in questions])
    rows = processor(ds).take_all()

    assert len(rows) == len(questions)
    assert all(r["answered"] for r in rows)
    assert all(len(r["generated_ids"]) > 0 for r in rows)
    assert all(isinstance(r["generated_text"], str) for r in rows)

    # Greedy decode must agree with a directly-driven engine.
    direct = ContinuousBatchingEngine(engine_cfg)
    tok = ByteTokenizer()
    by_prompt = {r["prompt"]: r for r in rows}
    want = direct.generate([tok.encode(questions[3])], max_tokens=8,
                           stop_ids=(tok.eos_id,))[0]
    assert list(by_prompt[questions[3]]["generated_ids"]) == want

    summary = throughput_summary(rows)
    assert summary["num_generated_tokens"] >= len(questions)
    assert summary["tokens_per_s"] > 0


def test_batch_processor_config_validation():
    from ray_tpu.llm import ProcessorConfig
    with pytest.raises(ValueError):
        ProcessorConfig(concurrency=0)
    with pytest.raises(ValueError):
        ProcessorConfig(concurrency=(3, 2))
    assert ProcessorConfig(concurrency=(1, 3)).concurrency == (1, 3)


def test_sampling_param_validation():
    # Bad client params must be rejected per-request, not reach the
    # shared stepper thread (where they would fail every in-flight
    # request on the replica).
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    config = LLMConfig(
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64),
        max_tokens=4)
    server = LLMServer(config)
    out = server.completions({"prompt": "hi", "top_k": 10**9})
    # top_k is clamped to vocab, so this must succeed, not error
    assert "error" not in out
    out = server.completions({"prompt": "hi", "temperature": "hot"})
    assert out["error"]["type"] == "invalid_request_error"
    out = server.completions({"prompt": "hi", "max_tokens": -3})
    assert out["error"]["type"] == "invalid_request_error"
    out = server.chat_completions({"messages": "nope"})
    assert out["error"]["type"] == "invalid_request_error"
    # engine still healthy after the rejects
    out = server.completions({"prompt": "hi", "max_tokens": 2})
    assert out["usage"]["completion_tokens"] == 2


def test_on_device_sampling_greedy_matches_argmax():
    """temperature=0 must be exact argmax regardless of the fused
    sampler (regression: sampling moved on-device)."""
    import jax
    from ray_tpu.models.llama import llama_forward

    config = EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=2, max_seq=64)
    engine = ContinuousBatchingEngine(config)
    prompt = [1, 5, 9, 13]
    out = engine.generate([prompt], max_tokens=6)[0]
    # oracle: greedy decode via repeated full forwards
    ids = list(prompt)
    want = []
    for _ in range(6):
        logits = llama_forward(engine.params, np.asarray([ids]),
                               config.model)
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        want.append(nxt)
        ids.append(nxt)
    assert out == want


def test_on_device_sampling_topk_valid():
    """top-k sampling must only emit tokens from the top-k set."""
    import jax
    from ray_tpu.models.llama import llama_forward

    config = EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=2, max_seq=64, seed=7)
    engine = ContinuousBatchingEngine(config)
    prompt = [2, 4, 6]
    out = engine.generate([prompt], max_tokens=1, temperature=0.8,
                          top_k=3)[0]
    logits = llama_forward(engine.params, np.asarray([prompt]),
                           config.model)
    top3 = set(np.argsort(np.asarray(logits[0, -1]))[-3:].tolist())
    assert out[0] in top3


def test_multi_lora_adapters_diverge_and_batch_together():
    """Two adapters + base in ONE decode batch must produce base output
    for base slots and adapter-specific output for adapter slots."""
    import jax
    from ray_tpu.models.llama import lora_init

    config = EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=4, max_seq=64, max_loras=2, lora_rank=4)
    engine = ContinuousBatchingEngine(config)
    c = config.model
    rng = jax.random.PRNGKey(3)
    # non-trivial adapters: random B too (fresh lora_init B=0 is identity)
    lora_a = lora_init(rng, c, rank=4)
    lora_a["B_q"] = jax.random.normal(
        jax.random.fold_in(rng, 1), lora_a["B_q"].shape, dtype=c.dtype) * 0.5
    lora_a["B_v"] = jax.random.normal(
        jax.random.fold_in(rng, 2), lora_a["B_v"].shape, dtype=c.dtype) * 0.5
    lora_b = lora_init(jax.random.fold_in(rng, 9), c, rank=4)
    lora_b["B_q"] = jax.random.normal(
        jax.random.fold_in(rng, 3), lora_b["B_q"].shape, dtype=c.dtype) * 0.5
    engine.register_adapter("ada", lora_a)
    engine.register_adapter("bob", lora_b)

    prompt = [3, 7, 11, 15]
    base_alone = engine.generate([prompt], max_tokens=5)[0]

    reqs = [
        engine.add_request(GenerationRequest(prompt_ids=list(prompt),
                                             max_tokens=5)),
        engine.add_request(GenerationRequest(prompt_ids=list(prompt),
                                             max_tokens=5, adapter="ada")),
        engine.add_request(GenerationRequest(prompt_ids=list(prompt),
                                             max_tokens=5, adapter="bob")),
    ]
    while any(not r.done for r in reqs):
        engine.step()
    base_mixed, ada_out, bob_out = [r.output_ids for r in reqs]
    # base slot unaffected by neighbors' adapters
    assert base_mixed == base_alone
    # adapters actually change the output (random B's make that certain)
    assert ada_out != base_alone
    assert bob_out != ada_out


def test_fresh_adapter_is_identity():
    """A fresh lora_init adapter (B=0) must decode exactly like base."""
    import jax
    from ray_tpu.models.llama import lora_init

    config = EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=2, max_seq=64, max_loras=1)
    engine = ContinuousBatchingEngine(config)
    engine.register_adapter("zero", lora_init(jax.random.PRNGKey(0),
                                              config.model, rank=8))
    prompt = [1, 2, 3]
    base = engine.generate([prompt], max_tokens=4)[0]
    req = engine.add_request(GenerationRequest(
        prompt_ids=list(prompt), max_tokens=4, adapter="zero"))
    while not req.done:
        engine.step()
    assert req.output_ids == base


def test_unknown_adapter_fails_fast():
    config = EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=2, max_seq=64, max_loras=1)
    engine = ContinuousBatchingEngine(config)
    with pytest.raises(ValueError):
        engine.add_request(GenerationRequest(prompt_ids=[1],
                                             adapter="nope"))


def test_prefill_decode_disaggregation(ray_start_shared):
    """Disaggregated serving must produce EXACTLY the same greedy
    output as the colocated engine (the KV block travels prefill ->
    decode through the object plane)."""
    from ray_tpu import serve
    from ray_tpu.llm.disagg import build_disagg_app
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    cfg = LLMConfig(
        model_id="llama-disagg",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64, seed=0),
        max_tokens=8)

    # gold: colocated engine, same seed => same weights
    colocated = LLMServer(cfg)
    want = colocated.completions({"prompt": "hello world", "max_tokens": 6})
    assert "error" not in want

    try:
        app = build_disagg_app(cfg, num_prefill=1, num_decode=1)
        handle = serve.run(app, name="disagg", route_prefix="/llm")
        got = handle.remote({"__path__": "/v1/completions",
                             "prompt": "hello world",
                             "max_tokens": 6}).result(timeout_s=120)
        assert "error" not in got, got
        assert got["choices"][0]["text"] == want["choices"][0]["text"]
        assert got["usage"] == want["usage"]
        # a second round-trip reuses the freed slot
        got2 = handle.remote({"__path__": "/v1/completions",
                              "prompt": "abc",
                              "max_tokens": 4}).result(timeout_s=120)
        assert "error" not in got2
        want2 = colocated.completions({"prompt": "abc", "max_tokens": 4})
        assert got2["choices"][0]["text"] == want2["choices"][0]["text"]
    finally:
        serve.shutdown()


def test_disagg_token_streaming(ray_start_shared):
    """Token streaming over the DISAGGREGATED path (VERDICT round-2
    item 6): SSE deltas flow decode replica -> router -> client, the
    concatenated stream matches the colocated greedy output exactly,
    and the final chunk reports usage + the KV-handoff latency."""
    import json

    from ray_tpu import serve
    from ray_tpu.llm.disagg import build_disagg_app
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    cfg = LLMConfig(
        model_id="llama-disagg-stream",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64, seed=0),
        max_tokens=8)

    colocated = LLMServer(cfg)
    want = colocated.completions({"prompt": "hello world",
                                  "max_tokens": 6})
    assert "error" not in want

    try:
        app = build_disagg_app(cfg, num_prefill=1, num_decode=1)
        handle = serve.run(app, name="disagg-stream",
                           route_prefix="/llm-stream")
        events = list(handle.options(stream=True).remote(
            {"__path__": "/v1/completions", "prompt": "hello world",
             "max_tokens": 6, "stream": True}))
        assert events[-1] == "data: [DONE]\n\n"
        chunks = [json.loads(e[len("data: "):]) for e in events[:-1]]
        text = "".join(c["choices"][0]["text"] for c in chunks)
        assert text == want["choices"][0]["text"]
        # genuinely incremental: more than one non-empty delta chunk
        assert sum(1 for c in chunks if c["choices"][0]["text"]) >= 2
        final = chunks[-1]
        assert final["choices"][0]["finish_reason"] in ("stop", "length")
        assert final["usage"] == want["usage"]
        assert final["kv_handoff_ms"] >= 0.0
    finally:
        serve.shutdown()


# ----------------------------------------------------- speculative decoding

def _spec_cfgs():
    target = LlamaConfig.tiny(max_seq_len=64, attention="reference",
                              remat=False)
    draft = LlamaConfig.tiny(max_seq_len=64, attention="reference",
                             remat=False, dim=32, n_layers=1, n_heads=2,
                             n_kv_heads=1, hidden_dim=64)
    return target, draft


def test_speculative_matches_target_greedy():
    """The speculative correctness invariant: greedy output must be
    IDENTICAL to target-only greedy decoding, for any draft model."""
    import jax
    from ray_tpu.models.llama import llama_init

    target, draft = _spec_cfgs()
    params = llama_init(jax.random.PRNGKey(3), target)
    base = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=2, max_seq=64),
        params=params)
    spec = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=2, max_seq=64,
                     draft_model=draft, spec_tokens=4),
        params=params)
    prompts = [[1, 5, 9, 13], [2, 4, 6]]
    want = base.generate(prompts, max_tokens=16)
    got = spec.generate(prompts, max_tokens=16)
    assert got == want
    assert all(len(o) == 16 for o in got)


def test_speculative_perfect_draft_skips_target_steps():
    """With draft == target every proposal is accepted: the engine
    must emit spec_tokens tokens per target forward, not one."""
    import jax
    from ray_tpu.models.llama import llama_init

    target, _ = _spec_cfgs()
    params = llama_init(jax.random.PRNGKey(5), target)
    spec = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64,
                     draft_model=target, spec_tokens=4),
        params=params, draft_params=params)
    [out] = spec.generate([[1, 2, 3]], max_tokens=13)
    assert len(out) == 13
    # prefill (+1 counter) + ceil(12 / 4) = 3 verify rounds
    assert spec._step_counter <= 1 + 3
    base = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64),
        params=params)
    [want] = base.generate([[1, 2, 3]], max_tokens=13)
    assert out == want


def test_speculative_sampled_requests_stay_correct():
    """temperature>0 requests take the non-speculative fallback inside
    the spec engine and still produce tokens."""
    import jax
    from ray_tpu.models.llama import llama_init

    target, draft = _spec_cfgs()
    params = llama_init(jax.random.PRNGKey(7), target)
    spec = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=2, max_seq=64,
                     draft_model=draft, spec_tokens=3),
        params=params)
    [a, b] = spec.generate([[1, 2], [3, 4]], max_tokens=8,
                           temperature=0.8, top_k=20)
    assert len(a) == 8 and len(b) == 8
    assert all(0 <= t < 258 for t in a + b)


def test_speculative_stop_mid_chunk():
    """A stop token emitted inside an accepted chunk must end the
    request there, not after the whole chunk."""
    import jax
    from ray_tpu.models.llama import llama_init

    target, _ = _spec_cfgs()
    params = llama_init(jax.random.PRNGKey(9), target)
    base = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64),
        params=params)
    [full] = base.generate([[1, 2, 3]], max_tokens=12)
    stop = full[5]  # force a stop on the 6th greedy token
    spec = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64,
                     draft_model=target, spec_tokens=4),
        params=params, draft_params=params)
    req = spec.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=12, stop_ids=(int(stop),)))
    while not req.done:
        spec.step()
    assert req.finish_reason == "stop"
    # ends at the FIRST occurrence of the stop token (the tiny random
    # model may repeat it before index 5)
    assert req.output_ids == full[:full.index(stop) + 1]


def test_speculative_config_validation():
    target, draft = _spec_cfgs()
    import dataclasses
    bad_draft = dataclasses.replace(draft, vocab_size=999)
    with pytest.raises(ValueError, match="vocab_size"):
        ContinuousBatchingEngine(EngineConfig(
            model=target, draft_model=bad_draft))
    with pytest.raises(ValueError, match="spec_tokens"):
        ContinuousBatchingEngine(EngineConfig(
            model=target, draft_model=draft, spec_tokens=1))


def test_speculative_mixed_batch():
    """Greedy and sampled requests share a speculation round: the
    greedy slot speculates, the sampled slot gets one properly-sampled
    target token per round."""
    import jax
    from ray_tpu.models.llama import llama_init

    target, draft = _spec_cfgs()
    params = llama_init(jax.random.PRNGKey(11), target)
    base = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64),
        params=params)
    [want] = base.generate([[1, 2, 3]], max_tokens=10)
    spec = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=2, max_seq=64,
                     draft_model=draft, spec_tokens=3),
        params=params)
    r1 = spec.add_request(GenerationRequest(prompt_ids=[1, 2, 3],
                                            max_tokens=10))
    r2 = spec.add_request(GenerationRequest(prompt_ids=[4, 5],
                                            max_tokens=10,
                                            temperature=0.7, top_k=12))
    while not (r1.done and r2.done):
        spec.step()
    assert r1.output_ids == want
    assert len(r2.output_ids) == 10


# ----------------------------------------------------- multi-step decoding

def test_multi_step_matches_single_step_greedy():
    """Fused K-step decoding must produce exactly the single-step
    greedy outputs (discarding past a stop/max mid-chunk)."""
    engine = tiny_engine(max_batch=2)
    want = engine.generate([[1, 2, 3], [7, 8]], max_tokens=11)
    multi = tiny_engine(max_batch=2, multi_step=4)
    got = multi.generate([[1, 2, 3], [7, 8]], max_tokens=11)
    assert got == want
    # 11 tokens: 1 from prefill + ceil(10/4)=3 fused rounds
    assert multi._step_counter <= 2 + 3  # 2 prefills + 3 rounds


def test_multi_step_stop_token_truncates():
    engine = tiny_engine(max_batch=1)
    [full] = engine.generate([[1, 2, 3]], max_tokens=12)
    stop = full[4]
    multi = tiny_engine(max_batch=1, multi_step=4)
    req = multi.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=12, stop_ids=(int(stop),)))
    while not req.done:
        multi.step()
    assert req.finish_reason == "stop"
    assert req.output_ids == full[:full.index(stop) + 1]


def test_multi_step_sampled_and_overflow():
    """Sampling works inside the fused chunk, and slot recycling
    still drains more requests than slots."""
    multi = tiny_engine(max_batch=2, multi_step=3)
    outs = multi.generate([[1], [2, 3], [4], [5, 6]], max_tokens=7,
                          temperature=0.9, top_k=40)
    assert [len(o) for o in outs] == [7, 7, 7, 7]


def test_multi_step_excludes_draft():
    target, draft = _spec_cfgs()
    with pytest.raises(ValueError, match="mutually exclusive"):
        ContinuousBatchingEngine(EngineConfig(
            model=target, draft_model=draft, multi_step=4))


def test_speculative_disagg_adopt_without_ids_stays_dense():
    """A disagg-adopted request without prompt_ids cannot feed the
    draft; the engine must decode it dense (correctly) instead of
    speculating on a garbage prefix."""
    import jax
    from ray_tpu.models.llama import llama_init

    target, draft = _spec_cfgs()
    params = llama_init(jax.random.PRNGKey(13), target)
    prefiller = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64),
        params=params)
    ks, vs, plen, tok = prefiller.prefill_only([1, 2, 3, 4])
    spec = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64,
                     draft_model=draft, spec_tokens=4),
        params=params)
    req = GenerationRequest(prompt_ids=[], max_tokens=10)
    spec.add_prefilled(req, ks, vs, plen, tok)
    while not req.done:
        spec.step()
    base = ContinuousBatchingEngine(
        EngineConfig(model=target, max_batch=1, max_seq=64),
        params=params)
    [want] = base.generate([[1, 2, 3, 4]], max_tokens=10)
    assert req.output_ids == want


# ----------------------------------------------------- prefix caching

def test_prefix_cache_shared_system_prompt_exact_outputs():
    """Two prompts sharing a long prefix: the second prefills only its
    suffix, and greedy outputs are identical to an uncached engine."""
    sysp = list(range(10, 26))  # 16-token shared "system prompt"
    p1 = sysp + [1, 2, 3]
    p2 = sysp + [7, 8]
    plain = tiny_engine(max_batch=2)
    want = plain.generate([p1, p2], max_tokens=9)
    cached = tiny_engine(max_batch=2, enable_prefix_caching=True,
                         prefix_cache_min_tokens=8)
    got_1 = cached.generate([p1], max_tokens=9)
    got_2 = cached.generate([p2], max_tokens=9)
    assert got_1[0] == want[0]
    assert got_2[0] == want[1]
    s = cached.stats()
    assert s["prefix_hits"] == 1 and s["prefix_misses"] == 1


def test_prefix_cache_repeat_prompt_hits():
    cached = tiny_engine(max_batch=1, enable_prefix_caching=True,
                         prefix_cache_min_tokens=4)
    prompt = [5, 6, 7, 8, 9, 10]
    a = cached.generate([prompt], max_tokens=6)
    b = cached.generate([prompt], max_tokens=6)
    assert a == b
    assert cached.stats()["prefix_hits"] == 1


def test_prefix_cache_lru_and_min_tokens():
    cached = tiny_engine(max_batch=1, enable_prefix_caching=True,
                         prefix_cache_min_tokens=4,
                         prefix_cache_entries=2)
    cached.generate([[1, 2]], max_tokens=2)         # below min: not stored
    assert cached.stats()["prefix_cache_entries"] == 0
    for base in (10, 20, 30):
        cached.generate([[base, base + 1, base + 2, base + 3]],
                        max_tokens=2)
    assert cached.stats()["prefix_cache_entries"] == 2  # LRU capped


# ----------------------------------------------------- chunked prefill

def test_chunked_prefill_matches_blocking():
    """Chunked prompt processing must produce the exact greedy outputs
    of blocking whole-prompt prefill."""
    plain = tiny_engine(max_batch=2)
    prompts = [list(range(1, 21)), list(range(30, 37))]
    want = plain.generate(prompts, max_tokens=9)
    chunked = tiny_engine(max_batch=2, chunked_prefill_tokens=8)
    got = chunked.generate(prompts, max_tokens=9)
    assert got == want


def test_chunked_prefill_interleaves_with_decode():
    """A long prompt admitted mid-stream must NOT stall an ongoing
    decode: the decoding request keeps emitting while the newcomer's
    prompt advances chunk by chunk."""
    engine = tiny_engine(max_batch=2, chunked_prefill_tokens=4)
    r1 = engine.add_request(GenerationRequest(prompt_ids=[1, 2, 3],
                                              max_tokens=30))
    engine.step()  # r1 admitted (instant: 3 < chunk? still chunked path)
    while not r1.output_ids:
        engine.step()
    baseline = len(r1.output_ids)
    r2 = engine.add_request(GenerationRequest(
        prompt_ids=list(range(1, 17)), max_tokens=4))  # 4 chunks
    for _ in range(3):
        engine.step()
    # r1 kept decoding during r2's chunked prefill rounds
    assert len(r1.output_ids) >= baseline + 3
    while not (r1.done and r2.done):
        engine.step()
    assert len(r2.output_ids) == 4


def test_chunked_prefill_overflow_and_sampling():
    engine = tiny_engine(max_batch=2, chunked_prefill_tokens=4)
    prompts = [list(range(1, 11)), [5, 6], list(range(20, 33)), [9]]
    outs = engine.generate(prompts, max_tokens=6, temperature=0.8,
                           top_k=30)
    assert [len(o) for o in outs] == [6, 6, 6, 6]
    assert engine.stats()["prefilling"] == 0


def test_chunked_prefill_config_validation():
    target, draft = _spec_cfgs()
    with pytest.raises(ValueError, match="mutually exclusive"):
        ContinuousBatchingEngine(EngineConfig(
            model=target, draft_model=draft, chunked_prefill_tokens=8))
    with pytest.raises(ValueError, match="mutually exclusive"):
        ContinuousBatchingEngine(EngineConfig(
            model=target, enable_prefix_caching=True,
            chunked_prefill_tokens=8))
    with pytest.raises(ValueError, match="max_seq"):
        ContinuousBatchingEngine(EngineConfig(
            model=target, max_seq=64, chunked_prefill_tokens=128))


# ----------------------------------------------------- embeddings

def test_engine_embed_shapes_and_determinism():
    engine = tiny_engine()
    v1 = engine.embed([1, 2, 3, 4])
    v2 = engine.embed([1, 2, 3, 4])
    v3 = engine.embed([9, 8])
    dim = engine.config.model.dim
    assert v1.shape == (dim,)
    assert np.allclose(v1, v2)
    assert not np.allclose(v1, v3)
    with pytest.raises(ValueError):
        engine.embed([])


def test_openai_embeddings_endpoint(ray_start_shared):
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    config = LLMConfig(
        model_id="embed-test",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64),
        max_tokens=8)
    serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(build_openai_app(config=config), name="emb_app",
              route_prefix="/v1")
    try:
        body = json.dumps({"input": ["hello", "world"]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/embeddings", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            payload = json.loads(resp.read())
        assert payload["object"] == "list"
        assert [d["index"] for d in payload["data"]] == [0, 1]
        dim = config.engine.model.dim
        assert all(len(d["embedding"]) == dim for d in payload["data"])
        assert payload["data"][0]["embedding"] != \
            payload["data"][1]["embedding"]
        assert payload["usage"]["prompt_tokens"] > 0
    finally:
        serve.shutdown()


def test_embeddings_input_validation(ray_start_shared):
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    config = LLMConfig(
        model_id="embed-val",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64),
        max_tokens=4)
    server = LLMServer(config)
    try:
        for bad in (123, None, [], [""], [1, 2]):
            out = server.embeddings({"input": bad})
            assert out["error"]["type"] == "invalid_request_error", bad
        # over-length input: context error, not silent tail truncation
        out = server.embeddings({"input": "x" * 500})
        assert out["error"]["type"] == "invalid_request_error"
        assert "maximum context" in out["error"]["message"]
    finally:
        server.stop()


# ----------------------------------------------------- logit_bias

def test_logit_bias_forces_and_bans_tokens():
    engine = tiny_engine(max_batch=2)
    [base] = engine.generate([[1, 2, 3]], max_tokens=6)
    # +100 on one id forces greedy decoding to emit it every step
    forced = 7
    req = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6,
        logit_bias={forced: 100.0}))
    while not req.done:
        engine.step()
    assert req.output_ids == [forced] * 6
    # -100 on the unbiased path's first token bans it
    req2 = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6,
        logit_bias={int(base[0]): -100.0}))
    while not req2.done:
        engine.step()
    assert base[0] not in req2.output_ids
    # a biased and an unbiased request share a batch without bleed
    r_biased = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6,
        logit_bias={forced: 100.0}))
    r_plain = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6))
    while not (r_biased.done and r_plain.done):
        engine.step()
    assert r_biased.output_ids == [forced] * 6
    assert r_plain.output_ids == base


def test_logit_bias_in_multi_step_and_chunked():
    forced = 9
    for kw in ({"multi_step": 3}, {"chunked_prefill_tokens": 4}):
        engine = tiny_engine(max_batch=1, **kw)
        req = engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3, 4, 5], max_tokens=5,
            logit_bias={forced: 100.0}))
        while not req.done:
            engine.step()
        assert req.output_ids == [forced] * 5, kw


def test_logit_bias_validation():
    engine = tiny_engine(max_batch=1)
    with pytest.raises(ValueError, match="outside vocab"):
        engine.add_request(GenerationRequest(
            prompt_ids=[1], logit_bias={99999: 1.0}))

    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="lb", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=4))
    try:
        for bad in ([1, 2], {"x": 1.0}, {"5": "no"}, {"500": 1.0}):
            out = server.completions({"prompt": "a", "logit_bias": bad})
            assert out["error"]["type"] == "invalid_request_error", bad
        # happy path end-to-end through the OpenAI surface
        ok = server.completions({"prompt": "hi", "max_tokens": 3,
                                 "logit_bias": {"65": 100.0}})
        assert ok["choices"][0]["text"] == "AAA"  # byte tokenizer: 65='A'
    finally:
        server.stop()


def test_logit_bias_chat_and_stream_paths():
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="lb2", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=4))
    try:
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3, "logit_bias": {"66": 100.0}})
        assert out["choices"][0]["message"]["content"] == "BBB"
        chunks = list(server.completions({
            "prompt": "hi", "max_tokens": 3, "stream": True,
            "logit_bias": {"67": 100.0}}))
        text = "".join(
            __import__("json").loads(c[len("data: "):])
            ["choices"][0]["text"]
            for c in chunks if c.startswith("data: ")
            and "[DONE]" not in c)
        assert text == "CCC"
        # invalid bias reaches prefill_only-style callers too
        import pytest as _pt
        with _pt.raises(ValueError, match="outside vocab"):
            server.engine.prefill_only([1, 2], logit_bias={999: 1.0})
    finally:
        server.stop()


# ----------------------------------------------------- stop strings

def test_stop_strings_non_streaming():
    from ray_tpu.llm.tokenizer import get_tokenizer
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="stops", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=12))
    tok = get_tokenizer(None)
    try:
        base = server.completions({"prompt": "hi", "max_tokens": 12})
        full = base["choices"][0]["text"]
        assert len(full) >= 4
        stop_s = full[2:4]  # a substring the model WILL produce
        out = server.completions({"prompt": "hi", "max_tokens": 12,
                                  "stop": stop_s})
        assert out["choices"][0]["text"] == full[:full.find(stop_s)]
        assert out["choices"][0]["finish_reason"] == "stop"
        # fewer tokens decoded than the unstopped run (early cancel)
        assert out["usage"]["completion_tokens"] <= \
            base["usage"]["completion_tokens"]
        # stop list + validation
        bad = server.completions({"prompt": "x", "stop": ["a"] * 5})
        assert bad["error"]["type"] == "invalid_request_error"
        bad = server.completions({"prompt": "x", "stop": [""]})
        assert bad["error"]["type"] == "invalid_request_error"
    finally:
        server.stop()


def test_stop_strings_streaming_never_leak():
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="stops2", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=12))
    try:
        base = server.completions({"prompt": "hi", "max_tokens": 12})
        full = base["choices"][0]["text"]
        stop_s = full[3:5]
        chunks = list(server.completions({
            "prompt": "hi", "max_tokens": 12, "stream": True,
            "stop": stop_s}))
        import json as _json
        text = "".join(
            _json.loads(c[len("data: "):])["choices"][0]["text"]
            for c in chunks if c.startswith("data: ")
            and "[DONE]" not in c)
        assert stop_s not in text
        assert text == full[:full.find(stop_s)]
    finally:
        server.stop()


def test_engine_cancel_releases_slot():
    engine = tiny_engine(max_batch=1)
    import queue as _q
    r1 = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=50, stream_queue=_q.Queue()))
    for _ in range(3):
        engine.step()
    engine.cancel(r1, "abort")
    assert r1.finish_reason == "abort"
    n_at_cancel = len(r1.output_ids)
    # a queued request gets the slot and completes
    r2 = engine.add_request(GenerationRequest(prompt_ids=[4, 5],
                                              max_tokens=4))
    while not r2.done:
        engine.step()
    assert len(r2.output_ids) == 4
    assert len(r1.output_ids) == n_at_cancel  # no post-cancel tokens


def test_completions_n_choices():
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="nchoice", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=4, max_seq=64), max_tokens=6))
    try:
        out = server.completions({"prompt": "hi", "max_tokens": 6,
                                  "temperature": 0.9, "top_k": 50,
                                  "n": 3})
        assert [c["index"] for c in out["choices"]] == [0, 1, 2]
        # a sample may hit EOS early, so bound rather than pin counts
        assert 3 <= out["usage"]["completion_tokens"] <= 18
        assert all(isinstance(c["text"], str) for c in out["choices"])
        # chat honors n too
        chat = server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "temperature": 0.9, "top_k": 50, "n": 2})
        assert [c["index"] for c in chat["choices"]] == [0, 1]
        # greedy n>1 is rejected (identical choices would be useless)
        bad = server.completions({"prompt": "x", "n": 3})
        assert bad["error"]["type"] == "invalid_request_error"
        bad = server.completions({"prompt": "x", "n": 99,
                                  "temperature": 0.9})
        assert bad["error"]["type"] == "invalid_request_error"
        # streaming + n>1 is rejected, not silently single-choice
        bad = server.completions({"prompt": "x", "n": 2, "stream": True,
                                  "temperature": 0.9})
        assert bad["error"]["type"] == "invalid_request_error"
    finally:
        server.stop()


# ------------------------------------------- guided decoding (tools /
# response_format; reference surface: openai_api_models.py:14-38 —
# enforcement is the in-tree grammar-mask path in ray_tpu/llm/guided.py)

def _guided_vocab():
    return ByteTokenizer().token_strings()


def _guided_engine(max_batch=2, **kw):
    # vocab 258 so the ByteTokenizer's full id range (incl. specials)
    # fits the constraint's mask rows
    return ContinuousBatchingEngine(EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=max_batch, max_seq=64, **kw))


def _answer_schema():
    return {"type": "object",
            "properties": {"ok": {"type": "boolean"},
                           "n": {"type": "integer"}},
            "required": ["ok", "n"]}


def test_guided_grammar_accepts_and_rejects():
    from ray_tpu.llm.guided import (json_object_constraint,
                                    json_schema_constraint,
                                    tool_call_constraint)
    ts = _guided_vocab()
    c = json_schema_constraint(_answer_schema(), ts)
    assert c.matches('{"ok":true,"n":42}')
    assert c.matches('{"ok":false,"n":-7}')
    assert not c.matches('{"n":1,"ok":true}')   # strict field order
    assert not c.matches('{"ok":1,"n":2}')      # wrong type
    assert c.valid_prefix('{"ok":tr')
    assert not c.valid_prefix('{"ok":yes')
    cj = json_object_constraint(ts, max_depth=3)
    assert cj.matches('{"a":[1,{"b":"c"}],"d":null}')
    assert not cj.matches('[1]')                # JSON mode: object root
    tools = [{"type": "function", "function": {
        "name": "f", "parameters": _answer_schema()}}]
    ct = tool_call_constraint(tools, ts)
    assert ct.matches('{"name":"f","arguments":{"ok":true,"n":1}}')
    assert not ct.matches('{"name":"g","arguments":{}}')
    # unsupported schema keywords fail loudly, not silently
    with pytest.raises(ValueError, match="unsupported"):
        json_schema_constraint({"type": "string", "pattern": "a+"}, ts)


def test_guided_schema_enforced_on_all_engine_paths():
    """Masked decoding yields schema-valid JSON on the dense,
    multi-step, chunked-prefill and speculative(-fallback) paths,
    co-batched with an unguided request."""
    from ray_tpu.llm.guided import json_schema_constraint
    ts = _guided_vocab()
    for kw in ({}, {"multi_step": 3}, {"chunked_prefill_tokens": 4},
               {"draft_model": LlamaConfig.tiny(
                   vocab_size=258, max_seq_len=64,
                   attention="reference", remat=False)}):
        engine = _guided_engine(max_batch=2, **kw)
        c = json_schema_constraint(_answer_schema(), ts)
        guided = engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3], max_tokens=48, guided=c))
        plain = engine.add_request(GenerationRequest(
            prompt_ids=[4, 5], max_tokens=8))
        while not (guided.done and plain.done):
            engine.step()
        text = ByteTokenizer().decode(guided.output_ids)
        obj = json.loads(text)
        assert isinstance(obj["ok"], bool), (kw, text)
        assert isinstance(obj["n"], int), (kw, text)
        assert guided.finish_reason == "stop", (kw, guided.finish_reason)
        assert len(plain.output_ids) == 8, kw


def test_guided_disagg_prefill_to_decode():
    """prefill_only samples the first token under the start-state mask;
    the decode engine re-walks the automaton after adoption."""
    from ray_tpu.llm.guided import json_schema_constraint
    ts = _guided_vocab()
    pre = _guided_engine(max_batch=1)
    dec = _guided_engine(max_batch=1)
    c = json_schema_constraint(_answer_schema(), ts)
    ids = [1, 2, 3]
    ks, vs, plen, tok0 = pre.prefill_only(ids, guided=c)
    req = GenerationRequest(prompt_ids=ids, max_tokens=48, guided=c)
    dec.add_prefilled(req, ks, vs, plen, tok0)
    while not req.done:
        dec.step()
    obj = json.loads(ByteTokenizer().decode(req.output_ids))
    assert isinstance(obj["ok"], bool) and isinstance(obj["n"], int)


def test_guided_json_object_truncation_is_valid_prefix():
    from ray_tpu.llm.guided import json_object_constraint
    ts = _guided_vocab()
    engine = _guided_engine(max_batch=1)
    c = json_object_constraint(ts, max_depth=3)
    req = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=24, guided=c))
    while not req.done:
        engine.step()
    text = ByteTokenizer().decode(req.output_ids)
    assert c.valid_prefix(text), text


def test_guided_vocab_mismatch_fails_fast():
    from ray_tpu.llm.guided import json_schema_constraint
    big_vocab = [chr(i % 256) for i in range(1000)]
    c = json_schema_constraint(_answer_schema(), big_vocab)
    engine = tiny_engine(max_batch=1)
    with pytest.raises(ValueError, match="vocab"):
        engine.add_request(GenerationRequest(prompt_ids=[1], guided=c))


def _guided_server(model_id="guided", max_batch=2):
    # the byte tokenizer spends ~280 tokens on the rendered tool
    # definitions alone and a tool call runs ~60 more, so guided serve
    # tests need real sequence room (the usual tiny engines use 64)
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    return LLMServer(LLMConfig(
        model_id=model_id, engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=512,
                                   attention="reference", remat=False),
            max_batch=max_batch, max_seq=512), max_tokens=96))


_WEATHER_TOOLS = [
    {"type": "function", "function": {
        "name": "get_weather",
        "parameters": {"type": "object",
                       "properties": {"city": {"enum": ["sf", "nyc"]},
                                      "celsius": {"type": "boolean"}},
                       "required": ["city", "celsius"]}}},
    {"type": "function", "function": {"name": "noop"}},
]


def test_openai_tool_calling_forced_and_named():
    server = _guided_server("tools1")
    try:
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "weather please"}],
            "tools": _WEATHER_TOOLS, "tool_choice": "required",
            "max_tokens": 96})
        ch = out["choices"][0]
        assert ch["finish_reason"] == "tool_calls"
        assert ch["message"]["content"] is None
        tc = ch["message"]["tool_calls"][0]
        assert tc["id"].startswith("call_") and tc["type"] == "function"
        assert tc["function"]["name"] in ("get_weather", "noop")
        args = json.loads(tc["function"]["arguments"])
        if tc["function"]["name"] == "get_weather":
            assert args["city"] in ("sf", "nyc")
            assert isinstance(args["celsius"], bool)
        # named tool_choice pins the function
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "tools": _WEATHER_TOOLS,
            "tool_choice": {"type": "function",
                            "function": {"name": "noop"}},
            "max_tokens": 64})
        tc = out["choices"][0]["message"]["tool_calls"][0]
        assert tc["function"]["name"] == "noop"
        assert json.loads(tc["function"]["arguments"]) == {}
        # tool/assistant-tool_calls message roles render into the prompt
        out = server.chat_completions({
            "messages": [
                {"role": "user", "content": "weather?"},
                {"role": "assistant", "tool_calls": [
                    {"id": "call_1", "type": "function",
                     "function": {"name": "get_weather",
                                  "arguments": '{"city":"sf"}'}}]},
                {"role": "tool", "tool_call_id": "call_1",
                 "content": "sunny"}],
            "max_tokens": 4})
        assert "error" not in out
    finally:
        server.stop()


def test_openai_tool_calling_streaming_deltas():
    server = _guided_server("tools2")
    try:
        chunks = list(server.chat_completions({
            "messages": [{"role": "user", "content": "go"}],
            "tools": _WEATHER_TOOLS, "tool_choice": "required",
            "stream": True, "max_tokens": 96}))
        assert chunks[-1] == "data: [DONE]\n\n"
        events = [json.loads(c[len("data: "):]) for c in chunks
                  if c.startswith("data: ") and "[DONE]" not in c]
        tool_deltas = [e["choices"][0]["delta"]["tool_calls"]
                       for e in events
                       if e["choices"][0]["delta"].get("tool_calls")]
        head = tool_deltas[0][0]
        assert head["id"].startswith("call_")
        assert head["function"]["arguments"] == ""
        assert head["function"]["name"] in ("get_weather", "noop")
        args = "".join(d[0]["function"].get("arguments", "")
                       for d in tool_deltas)
        json.loads(args)  # argument deltas concatenate to valid JSON
        assert events[-1]["choices"][0]["finish_reason"] == "tool_calls"
    finally:
        server.stop()


def test_openai_response_format_json_schema_and_object():
    server = _guided_server("rf1")
    try:
        schema = _answer_schema()
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "answer"}],
            "response_format": {
                "type": "json_schema",
                "json_schema": {"name": "ans", "schema": schema}},
            "max_tokens": 48})
        ch = out["choices"][0]
        obj = json.loads(ch["message"]["content"])
        assert isinstance(obj["ok"], bool) and isinstance(obj["n"], int)
        assert ch["finish_reason"] == "stop"
        # streaming: content deltas concatenate to schema-valid JSON
        chunks = list(server.chat_completions({
            "messages": [{"role": "user", "content": "answer"}],
            "response_format": {
                "type": "json_schema",
                "json_schema": {"schema": schema}},
            "stream": True, "max_tokens": 48}))
        text = "".join(
            json.loads(c[len("data: "):])["choices"][0]["delta"]
            .get("content", "")
            for c in chunks
            if c.startswith("data: ") and "[DONE]" not in c)
        json.loads(text)
        # json_object mode works on completions too; output is a valid
        # JSON prefix even when length-truncated
        out = server.completions({
            "prompt": "data:", "max_tokens": 16,
            "response_format": {"type": "json_object"}})
        from ray_tpu.llm.guided import json_object_constraint
        probe = json_object_constraint(ByteTokenizer().token_strings())
        assert probe.valid_prefix(out["choices"][0]["text"])
    finally:
        server.stop()


def test_guided_request_validation():
    server = _guided_server("rfbad", max_batch=1)
    try:
        cases = [
            {"tools": "nope"},
            {"tools": []},
            {"tools": _WEATHER_TOOLS,
             "tool_choice": {"type": "function",
                             "function": {"name": "bogus"}}},
            {"tools": _WEATHER_TOOLS, "tool_choice": "sometimes"},
            {"tool_choice": "required"},
            {"response_format": {"type": "yaml"}},
            {"response_format": {"type": "json_schema"}},
            {"tools": _WEATHER_TOOLS, "tool_choice": "required",
             "response_format": {"type": "json_object"}},
            {"response_format": {
                "type": "json_schema",
                "json_schema": {"schema": {"type": "string",
                                           "pattern": "a+"}}}},
        ]
        for extra in cases:
            out = server.chat_completions(
                {"messages": [{"role": "user", "content": "x"}], **extra})
            assert out.get("error", {}).get("type") == \
                "invalid_request_error", extra
        # tools are chat-only
        out = server.completions({"prompt": "x",
                                  "tools": _WEATHER_TOOLS})
        assert out["error"]["type"] == "invalid_request_error"
    finally:
        server.stop()


def test_guided_response_format_on_disagg_surface(ray_start_shared):
    """response_format rides the serve-level disagg path: the prefill
    replica samples the first token under the start-state mask, the
    decode replica rebuilds the constraint from the spec and re-walks
    the automaton (non-stream and stream)."""
    from ray_tpu import serve
    from ray_tpu.llm.disagg import build_disagg_app
    from ray_tpu.serve.llm import LLMConfig

    cfg = LLMConfig(
        model_id="llama-disagg-guided",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=128,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=128, seed=0),
        max_tokens=64)
    rf = {"type": "json_schema",
          "json_schema": {"schema": _answer_schema()}}
    try:
        app = build_disagg_app(cfg, num_prefill=1, num_decode=1)
        handle = serve.run(app, name="disagg_guided",
                           route_prefix="/llmg")
        got = handle.remote({"__path__": "/v1/completions",
                             "prompt": "answer:", "max_tokens": 64,
                             "response_format": rf}
                            ).result(timeout_s=180)
        assert "error" not in got, got
        obj = json.loads(got["choices"][0]["text"])
        assert isinstance(obj["ok"], bool) and isinstance(obj["n"], int)
        # streaming: deltas concatenate to the same schema-valid JSON
        chunks = list(handle.options(stream=True).remote(
            {"__path__": "/v1/completions",
             "prompt": "answer:", "max_tokens": 64,
             "stream": True, "response_format": rf}))
        text = "".join(
            json.loads(c[len("data: "):])["choices"][0]["text"]
            for c in chunks
            if c.startswith("data: ") and "[DONE]" not in c)
        assert json.loads(text) == obj
        # invalid schema rejected at the router, not a replica blowup
        bad = handle.remote({"__path__": "/v1/completions",
                             "prompt": "x",
                             "response_format": {
                                 "type": "json_schema",
                                 "json_schema": {"schema": {
                                     "type": "string",
                                     "pattern": "a+"}}}}
                            ).result(timeout_s=60)
        assert bad["error"]["type"] == "invalid_request_error"
    finally:
        serve.shutdown()


def test_score_endpoint():
    """/v1/score (reference: openai_api_models.py:123): cosine scores
    of text_1 against each text_2 over pooled embeddings, OpenAI list
    shape, strict validation."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="scorer", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64)))
    try:
        out = server({"__path__": "/v1/score",
                      "text_1": "tpu pods",
                      "text_2": ["tpu pods", "apples"]})
        assert out["object"] == "list"
        assert [d["index"] for d in out["data"]] == [0, 1]
        # identical text scores (numerically) 1.0; all scores bounded
        assert out["data"][0]["score"] == pytest.approx(1.0, abs=1e-3)
        assert all(-1.001 <= d["score"] <= 1.001 for d in out["data"])
        assert out["usage"]["prompt_tokens"] > 0
        # single string text_2 works
        one = server.score({"text_1": "a", "text_2": "b"})
        assert len(one["data"]) == 1
        # validation
        for bad in ({"text_2": ["x"]},
                    {"text_1": "x"},
                    {"text_1": "x", "text_2": []},
                    {"text_1": "x", "text_2": [1]},
                    {"text_1": "y" * 500, "text_2": "x"}):
            out = server.score(bad)
            assert out["error"]["type"] == "invalid_request_error", bad
    finally:
        server.stop()


# --------------------------------------------------- int8 quantization
# (EngineConfig.quantization="int8" -> quantize_llama_ffn ->
#  _ffn int8 path; reference analog: vLLM quantization passthrough,
#  vllm_models.py:214)

def test_quantized_forward_close_to_float():
    import jax
    from ray_tpu.models.llama import (llama_forward, llama_init,
                                      quantize_llama_ffn)
    cfg = LlamaConfig.tiny(max_seq_len=64, attention="reference",
                           remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    qparams = quantize_llama_ffn(params, cfg)
    toks = np.arange(12, dtype=np.int32)[None, :]
    full = np.asarray(llama_forward(params, toks, cfg))
    quant = np.asarray(llama_forward(qparams, toks, cfg))
    # weight-only int8 with per-channel scales: ~1% relative error
    rel = (np.linalg.norm(full - quant)
           / max(np.linalg.norm(full), 1e-9))
    assert rel < 0.05, rel
    # the FFN stacks really are int8 now
    assert qparams["layers"]["w1_q8"].dtype == np.int8
    assert "w1" not in qparams["layers"]


def test_quantized_engine_serves():
    engine = tiny_engine(max_batch=2, quantization="int8")
    ref = tiny_engine(max_batch=2)
    out_q = engine.generate([[1, 2, 3], [7, 8]], max_tokens=8)
    out_f = ref.generate([[1, 2, 3], [7, 8]], max_tokens=8)
    assert [len(o) for o in out_q] == [8, 8]
    # greedy argmax is stable under ~1% logit error for most steps;
    # require the prefixes to agree rather than full equality
    assert out_q[0][:2] == out_f[0][:2]
    # deterministic across engines with the same seed + quantization
    engine2 = tiny_engine(max_batch=2, quantization="int8")
    assert engine2.generate([[1, 2, 3]], max_tokens=8)[0] == out_q[0]


def test_quantization_validation_and_serve_config():
    with pytest.raises(ValueError, match="quantization"):
        tiny_engine(quantization="fp4")
    moe = LlamaConfig.tiny_moe(max_seq_len=64, attention="reference",
                               remat=False)
    with pytest.raises(ValueError, match="dense"):
        ContinuousBatchingEngine(EngineConfig(
            model=moe, max_batch=1, max_seq=64, quantization="int8"))
    # the flag rides LLMConfig.engine into a serving replica
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="q8", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64, quantization="int8"),
        max_tokens=4))
    try:
        out = server.completions({"prompt": "hi", "max_tokens": 3})
        assert "error" not in out
        assert "w1_q8" in server.engine.params["layers"]
    finally:
        server.stop()


def test_llm_combined_saturation():
    """Cross-feature interference test (VERDICT r4 item 6): spec
    decode, prefix caching, chunked prefill + multi-step, guided
    decoding, stop-string cancellation and n-choices run CONCURRENTLY
    through one multi-model multiplex server under slot-recycling
    load; greedy outputs must equal the single-feature baselines and
    engine stats must show no slot/cache leaks afterwards. (LRU
    eviction chaos is covered by test_multiplex_eviction_stops_engine;
    here the 3 models stay resident so baselines stay deterministic.)
    """
    import concurrent.futures as cf

    from ray_tpu.serve.llm import LLMConfig, LLMServer, MultiplexLLMServer

    def model258(**kw):
        return LlamaConfig.tiny(vocab_size=258, max_seq_len=128,
                                attention="reference", remat=False, **kw)

    draft258 = LlamaConfig.tiny(vocab_size=258, max_seq_len=128,
                                attention="reference", remat=False,
                                dim=32, n_layers=1, n_heads=2,
                                n_kv_heads=1, hidden_dim=64)

    def cfgs():
        return [
            LLMConfig(model_id="spec", engine=EngineConfig(
                model=model258(), draft_model=draft258, spec_tokens=4,
                max_batch=2, max_seq=128, seed=1), max_tokens=10),
            LLMConfig(model_id="prefix", engine=EngineConfig(
                model=model258(), enable_prefix_caching=True,
                prefix_cache_min_tokens=8, prefix_cache_entries=4,
                max_batch=2, max_seq=128, seed=2), max_tokens=10),
            LLMConfig(model_id="chunked", engine=EngineConfig(
                model=model258(), chunked_prefill_tokens=8,
                max_batch=2, max_seq=128, seed=3), max_tokens=10),
        ]

    system = "You are a helpful assistant speaking briefly. "
    prompts = {
        "spec": [f"alpha {i}" for i in range(6)],
        "prefix": [system + f"question {i}" for i in range(6)],
        "chunked": [f"a long prompt padding padding {i}" for i in range(6)],
    }

    # single-feature baselines: solo servers, same configs/seeds
    baselines = {}
    for cfg in cfgs():
        solo = LLMServer(cfg)
        try:
            baselines[cfg.model_id] = [
                solo.completions({"prompt": p, "max_tokens": 10})
                ["choices"][0]["text"]
                for p in prompts[cfg.model_id]]
        finally:
            solo.stop()

    mux = MultiplexLLMServer(cfgs(), max_models_per_replica=3)
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"]}

    def plain(model, prompt):
        out = mux({"__path__": "/v1/completions", "model": model,
                   "prompt": prompt, "max_tokens": 10})
        assert "error" not in out, out
        return ("plain", model, prompt, out["choices"][0]["text"])

    def stopped(model, prompt):
        # stop strings drive the engine.cancel path mid-batch
        out = mux({"__path__": "/v1/completions", "model": model,
                   "prompt": prompt, "max_tokens": 10,
                   "stop": [baselines[model][0][:2] or "zz"]})
        assert "error" not in out, out
        return ("stopped", model, prompt, out["choices"][0]["text"])

    def guided(model):
        out = mux({"__path__": "/v1/chat/completions", "model": model,
                   "messages": [{"role": "user", "content": "answer"}],
                   "response_format": {
                       "type": "json_schema",
                       "json_schema": {"schema": schema}},
                   "max_tokens": 24})
        assert "error" not in out, out
        obj = json.loads(out["choices"][0]["message"]["content"])
        assert isinstance(obj["ok"], bool)
        return ("guided", model, None, None)

    def sampled_n(model):
        out = mux({"__path__": "/v1/completions", "model": model,
                   "prompt": "sample", "max_tokens": 6,
                   "temperature": 0.9, "top_k": 50, "n": 2})
        assert "error" not in out, out
        assert len(out["choices"]) == 2
        return ("n", model, None, None)

    jobs = []
    with cf.ThreadPoolExecutor(max_workers=12) as pool:
        for model, plist in prompts.items():
            for p in plist:
                jobs.append(pool.submit(plain, model, p))
            jobs.append(pool.submit(stopped, model, plist[0]))
            jobs.append(pool.submit(guided, model))
            jobs.append(pool.submit(sampled_n, model))
        results = [j.result(timeout=300) for j in jobs]

    # greedy outputs under full concurrency == solo baselines
    for kind, model, prompt, text in results:
        if kind == "plain":
            want = baselines[model][prompts[model].index(prompt)]
            assert text == want, (model, prompt, text, want)
        elif kind == "stopped":
            # the stop string never leaks into the returned text
            assert baselines[model][0][:2] not in text

    # no slot / queue / cache leaks on any engine
    for model in prompts:
        server = mux._load(model)
        stats = server.engine.stats()
        assert stats["active"] == 0, (model, stats)
        assert stats["waiting"] == 0, (model, stats)
        assert stats.get("prefilling", 0) == 0, (model, stats)
        assert stats["total_generated"] > 0
        if model == "prefix":
            assert stats["prefix_cache_entries"] <= 4
            assert stats["prefix_hits"] >= 1  # shared system prompt hit
        server.stop()


# ------------------------------------- presence / frequency penalties

def test_penalties_break_repetition_and_validate():
    """frequency_penalty makes a greedily repeating token pay per
    occurrence until another token wins (reference: OpenAI sampling
    params via vLLM SamplingParams); implemented on the per-step
    bias-row refresh machinery."""
    engine = tiny_engine(max_batch=2)
    forced = 7
    # logit_bias pins greedy decoding to one token...
    rep = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=8,
        logit_bias={forced: 20.0}))
    while not rep.done:
        engine.step()
    assert rep.output_ids == [forced] * 8
    # ...and a frequency penalty overcomes the same bias after a few
    # occurrences (engine level is unclamped; the serve layer enforces
    # the OpenAI [-2, 2] range)
    pen = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=8,
        logit_bias={forced: 20.0}, frequency_penalty=6.0))
    plain = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=8,
        logit_bias={forced: 20.0}))
    while not (pen.done and plain.done):
        engine.step()
    assert pen.output_ids != [forced] * 8
    assert forced in pen.output_ids  # started repeating, then broke
    assert plain.output_ids == [forced] * 8  # co-batched, no bleed
    # presence penalty: one-shot, weaker than per-occurrence
    pres = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6,
        logit_bias={forced: 1.0}, presence_penalty=2.0))
    while not pres.done:
        engine.step()
    assert pres.output_ids[0] != pres.output_ids[1] or \
        pres.output_ids.count(forced) <= 1


def test_penalties_force_dense_fallback_and_serve_surface():
    # multi_step engine: penalized requests take the dense path and
    # still apply the penalty per token
    engine = tiny_engine(max_batch=1, multi_step=4)
    forced = 9
    req = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=8,
        logit_bias={forced: 20.0}, frequency_penalty=6.0))
    while not req.done:
        engine.step()
    assert req.output_ids != [forced] * 8
    # serve surface: accepted on completions + chat, validated
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="pen", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=8))
    try:
        out = server.completions({
            "prompt": "hi", "max_tokens": 8,
            "logit_bias": {"65": 5.0}, "frequency_penalty": 2.0})
        assert "error" not in out
        assert out["choices"][0]["text"] != "A" * 8
        for bad in ("x", 3.0, -2.5, float("nan")):
            out = server.completions({"prompt": "x",
                                      "presence_penalty": bad})
            assert out["error"]["type"] == "invalid_request_error", bad
    finally:
        server.stop()


# ----------------------------------------------------------- logprobs

def test_engine_logprobs_greedy_consistency():
    """Greedy decoding with logprobs: the chosen token is the top-1 of
    the recorded distribution, every entry has the requested top-k,
    and values are valid log-probabilities."""
    engine = tiny_engine(max_batch=2)
    req = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6, logprobs=3))
    plain = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6))
    while not (req.done and plain.done):
        engine.step()
    # logprob requests produce identical greedy tokens
    assert req.output_ids == plain.output_ids
    assert len(req.logprob_data) == 6  # prefill token + 5 decodes
    for e, tok in zip(req.logprob_data, req.output_ids):
        assert e["id"] == tok
        assert len(e["top"]) == 3
        assert e["top"][0][0] == tok  # greedy = top-1
        assert e["logprob"] == pytest.approx(e["top"][0][1], abs=1e-4)
        assert e["logprob"] <= 1e-6  # log prob <= 0
    assert plain.logprob_data == []
    # fused paths fall back to dense while a logprob request is active
    eng2 = tiny_engine(max_batch=1, multi_step=4)
    r2 = eng2.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3], max_tokens=6, logprobs=2))
    while not r2.done:
        eng2.step()
    assert r2.output_ids == req.output_ids
    assert len(r2.logprob_data) == 6
    # disagg decode path rejects logprobs loudly
    with pytest.raises(ValueError, match="disagg"):
        engine.add_prefilled(GenerationRequest(
            prompt_ids=[1], logprobs=1), None, None, 1, 0)


def test_openai_logprobs_surface():
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="lp", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64), max_tokens=6))
    try:
        # completions shape: logprobs: int
        out = server.completions({"prompt": "hi", "max_tokens": 4,
                                  "logprobs": 2})
        lp = out["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == len(lp["token_logprobs"])
        assert all(len(t) <= 2 for t in lp["top_logprobs"])
        assert lp["text_offset"][0] == 0
        # chat shape: logprobs: true + top_logprobs
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "logprobs": True, "top_logprobs": 3})
        content = out["choices"][0]["logprobs"]["content"]
        assert content and all(len(e["top_logprobs"]) == 3
                               for e in content)
        assert all(isinstance(e["bytes"], list) for e in content)
        # validation
        for bad in ({"logprobs": 9},
                    {"logprobs": True, "top_logprobs": 50},
                    {"top_logprobs": 3}):
            out = server.completions({"prompt": "x", **bad})
            assert out["error"]["type"] == "invalid_request_error", bad
        out = server.completions({"prompt": "x", "logprobs": 2,
                                  "stream": True})
        assert out["error"]["type"] == "invalid_request_error"
    finally:
        server.stop()


def test_logprobs_zero_top_and_stop_truncation():
    """OpenAI edge semantics: logprobs=0 / top_logprobs=0 record the
    CHOSEN token's logprob with an empty top list, and with stop
    strings the logprobs object covers exactly the returned text."""
    from ray_tpu.llm.tokenizer import get_tokenizer
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="lp0", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=8))
    try:
        out = server.completions({"prompt": "hi", "max_tokens": 4,
                                  "logprobs": 0})
        lp = out["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == 4
        assert all(t == {} for t in lp["top_logprobs"])
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3, "logprobs": True, "top_logprobs": 0})
        content = out["choices"][0]["logprobs"]["content"]
        assert content and all(e["top_logprobs"] == [] for e in content)
        # stop truncation: logprobs tokens rebuild exactly the text
        tok = get_tokenizer(None)
        base = server.completions({"prompt": "go", "max_tokens": 8})
        text8 = base["choices"][0]["text"]
        if len(text8) >= 3:
            stop = text8[2]
            out = server.completions({"prompt": "go", "max_tokens": 8,
                                      "logprobs": 1, "stop": [stop]})
            text = out["choices"][0]["text"]
            lp = out["choices"][0]["logprobs"]
            rebuilt = "".join(lp["tokens"])
            assert rebuilt.startswith(text)
            assert len(rebuilt) <= len(text) + 4  # no post-stop tail
    finally:
        server.stop()


def test_max_completion_tokens_and_stream_usage():
    """Newer OpenAI chat param names: max_completion_tokens aliases
    max_tokens; stream_options.include_usage appends a usage-only
    chunk (choices: []) before [DONE]."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    server = LLMServer(LLMConfig(
        model_id="so", engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=1, max_seq=64), max_tokens=16))
    try:
        out = server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "max_completion_tokens": 3})
        assert out["usage"]["completion_tokens"] == 3
        chunks = list(server.chat_completions({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "stream": True,
            "stream_options": {"include_usage": True}}))
        assert chunks[-1] == "data: [DONE]\n\n"
        events = [json.loads(c[len("data: "):]) for c in chunks[:-1]
                  if c.startswith("data: ")]
        assert events[-1]["choices"] == []
        u = events[-1]["usage"]
        assert u["completion_tokens"] == 4
        assert u["total_tokens"] == u["prompt_tokens"] + 4
        # completions stream too
        chunks = list(server.completions({
            "prompt": "hi", "max_tokens": 3, "stream": True,
            "stream_options": {"include_usage": True}}))
        events = [json.loads(c[len("data: "):]) for c in chunks[:-1]
                  if c.startswith("data: ")]
        assert events[-1]["usage"]["completion_tokens"] == 3
        # stream_options without stream is rejected
        out = server.completions({"prompt": "x",
                                  "stream_options": {
                                      "include_usage": True}})
        assert out["error"]["type"] == "invalid_request_error"
    finally:
        server.stop()
