"""The LFM2-MoE family (gated short-convolution mixers beside attention
layers with QK-norm and rotary, leading dense feed-forwards, then a
routed one behind a sigmoid router with a selection bias): the model
and the engine's dense path with its device counts, held to the plain
reference (benchmark/reference/lfm2.py) in float32 at tiny sizes:
hidden 64, five layers (conv, attention, conv, conv, attention) of
which the first is dense, 4 heads of 16 over 2 KV heads, 8 experts
top-3, a bias that moves picks, vocabulary 512."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2 as reference
from ray_tpu.llm.engine import (ContinuousBatchingEngine, EngineConfig,
                                GenerationRequest)
from ray_tpu.models import lfm2
from ray_tpu.models.family import family_of
from ray_tpu.models.lfm2 import (EXPERT_COUNTS, Lfm2Config, lfm2_forward,
                                 lfm2_init, lfm2_init_cache, lfm2_prefill)
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import attention as attention_op

CFG = Lfm2Config.tiny(dtype=jnp.float32)
# heads of 64, the published size: the cache keeps two KV heads a row
# of 128 lanes and the decode kernel (interpret mode) reads it so
WIDE_CFG = Lfm2Config.tiny(dtype=jnp.float32, dim=256)
TOL = 1e-4


def _init(cfg):
    return jax.jit(lfm2_init, static_argnums=1)(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


def _engine(params, cfg=CFG, **kw):
    return ContinuousBatchingEngine(
        EngineConfig(model=cfg, max_batch=3, max_seq=128, **kw),
        params=params)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


def _reference_logprobs(params, ids, n_out, cfg=CFG):
    """The reference's log-probability of each of the last ``n_out``
    tokens of ``ids``, from one full forward pass."""
    seq = jnp.asarray(ids, jnp.int32)
    logp = jax.nn.log_softmax(reference.logits(
        params, seq[:-1], **reference.kwargs_from(cfg)), -1)
    at = np.arange(len(ids) - 1 - n_out, len(ids) - 1)
    return np.asarray(logp[at, seq[at + 1]])


def test_config_keeps_the_published_pattern():
    assert CFG.layer_kinds == ("conv+dense", "attn+moe", "conv+moe",
                               "conv+moe", "attn+moe")
    assert CFG.runs == (("conv", "dense", 0, 0, 1), ("attn", "moe", 0, 0, 1),
                        ("conv", "moe", 1, 1, 2), ("attn", "moe", 1, 3, 1))
    full = Lfm2Config()
    assert [i for i, t in enumerate(full.layer_types)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert (full.n_layers, full.n_conv_layers, full.n_attn_layers,
            full.n_moe_layers) == (24, 18, 6, 22)
    # the benchmark's cut: layer_types[0:14]
    cut = Lfm2Config(layer_types=full.layer_types[:14])
    assert cut.layer_kinds[:2] == ("conv+dense",) * 2
    assert cut.layer_kinds[2:] == ("attn+moe", "conv+moe", "conv+moe",
                                   "conv+moe") * 3
    assert (cut.n_dense_layers, cut.n_attn_layers, cut.n_conv_layers,
            cut.n_moe_layers) == (2, 3, 9 + 2, 12)
    assert (full.head_dim, full.n_experts, full.top_k, full.expert_dim,
            full.dense_dim, full.conv_taps) == (64, 32, 4, 1792, 7168, 3)
    assert full.scoring.kind == "sigmoid" and full.scoring.eps == 1e-6
    family = family_of(CFG)
    assert family.dense_only and not family.skips_parked_state
    assert family.expert_counts == EXPERT_COUNTS
    assert EXPERT_COUNTS[-2:] == ("picks_bias_moved", "picks_bias_kept")
    with pytest.raises(ValueError, match="layer types"):
        Lfm2Config.tiny(layer_types=("conv", "mamba"))


def test_forward_matches_the_reference(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 512)
    got = jax.jit(lambda p, t: lfm2_forward(p, t, CFG))(params, tokens)
    for i in range(2):
        want = reference.logits(params, tokens[i],
                                **reference.kwargs_from(CFG))
        assert float(jnp.abs(got[i] - want).max()) < TOL


@pytest.mark.parametrize("length,wide", [
    (2, False), (5, False), (16, False), (37, False), (100, False),
    (37, True), (100, True)])
def test_engine_prefill_then_decode_matches_the_reference(
        params, length, wide, monkeypatch):
    """A bucketed prefill told the prompt's true length (5, 37 and 100
    are shorter than their buckets of 8, 64 and 128; 16 fills its own;
    2 is shorter than the convolution's three taps), then whole-batch
    decode steps with two parked slots: every token's log-probability
    against the reference's one full pass. ``wide``: heads of 64, the
    cache's rows packed to 128 lanes, the decode kernel in interpret
    mode."""
    cfg = CFG
    if wide:
        monkeypatch.setattr(attention_op, "_INTERPRET", True)
        cfg, params = WIDE_CFG, _init(WIDE_CFG)
        assert lfm2_init_cache(cfg, 3, 128)["k"].shape == (2, 3, 128, 1, 128)
    engine = _engine(params, cfg)
    # the engine counts the rows the decode kernel reads by the blocks
    # of the cache as this family stores it
    assert engine._kv_block == (128 if wide else engine.config.max_seq)
    ids = _prompt(length, seed=length)
    request = engine.add_request(GenerationRequest(
        prompt_ids=ids, max_tokens=20, logprobs=0))
    while engine.has_work():
        engine.step()
    assert request.error is None and len(request.output_ids) == 20
    got = [e["logprob"] for e in request.logprob_data]
    want = _reference_logprobs(params, ids + request.output_ids, 20, cfg)
    assert np.abs(np.asarray(got) - want).max() < TOL
    assert engine._decode._cache_size() == 1
    assert engine.stats()["dropped_rows"] == 0


def test_padding_leaves_the_state_of_the_true_last_token(params):
    """The same prompt through two buckets: the cache entry (the
    convolution's two columns, the K/V rows of the prompt), the logits
    and the expert counts do not see the padding."""
    ids = _prompt(21, seed=3)
    outs = []
    for bucket in (32, 64):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :21] = ids
        outs.append(jax.jit(lambda p, t, n: lfm2_prefill(p, t, n, CFG))(
            params, padded, np.int32(21)))
    (logits_a, a, counts_a), (logits_b, b, counts_b) = outs
    assert float(jnp.abs(logits_a - logits_b).max()) < 1e-5
    assert a["conv"].shape == (3, 1, 2, 64)
    assert float(jnp.abs(a["conv"] - b["conv"]).max()) < 1e-5
    assert float(jnp.abs(a["conv"]).max()) > 0
    for leaf in ("k", "v"):
        assert float(jnp.abs(a[leaf][:, :, :21]
                             - b[leaf][:, :, :21]).max()) < 1e-5
    # the places walked alone see the padding: none in the bucket of 32
    # (the few-rows form), one chunk of 64 x 3 a routed layer in the
    # bucket of 64
    walked = EXPERT_COUNTS.index("pairs_walked")
    assert (int(counts_a[walked]), int(counts_b[walked])) == (0, 4 * 64 * 3)
    counts_a, counts_b = (np.delete(np.asarray(c), walked)
                          for c in (counts_a, counts_b))
    # 21 positions x 4 routed layers x 3 picks, wherever the padding
    # ends; all 8 experts are held, every pick computed, and a prefill
    # counts no expert slots
    assert counts_a.tolist() == counts_b.tolist()
    held, absent, computed, hit, idle, moved, kept = counts_a.tolist()
    assert (held, absent, computed, hit, idle) == (21 * 4 * 3, 0,
                                                   21 * 4 * 3, 0, 0)
    assert moved + kept == held and 0 < moved < kept


def _leave_out_of_q_and_k(monkeypatch, part):
    """The program and the reference without the per-head norms, or
    without rotary: neither has an option for it, so both lose the same
    line of what they do to ``q`` and ``k``."""
    def program(c):
        def qk(p, q, k, positions):
            if part != "qk_norm":
                q = lfm2.rms_norm(q, p["q_norm"], c.norm_eps)
                k = lfm2.rms_norm(k, p["k_norm"], c.norm_eps)
            if part != "rope":
                cos, sin = lfm2.rope_at(positions, c.head_dim, c.rope_theta)
                q, k = (lfm2.apply_rope(x[None], cos, sin)[0]
                        for x in (q, k))
            return q, k
        return qk

    def plain(q, k, layer, eps, theta):
        if part != "qk_norm":
            q = reference._rms_norm(q, layer["q_norm"], eps)
            k = reference._rms_norm(k, layer["k_norm"], eps)
        if part != "rope":
            q, k = reference._rope(q, theta), reference._rope(k, theta)
        return q, k

    monkeypatch.setattr(lfm2, "_norm_rope", program)
    monkeypatch.setattr(reference, "_norm_rope", plain)


@pytest.mark.parametrize("leaves_out", ["bias_dropped", "conv_tap_zeroed",
                                        "qk_norm", "rope", "routed_scaling"])
def test_each_part_moves_the_output_as_the_reference_says(
        params, leaves_out, monkeypatch):
    """What the program ignored would leave its logits where they were:
    the selection bias, the convolution's oldest tap, the per-head
    norms of q and k (given weights that are not 1, or they would only
    scale), rotary and the gates' scale each move them, to where the
    reference's go."""
    params = {**params, "attn": {
        **params["attn"],
        "q_norm": 1.0 + 0.5 * jnp.sin(jnp.arange(32.0)).reshape(2, 16),
        "k_norm": 1.0 + 0.5 * jnp.cos(jnp.arange(32.0)).reshape(2, 16)}}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, 512)
    base = jax.jit(lambda p, t: lfm2_forward(p, t, CFG))(params, tokens)
    want_base = reference.logits(params, tokens[0],
                                 **reference.kwargs_from(CFG))
    assert float(jnp.abs(base[0] - want_base).max()) < TOL
    cfg, changed = CFG, params
    if leaves_out == "bias_dropped":
        changed = {**params, "moe": {
            **params["moe"],
            "router_bias": jnp.zeros_like(params["moe"]["router_bias"])}}
    elif leaves_out == "conv_tap_zeroed":
        changed = {**params, "conv": {
            **params["conv"],
            "conv_w": params["conv"]["conv_w"].at[:, 0].set(0)}}
    elif leaves_out == "routed_scaling":
        cfg = dataclasses.replace(CFG, routed_scaling=1.0)
    else:
        _leave_out_of_q_and_k(monkeypatch, leaves_out)
    got = jax.jit(lambda p, t: lfm2_forward(p, t, cfg))(changed, tokens)
    want = reference.logits(changed, tokens[0],
                            **reference.kwargs_from(cfg))
    assert float(jnp.abs(got[0] - want).max()) < TOL
    assert float(jnp.abs(got - base).max()) > 1e-2


def test_requests_admitted_at_different_steps_equal_their_solo_outputs(
        params):
    """Two requests of unequal length share the batch from different
    steps on; a third takes the slot the first one left. A parked
    slot's state is written by every step and replaced whole at
    admission."""
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
    ("quantization", {"quantization": "int8"})])
def test_engine_refuses_what_a_consumed_state_cannot_honour(
        params, option, kwargs):
    """Each by name, at construction: the refusals PR 34 wrote apply to
    this family as they stand (its two columns a layer are consumed by
    every step like any recurrent state)."""
    with pytest.raises(ValueError, match=option):
        _engine(params, **kwargs)


def test_stats_and_series_tell_the_picks_the_hit_experts_and_the_bias(
        params):
    """The device counts reach ``stats()`` and the series through the
    metrics flush, the family's own two among them: a live row's picks
    (all held), the experts a live row used in every routed layer of
    every dense decode step, and the picks that the selection bias
    moved (not among the row's 3 largest scores) and kept."""
    from ray_tpu.util import metrics
    engine = _engine(params)
    engine.generate([_prompt(5), _prompt(37)], max_tokens=3)
    stats = engine.stats()
    cache = lfm2_init_cache(CFG, 3, 128)
    assert stats["cache_bytes"] == {
        "kv": cache["k"].nbytes + cache["v"].nbytes,
        "recurrent": cache["conv"].nbytes}
    assert stats["cache_bytes"]["recurrent"] == 3 * 3 * 2 * 64 * 4
    assert stats["prefill_tokens"] == {"real": 42, "pad": 3 + 27}
    assert sorted(stats["programs"]) == ["decode", "prefill_64",
                                         "prefill_8"]
    assert "state_slots" not in stats
    # 42 prompt positions and 2 decode steps of 2 live rows, over 4
    # routed layers of 3 picks a row; nothing is absent
    n_picks = (42 + 2 * 2) * 4 * 3
    assert stats["expert_picks"] == {"held": n_picks, "absent": 0}
    assert stats["dropped_rows"] == 0
    # every pair is held: the bucket of 64 walks all its 64 x 3 places
    # in each routed layer (the bucket of 8 takes the few-rows form)
    assert stats["expert_pairs_walked"] == 4 * 64 * 3
    # 2 decode steps x 4 routed layers x 8 experts; 2 live rows of 3
    # picks hit at most 6 of a layer's 8
    slots = stats["expert_slots"]
    assert slots["hit"] + slots["idle"] == stats["decode_steps"] * 4 * 8
    assert 2 * 4 * 3 <= slots["hit"] <= 2 * 4 * 6
    moved = stats["router_picks"]
    assert moved["moved"] + moved["kept"] == n_picks
    assert 0.02 * n_picks < moved["moved"] < 0.5 * n_picks
    # a second read adds nothing the device has not counted since
    assert engine.stats()["router_picks"] == moved
    text = metrics.prometheus_text()
    for series in ('ray_tpu_engine_router_picks_total{bias="moved"}',
                   'ray_tpu_engine_router_picks_total{bias="kept"}',
                   'ray_tpu_engine_expert_picks_total{where="held"}',
                   'ray_tpu_engine_expert_slots_total{state="hit"}',
                   'ray_tpu_engine_cache_bytes{kind="recurrent"}'):
        assert series in text
    engine.close()


def test_a_family_without_a_bias_tells_no_router_picks():
    """Granite's counts stay the expert layer's six: no
    ``router_picks`` in its stats."""
    from ray_tpu.models.granite import GraniteConfig, granite_init
    cfg = GraniteConfig.tiny(dtype=jnp.float32)
    engine = ContinuousBatchingEngine(
        EngineConfig(model=cfg, max_batch=3, max_seq=128),
        params=jax.jit(granite_init, static_argnums=1)(
            jax.random.PRNGKey(0), cfg))
    engine.generate([_prompt(9)], max_tokens=3)
    stats = engine.stats()
    assert "router_picks" not in stats and stats["expert_picks"]["held"] > 0
    assert len(family_of(cfg).expert_counts) == 6
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
            lfm2_init_cache(CFG, 3, 128))]
    again = engine.generate([_prompt(7)], max_tokens=4)
    assert again == _engine(params).generate([_prompt(7)], max_tokens=4)
