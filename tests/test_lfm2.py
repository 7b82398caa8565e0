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
import pytest

from benchmark.reference import lfm2 as reference
from family_contract import *        # noqa: F401,F403 the contract, over ROW
from family_contract import Row, Variant, engine_of, prompt, weights
from ray_tpu.llm.engine import ContinuousBatchingEngine, EngineConfig
from ray_tpu.models import lfm2
from ray_tpu.models.family import family_of
from ray_tpu.models.lfm2 import (EXPERT_COUNTS, Lfm2Config, lfm2_forward,
                                 lfm2_init_cache)
from ray_tpu.ops import attention as attention_op

CFG = Lfm2Config.tiny(dtype=jnp.float32)
# heads of 64, the published size: the cache keeps two KV heads a row
# of 128 lanes and the decode kernel (interpret mode) reads it so
WIDE_CFG = Lfm2Config.tiny(dtype=jnp.float32, dim=256)
TOL = 1e-4


def _rows_are_packed(engine, stats):
    assert lfm2_init_cache(engine.config.model, 3, 128)["k"].shape \
        == (2, 3, 128, 1, 128)


def _two_columns_a_layer(a, b):
    assert a["conv"].shape == (3, 1, 2, 64)


def _all_held_and_some_moved(count):
    """All 8 experts are held; the bias moved some picks, not most."""
    assert count["picks_absent"] == 0
    moved, kept = count["picks_bias_moved"], count["picks_bias_kept"]
    assert moved + kept == count["picks_held"] and 0 < moved < kept


# 5, 37 and 100 are shorter than their buckets of 8, 64 and 128; 16
# fills its own; 2 is shorter than the convolution's three taps.
# ``wide``: heads of 64, the cache's rows packed to 128 lanes, the
# decode kernel in interpret mode
ROW = Row(reference=reference, forward=lfm2_forward,
          variants={"": Variant(CFG),
                    "wide": Variant(WIDE_CFG, interpret=(attention_op,),
                                    kv_block=128, check=_rows_are_packed)},
          decode_cases=((2, ""), (5, ""), (16, ""), (37, ""), (100, ""),
                        (37, "wide"), (100, "wide")),
          refusal="holds recurrent state that a decode step consumes",
          routed_layers=4, check_entry=_two_columns_a_layer,
          check_prefill_counts=_all_held_and_some_moved)


@pytest.fixture(scope="module")
def params():
    return weights(CFG)


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


def test_stats_and_series_tell_the_picks_the_hit_experts_and_the_bias():
    """The device counts reach ``stats()`` and the series through the
    metrics flush, the family's own two among them: a live row's picks
    (all held), the experts a live row used in every routed layer of
    every dense decode step, and the picks that the selection bias
    moved (not among the row's 3 largest scores) and kept."""
    from ray_tpu.util import metrics
    engine = engine_of(CFG)
    engine.generate([prompt(5), prompt(37)], max_tokens=3)
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
    engine.generate([prompt(9)], max_tokens=3)
    stats = engine.stats()
    assert "router_picks" not in stats and stats["expert_picks"]["held"] > 0
    assert len(family_of(cfg).expert_counts) == 6
    engine.close()
