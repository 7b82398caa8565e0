"""The latent-attention family (the DeepSeek-V3 block: a cache of
latent rows with an expanded and an absorbed attention form over it,
YaRN rotary on a shared key, a leading dense feed-forward, then routed
experts with a shared one behind a sigmoid router with a selection
bias, served as one rank of an expert-parallel group): the model and
the engine's dense path with its device counts, held to the plain
reference (benchmark/reference/mla.py, the expanded form alone) in
float32 at tiny sizes: hidden 64, four layers of which the first is
dense, 4 heads of 16 + 8 over values of 16, a latent of 32, 16 experts
of which the first 4 are held, top-3, a bias that moves picks,
vocabulary 512."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mla as reference
from family_contract import *        # noqa: F401,F403 the contract, over ROW
from family_contract import Row, Variant, engine_of, prompt, weights
from ray_tpu.models import mla
from ray_tpu.models.family import family_of, insert_slot
from ray_tpu.models.mla import (EXPERT_COUNTS, MlaConfig, mla_forward,
                                mla_init_cache)
from ray_tpu.ops import attention as attention_op
from ray_tpu.ops.rope import yarn_inv_freq, yarn_mscale

CFG = MlaConfig.tiny(dtype=jnp.float32)
TOL = 1e-4


def _two_blocks_read(engine, stats):
    """Every step read the live slot's two blocks and a parked slot's
    one (the first read happens with the prompt's last token at
    position 599, in the second block)."""
    steps = stats["decode_steps"]
    assert stats["decode_kv_rows_read"] == steps * 512 * (2 + 2)


def _latent_rows_in_their_slot(a, b):
    """The entry is the prompt's latent rows and nothing else, and
    ``insert_slot`` lands it in its slot and in no other."""
    assert list(a) == ["latent"]
    assert a["latent"].shape == (4, 1, 32, 1, CFG.latent_lanes)
    cache = insert_slot(mla_init_cache(CFG, 3, 128), a, 1)
    assert cache["latent"].shape == (4, 3, 128, 1, CFG.latent_lanes)
    assert float(jnp.abs(cache["latent"][:, 1, :32]
                         - a["latent"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache["latent"][:, [0, 2]]).max()) == 0.0
    assert float(jnp.abs(cache["latent"][:, 1, 32:]).max()) == 0.0


def _a_quarter_held_and_some_moved(count):
    """A quarter of the 16 experts is held; the bias moved some picks,
    not most."""
    held, absent = count["picks_held"], count["picks_absent"]
    moved, kept = count["picks_bias_moved"], count["picks_bias_kept"]
    assert moved + kept == held + absent
    assert 0 < held < absent and 0 < moved < kept


# A prefill runs the expanded form (5, 37 and 100 are shorter than their
# buckets of 8, 64 and 128) and hands the prompt's LATENT rows to the
# slot; the decode steps run the absorbed form. ``flash``: the expanded
# form through the forward kernel (interpret mode), keys of 24 over
# values of 16 padded to 128 lanes, the scale the family's own.
# ``blocks``: the decode kernel in interpret mode over a cache of 1024
# rows, which it reads in blocks of 512: a prompt of 600 spans two
ROW = Row(reference=reference, forward=mla_forward,
          variants={"": Variant(CFG),
                    "flash": Variant(
                        dataclasses.replace(CFG, attention="flash"),
                        interpret=(attention_op,)),
                    "blocks": Variant(CFG, interpret=(attention_op,),
                                      max_seq=1024, new_tokens=6,
                                      kv_block=512, check=_two_blocks_read)},
          forward_cases=(("", 128), ("flash", 128)),
          decode_cases=((5, ""), (37, ""), (100, ""), (600, "blocks")),
          refusal="rows of a latent", routed_layers=3,
          check_entry=_latent_rows_in_their_slot,
          check_prefill_counts=_a_quarter_held_and_some_moved)


@pytest.fixture(scope="module")
def params():
    return weights(CFG)


def _reference_logits(params, tokens, cfg=CFG):
    return reference.logits(params, tokens, **reference.kwargs_from(cfg))


def test_config_holds_the_published_sizes_and_the_scale_holds_m_squared():
    full = MlaConfig()
    assert (full.dim, full.n_layers, full.n_dense_layers, full.n_heads,
            full.q_lora_rank, full.kv_lora_rank, full.qk_head_dim,
            full.v_head_dim, full.dense_dim, full.expert_dim) \
        == (7168, 61, 1, 64, 1536, 512, 192, 128, 18432, 2048)
    assert (full.n_experts, full.top_k, full.routed_scaling) \
        == (384, 8, 2.827)
    # a latent row: 512 + 64 values in 640 lanes
    assert (full.latent_dim, full.latent_lanes) == (576, 640)
    assert full.scoring.kind == "sigmoid" and full.scoring.eps == 1e-20
    # worked by hand: m = 0.1 ln 64 + 1 = 1.41589, 192 ** -0.5 = 0.072169
    assert yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, abs=1e-5)
    assert full.sm_scale == pytest.approx(0.072169 * 2.00474, rel=1e-5)
    assert full.rope_amplitude == 1.0
    # nothing stretched: the key width's scale alone
    plain = dataclasses.replace(full, rope_factor=1.0)
    assert plain.sm_scale == pytest.approx(192 ** -0.5)
    # the benchmark's cut
    cut = MlaConfig(vocab_size=20480, n_layers=7, experts_held=(0, 12))
    assert (cut.n_moe_layers, cut.experts_held) == (6, (0, 12))
    family = family_of(CFG)
    assert family.dense_only and not family.skips_parked_state
    assert family.expert_counts == EXPERT_COUNTS
    assert EXPERT_COUNTS[-2:] == ("picks_bias_moved", "picks_bias_kept")
    assert family.kv_row_shape(full) == (1, 640)
    with pytest.raises(ValueError, match="experts_held"):
        MlaConfig.tiny(experts_held=(12, 8))
    with pytest.raises(ValueError, match="n_dense_layers"):
        MlaConfig.tiny(n_dense_layers=5)


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """Kimi-K2.7-Code's constants: 64 rotary lanes, theta 50000, factor
    64 over 4096 positions, beta 32 and 1. A pair keeps ``f_i = 50000
    ** (-i / 32)`` up to pair 8 (= floor(64 ln(4096 / (32 x 2 pi)) / (2
    ln 50000)) = floor(8.91)), has it divided by 64 from pair 20 (=
    ceil(19.16)) on, and between them the ramp ``(i - 8) / 12``."""
    got = np.asarray(yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0))
    assert got.shape == (32,)
    worked = {0: 1.0,                       # f_0
              8: 0.0668740,                 # f_8 = 50000 ** -0.25
              14: 0.00879429 * (0.5 / 64 + 0.5),  # the ramp's middle
              20: 0.00115649 / 64,          # f_20 = 50000 ** -0.625
              31: 2.80461e-05 / 64}         # f_31 = 50000 ** -0.96875
    for i, want in worked.items():
        assert got[i] == pytest.approx(want, rel=2e-5), i
    f = np.asarray([50000.0 ** (-i / 32) for i in range(32)])
    np.testing.assert_allclose(got[:9], f[:9], rtol=1e-6)
    np.testing.assert_allclose(got[20:], f[20:] / 64, rtol=1e-6)
    assert all(f[i] / 64 < got[i] < f[i] for i in range(9, 20))
    # the reference works its own and agrees
    np.testing.assert_allclose(
        got, reference.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0),
        rtol=1e-6)
    # nothing to stretch: the plain frequencies
    np.testing.assert_allclose(
        yarn_inv_freq(64, 50000.0, 1.0, 4096), f, rtol=1e-6)
    assert math.isclose(reference.mscale(64.0, 1.0), yarn_mscale(64.0))


def test_the_absorbed_form_equals_the_expanded_one(params):
    """One attention layer, the last of 40 positions: the expanded form
    over the sequence and the absorbed form over the latent rows that
    the expanded one left, as a decode step runs it."""
    p = mla._layer(params["attn"], 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, CFG.dim))
    want, latent = mla._attn_sequence(p, x, CFG)
    assert latent.shape == (40, CFG.latent_lanes)
    assert float(jnp.abs(latent[:, CFG.latent_dim:]).max()) == 0.0
    cache = jnp.zeros((4, 2, 64, 1, CFG.latent_lanes))
    cache = cache.at[1, 1, :40, 0].set(latent)
    rows = jnp.stack([x[7], x[39]])
    got, cache_after = mla._attn_decode(p, rows, cache, 1,
                                        jnp.array([7, 39]), CFG)
    assert float(jnp.abs(got[1] - want[39]).max()) < 1e-5
    # the step wrote the row it was given, the same row
    assert float(jnp.abs(cache_after[1, 1, 39, 0] - latent[39]).max()) < 1e-6


@pytest.mark.parametrize("rows", [8, 40])
def test_the_ranks_partial_sums_add_up_to_the_uncut_layer(rows):
    """Four ranks of four experts each compute their part of one routed
    layer for the same rows (8: the few-rows form; 40: the grouped
    product); the parts, with the shared expert that every rank computes
    alike counted once, add up to what the uncut reference gives for
    the whole layer."""
    whole = dataclasses.replace(CFG, experts_held=(0, 16))
    params = weights(whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (rows, whole.dim))
    layer = mla._layer(params["moe"], 1)
    u = reference._rms_norm(x, layer["ff_norm"], whole.norm_eps)
    with jax.default_matmul_precision("highest"):
        routed, _, picks = reference.routed(u, layer, 0, whole.top_k,
                                            whole.routed_scaling)
        shared = reference._gated(u, layer["w_in_s"], layer["w_out_s"])
    parts, held = [], 0
    for rank in range(4):
        cfg = dataclasses.replace(whole, experts_held=(4 * rank, 4))
        mine = {**params, "moe": {
            **params["moe"],
            "w_in_e": params["moe"]["w_in_e"][:, 4 * rank:4 * rank + 4],
            "w_out_e": params["moe"]["w_out_e"][:, 4 * rank:4 * rank + 4]}}
        out, counts = mla._ff(mine, "moe", 1, x, jnp.ones((rows,), bool),
                              cfg)
        parts.append(out - x)
        held += int(counts[0])
        # a rank's own part is the reference's at the same share
        with jax.default_matmul_precision("highest"):
            its, _, its_picks = reference.routed(
                u, mla._layer(mine["moe"], 1), 4 * rank, whole.top_k,
                whole.routed_scaling)
        assert float(jnp.abs(parts[-1] - its - shared).max()) < TOL
        # the reference counts the rank's picks as the program does
        assert int(its_picks.sum()) == int(counts[0])
        assert its_picks.tolist() == picks[4 * rank:4 * rank + 4].tolist()
    assert held == rows * whole.top_k      # every pick on some rank
    total = sum(parts) - 3 * shared        # the shared expert once
    assert float(jnp.abs(total - routed - shared).max()) < TOL
    assert float(jnp.abs(routed).max()) > 0.1


@pytest.mark.parametrize("leaves_out", [
    "bias_dropped", "rope_lanes_zeroed", "mscale_dropped", "yarn_ramp",
    "routed_scaling", "shared_expert"])
def test_each_part_moves_the_output_as_the_reference_says(
        params, leaves_out):
    """What the program ignored would leave its logits where they were:
    the selection bias, the rotary lanes of the latent row, the square
    of YaRN's factor in the scale, the stretched frequencies, the gates'
    scale and the shared expert each move them, to where the
    reference's go."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 512)
    base = jax.jit(lambda p, t: mla_forward(p, t, CFG))(params, tokens)
    cfg, changed = CFG, params

    def with_(stack, **leaves):
        return {**params, stack: {**params[stack], **leaves}}

    if leaves_out == "bias_dropped":
        changed = with_("moe", router_bias=params["moe"]["router_bias"] * 0)
    elif leaves_out == "rope_lanes_zeroed":
        changed = with_("attn", w_kva=params["attn"]["w_kva"]
                        .at[..., -CFG.qk_rope_dim:].set(0))
    elif leaves_out == "mscale_dropped":
        cfg = dataclasses.replace(CFG, rope_mscale_all_dim=0.0,
                                  rope_mscale=0.0)
        assert cfg.sm_scale == pytest.approx(CFG.qk_head_dim ** -0.5)
        assert CFG.sm_scale == pytest.approx(
            cfg.sm_scale * (0.1 * math.log(4.0) + 1) ** 2)
    elif leaves_out == "yarn_ramp":
        cfg = dataclasses.replace(CFG, rope_beta_fast=16.0)
    elif leaves_out == "routed_scaling":
        cfg = dataclasses.replace(CFG, routed_scaling=1.0)
    else:
        changed = with_("moe", w_out_s=params["moe"]["w_out_s"] * 0)
    got = jax.jit(lambda p, t: mla_forward(p, t, cfg))(changed, tokens)
    want = _reference_logits(changed, tokens[0], cfg)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    assert float(jnp.abs(got - base).max()) > 1e-2


def test_stats_and_series_tell_the_latent_cache_the_picks_and_the_bias():
    """The device counts reach ``stats()`` and the series through the
    metrics flush: the latent cache's bytes under a kind of their own, a
    live row's picks by where the expert lives (a quarter held), the
    held experts a live row used in every routed layer of every dense
    decode step, the picks that the selection bias moved, and the rows
    of LATENT cache a step covered."""
    from ray_tpu.util import metrics
    engine = engine_of(CFG)
    engine.generate([prompt(5), prompt(37)], max_tokens=3)
    stats = engine.stats()
    cache = mla_init_cache(CFG, 3, 128)
    assert stats["cache_bytes"] == {"latent": cache["latent"].nbytes}
    assert stats["cache_bytes"]["latent"] == 4 * 3 * 128 * 128 * 4
    assert stats["prefill_tokens"] == {"real": 42, "pad": 3 + 27}
    assert sorted(stats["programs"]) == ["decode", "prefill_64",
                                         "prefill_8"]
    assert "state_slots" not in stats
    # 42 prompt positions and 2 decode steps of 2 live rows, over 3
    # routed layers of 3 picks a row, on 4 held experts of 16
    n_picks = (42 + 2 * 2) * 3 * 3
    picks = stats["expert_picks"]
    assert picks["held"] + picks["absent"] == n_picks
    assert 0.1 * n_picks < picks["held"] < 0.45 * n_picks
    assert stats["dropped_rows"] == 0
    # the bucket of 64: one chunk of 64 x 3 places a routed layer
    assert stats["expert_pairs_walked"] == 3 * 64 * 3
    # 2 decode steps x 3 routed layers x 4 held experts
    slots = stats["expert_slots"]
    assert slots["hit"] + slots["idle"] == stats["decode_steps"] * 3 * 4
    moved = stats["router_picks"]
    assert moved["moved"] + moved["kept"] == n_picks
    assert 0.02 * n_picks < moved["moved"] < 0.5 * n_picks
    # off the TPU the plain form reads every row of every slot
    assert (stats["decode_kv_rows_read"], stats["decode_kv_rows_skipped"]) \
        == (stats["decode_steps"] * 3 * 128, 0)
    # a second read adds nothing the device has not counted since
    assert engine.stats()["router_picks"] == moved
    text = metrics.prometheus_text()
    for series in ('ray_tpu_engine_router_picks_total{bias="moved"}',
                   'ray_tpu_engine_expert_picks_total{where="absent"}',
                   'ray_tpu_engine_expert_slots_total{state="hit"}',
                   'ray_tpu_engine_decode_kv_rows_total{kind="read"}',
                   'ray_tpu_engine_cache_bytes{kind="latent"}'):
        assert series in text
    engine.close()
