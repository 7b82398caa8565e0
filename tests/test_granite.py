"""The Granite-4.0-H family (Mamba-2 mixers beside attention layers, a
routed and a shared feed-forward in every layer, four multipliers), as
one expert-parallel rank holds it: the model, the chunked recurrence,
the expert layer and the engine's dense path with its device counts,
held to the plain reference (benchmark/reference/granite.py) in float32
at tiny sizes: hidden 64, 8 Mamba heads of 16 x 16 states, chunks of
16, four layers of which the third is attention, 8 experts of which
the first 4 are held, top-3, vocabulary 512."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite as reference
from family_contract import *        # noqa: F401,F403 the contract, over ROW
from family_contract import Row, Variant, engine_of, prompt, weights
from ray_tpu.llm.engine import ContinuousBatchingEngine, EngineConfig
from ray_tpu.models.family import family_of
from ray_tpu.models.granite import (GraniteConfig, granite_forward,
                                    granite_init_cache, ssd_chunked)
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import ssd_update as ssd_update_op

CFG = GraniteConfig.tiny(dtype=jnp.float32)
# the narrowest state ``ops.ssd_update``'s kernel covers (128 heads of
# 8 x 128 states): hidden 512, the rest as above
KERNEL_CFG = GraniteConfig.tiny(dtype=jnp.float32, dim=512,
                                mamba_n_heads=128, mamba_d_head=8,
                                mamba_d_state=128)
TOL = 1e-4


def _kernel_covers(covers):
    """After a run: whether the decode step went through the
    ``ssd_update`` kernel (interpret mode) or its ``jax.numpy`` form."""
    def check(engine, stats):
        cfg = engine.config.model
        assert (ssd_update_op.head_block(
            cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)
            is not None) == covers
    return check


# 16 and 64 end on a chunk's boundary (chunks of 16), 5, 37 and 100 do
# not; 5 is shorter than a chunk. ``kernel``: the decode step through
# the ``ssd_update`` kernel at KERNEL_CFG, a parked slot's state left
# where it lies
ROW = Row(reference=reference, forward=granite_forward,
          variants={"": Variant(CFG, check=_kernel_covers(False)),
                    "kernel": Variant(KERNEL_CFG,
                                      interpret=(ssd_update_op,),
                                      check=_kernel_covers(True))},
          decode_cases=((5, ""), (16, ""), (37, ""), (64, ""), (100, ""),
                        (37, "kernel"), (100, "kernel")),
          admission_cases=("", "kernel"),
          refusal="holds recurrent state that a decode step consumes",
          routed_layers=4)


@pytest.fixture(scope="module")
def params():
    return weights(CFG)


def test_config_keeps_the_published_pattern_and_shares():
    assert CFG.layer_kinds == ("mamba", "mamba", "attn", "mamba")
    assert CFG.runs == (("mamba", 0, 2), ("attn", 0, 1), ("mamba", 2, 1))
    full = GraniteConfig()
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert (full.n_mamba_layers, full.n_attn_layers) == (36, 4)
    assert (full.d_inner, full.conv_dim) == (8192, 8448)
    assert full.q_scale == pytest.approx(128 ** 0.5 / 128)
    assert family_of(CFG).dense_only
    with pytest.raises(ValueError, match="experts_held"):
        GraniteConfig.tiny(experts_held=(6, 4))


@pytest.mark.parametrize("seq,length,chunk", [
    (8, 5, 16), (64, 64, 16), (64, 37, 16), (48, 48, 32), (40, 33, 16)])
def test_chunked_recurrence_matches_the_step_by_step_one(seq, length,
                                                         chunk):
    """``ssd_chunked`` against the recurrence one position at a time,
    with positions past ``length`` given ``dt = 0``: their outputs are
    junk nobody reads, the state is that of position length - 1."""
    heads, p, n = 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(seq + length), 5)
    xs = jax.random.normal(keys[0], (seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (seq, heads)) - 2)
    dt = jnp.where(jnp.arange(seq)[:, None] < length, dt, 0.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=0.0,
                                    maxval=2.5))
    b = jax.random.normal(keys[3], (seq, n))
    c = jax.random.normal(keys[4], (seq, n))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    with jax.default_matmul_precision("highest"):
        want_h, want_y = jax.lax.scan(
            step, jnp.zeros((heads, p, n)), (xs, dt, b, c))
        y, h = jax.jit(ssd_chunked, static_argnums=5)(xs, dt, a, b, c,
                                                      chunk)
        h_short, _ = jax.lax.scan(
            step, jnp.zeros((heads, p, n)),
            (xs[:length], dt[:length], b[:length], c[:length]))
    assert float(jnp.abs(y[:length] - want_y[:length]).max()) < TOL
    assert float(jnp.abs(h - want_h).max()) < TOL
    assert float(jnp.abs(h - h_short).max()) < TOL
    assert float(jnp.abs(h).max()) > 1e-3


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 5.0), ("attention_multiplier", 0.25),
    ("residual_multiplier", 0.8), ("logits_scaling", 4.0)])
def test_each_multiplier_moves_the_output_as_the_reference_says(
        params, field, value):
    """A multiplier that the program ignored would leave its logits
    where they were; each moves them, to where the reference's go. The
    attention multiplier is the score scale: it is not ``head_dim **
    -0.5`` (0.25 at these sizes) by default, and is given that here."""
    cfg = dataclasses.replace(CFG, **{field: value})
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, 512)
    base = jax.jit(lambda p, t: granite_forward(p, t, CFG))(params, tokens)
    got = jax.jit(lambda p, t: granite_forward(p, t, cfg))(params, tokens)
    want = reference.logits(params, tokens[0],
                            **reference.kwargs_from(cfg))
    assert float(jnp.abs(got[0] - want).max()) < TOL
    assert float(jnp.abs(got - base).max()) > 1e-2


def test_stats_and_series_tell_the_cache_the_picks_and_the_hit_experts():
    """The device counts reach ``stats()`` and the series through the
    metrics flush: a live row's picks by where the expert lives (parked
    slots and padding not counted), and for every layer of every dense
    decode step the held experts that a live row used and those none
    did. Nothing is dropped."""
    from ray_tpu.util import metrics
    engine = engine_of(CFG)
    engine.generate([prompt(5), prompt(37)], max_tokens=3)
    stats = engine.stats()
    cache = granite_init_cache(CFG, 3, 128)
    assert stats["cache_bytes"] == {
        "kv": cache["k"].nbytes + cache["v"].nbytes,
        "recurrent": cache["ssm"].nbytes + cache["conv"].nbytes}
    assert stats["prefill_tokens"] == {"real": 42, "pad": 3 + 27}
    assert stats["flash_fallbacks"] == []
    assert sorted(stats["programs"]) == ["decode", "prefill_64",
                                         "prefill_8"]
    # 42 prompt positions and 2 decode steps of 2 live rows, over 4
    # layers of 3 picks a row
    picks = stats["expert_picks"]
    assert picks["held"] + picks["absent"] == (42 + 2 * 2) * 4 * 3
    assert 0 < picks["held"] < picks["held"] + picks["absent"]
    # 2 decode steps x 4 layers x 4 held experts; 2 live rows of 3
    # picks cannot hit more than... all four of them
    slots = stats["expert_slots"]
    assert slots["hit"] + slots["idle"] == stats["decode_steps"] * 4 * 4
    assert stats["decode_steps"] == 2 and slots["hit"] > 0
    # the held picks whose product the layer computed are all of them
    assert engine._mbuf.expert_totals["picks_computed"] == picks["held"]
    assert stats["dropped_rows"] == 0
    # the bucket of 64 walks one chunk of its 64 x 3 places in each of
    # 4 layers, padding's too; the bucket of 8 and the decode steps
    # take the few-rows form, which walks none
    assert stats["expert_pairs_walked"] == 4 * 64 * 3
    # a second read adds nothing the device has not counted since
    assert engine.stats()["expert_picks"] == picks
    text = metrics.prometheus_text()
    for series in ('ray_tpu_engine_expert_picks_total{where="held"}',
                   'ray_tpu_engine_expert_pairs_walked_total',
                   'ray_tpu_engine_expert_picks_total{where="absent"}',
                   'ray_tpu_engine_expert_slots_total{state="hit"}',
                   'ray_tpu_engine_cache_bytes{kind="recurrent"}',
                   'ray_tpu_engine_cache_bytes{kind="kv"}'):
        assert series in text
    engine.close()


def _state_slots_series():
    from ray_tpu.util import metrics
    with metrics._registry.lock:
        return {dict(tags)["kind"]: value for (name, tags), value
                in metrics._registry.counters.items()
                if name == "ray_tpu_engine_state_slots_total"}


def test_state_slots_count_what_the_decode_step_moved_and_left_parked():
    """Every dense decode step counts its slots x 3 recurrent layers,
    a live slot's as moved and an empty one's as parked, in ``stats()``
    and in the series; the families whose step moves every slot's state
    (Llama has none, Jamba's ``mamba.update``) emit no such series."""
    from ray_tpu.models.jamba import JambaConfig, jamba_init
    before = _state_slots_series()
    engine = engine_of(CFG)
    engine.generate([prompt(5), prompt(37)], max_tokens=3)
    engine.generate([prompt(9)], max_tokens=4)
    stats = engine.stats()
    # two steps of two live slots of three, then three steps of one
    assert stats["decode_steps"] == 5
    assert stats["state_slots"] == {"moved": (2 * 2 + 3 * 1) * 3,
                                    "parked": (2 * 1 + 3 * 2) * 3}
    assert sum(stats["state_slots"].values()) == 3 * 3 * 5
    after = _state_slots_series()
    assert {kind: after[kind] - before.get(kind, 0.0)
            for kind in after} == stats["state_slots"]
    engine.close()
    for cfg, init in ((LlamaConfig.tiny(vocab_size=512, max_seq_len=128,
                                        attention="reference"), None),
                      (JambaConfig.tiny(dtype=jnp.float32), jamba_init)):
        other = ContinuousBatchingEngine(
            EngineConfig(model=cfg, max_batch=3, max_seq=128),
            params=init and jax.jit(init, static_argnums=1)(
                jax.random.PRNGKey(0), cfg))
        other.generate([prompt(5), prompt(9)], max_tokens=3)
        stats = other.stats()
        assert stats["decode_steps"] > 0
        assert "state_slots" not in stats and other._state_layers == 0
        assert _state_slots_series() == after
        other.close()


def test_the_stepper_never_reads_the_expert_counts(monkeypatch):
    """The counts come to the host with the metrics flush (its thread,
    ``stats()``), not on a step's path: with the flush held off, steps
    run and the totals stay where the last flush left them."""
    engine = engine_of(CFG)
    monkeypatch.setattr(engine._mbuf, "flush_interval_s", 3600.0)
    engine.generate([prompt(9)], max_tokens=4)
    assert set(engine._mbuf.expert_totals.values()) == {0}
    assert int(np.asarray(engine._expert_counts).sum()) > 0
    stats = engine.stats()
    assert sum(stats["expert_picks"].values()) == (9 + 3) * 4 * 3
    engine.close()
