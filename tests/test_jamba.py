"""The Jamba family (Mamba-1 mixers beside attention layers): the model,
the selective-scan kernel and the engine's dense path over a cache of
two kinds, held to the plain reference (benchmark/reference/jamba.py)
in float32 at tiny sizes: hidden 64, d_inner 128, 16 states, dt rank
4, four layers of which the third is attention, vocabulary 512."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import jamba as reference
from family_contract import *        # noqa: F401,F403 the contract, over ROW
from family_contract import Row, Variant, engine_of, prompt
from ray_tpu.models.jamba import JambaConfig, jamba_forward, jamba_init_cache
from ray_tpu.ops import selective_scan as scan_op

CFG = JambaConfig.tiny(dtype=jnp.float32)
TOL = 1e-4

ROW = Row(reference=reference, forward=jamba_forward,
          variants={"": Variant(CFG)},
          refusal="holds recurrent state that a decode step consumes")


def test_config_places_the_attention_layers():
    assert CFG.layer_kinds == ("mamba", "mamba", "attn", "mamba")
    full = JambaConfig()
    assert [i for i, k in enumerate(full.layer_kinds) if k == "attn"] \
        == [7, 21]
    assert (full.n_mamba_layers, full.n_attn_layers) == (26, 2)
    assert full.runs == (("mamba", 0, 7), ("attn", 0, 1), ("mamba", 7, 13),
                         ("attn", 1, 1), ("mamba", 20, 6))


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


def test_stats_and_series_tell_the_cache_and_the_padding():
    from ray_tpu.util import metrics
    engine = engine_of(CFG)
    engine.generate([prompt(5), prompt(37)], max_tokens=2)
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
