"""The decode-attention kernel (ops/attention.py) in interpret mode
against the XLA form it replaces, and the engine's two families with
and without it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.accelerators import jax_backend
from ray_tpu.llm.engine import (ContinuousBatchingEngine, EngineConfig,
                                GenerationRequest)
from ray_tpu.models.jamba import JambaConfig
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import attention as att

S, HD, LAYERS, LAYER = 1024, 128, 3, 1


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(att, "_INTERPRET", True)


def _case(kvh, n_rep, dtype, pos, seed=0):
    """q, a stacked cache of junk whose other layers and whose blocks
    wholly above a slot's position are NaN, and the same cache with
    zeros there (what the reference may read: it scores every row)."""
    rng = np.random.default_rng(seed)
    batch = len(pos)
    q = rng.standard_normal((batch, kvh, n_rep, HD))
    block = att.decode_block_rows(S, kvh, HD)
    dead = np.arange(S)[None, :] >= (np.asarray(pos)[:, None] // block
                                     + 1) * block                # [B, S]
    caches = []
    for _ in range(2):
        clean = rng.standard_normal((LAYERS, batch, S, kvh, HD))
        clean[LAYER][dead] = 0.0
        poisoned = np.full_like(clean, np.nan)
        poisoned[LAYER] = clean[LAYER]
        poisoned[LAYER][dead] = np.nan
        caches.append((jnp.asarray(poisoned, dtype),
                       jnp.asarray(clean, dtype)))
    (k_nan, k), (v_nan, v) = caches
    return (jnp.asarray(q, dtype), k_nan, v_nan, k, v,
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("kvh,n_rep", [(8, 4), (1, 20), (4, 1)])
def test_kernel_matches_the_reference(interpret, kvh, n_rep, dtype, tol):
    """Slots parked at row 0, on a block's last and first row, on the
    cache's last row and in between share one batch; the layer is in
    the middle of the stack and comes traced, as a layer scan hands it
    over. A kernel that reads a dead block or another layer returns
    NaN."""
    block = att.decode_block_rows(S, kvh, HD)
    assert block is not None and S // block >= 2
    pos = [0, block - 1, block, S - 1, 77, S - block - 1, 3]
    q, k_nan, v_nan, k, v, pos = _case(kvh, n_rep, dtype, pos)
    got = jax.jit(lambda *a: att.decode_attention(*a, dtype))(
        q, k_nan, v_nan, jnp.int32(LAYER), pos)
    want = att._decode_attention_reference(q, k, v, LAYER, pos, dtype)
    assert got.shape == want.shape == q.shape and got.dtype == dtype
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert np.isfinite(err).all() and err.max() < tol


def test_random_positions_and_a_static_layer(interpret):
    """Every slot somewhere else, the layer a Python int as the Jamba
    decode step passes it."""
    pos = np.random.default_rng(7).integers(0, S, 16)
    q, k_nan, v_nan, k, v, pos = _case(8, 4, jnp.float32, pos, seed=7)
    got = att.decode_attention(q, k_nan, v_nan, LAYER, pos, jnp.float32)
    want = att._decode_attention_reference(q, k, v, LAYER, pos, jnp.float32)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("why,shape,dtype", [
    ("head_dim", (2, 2, 128, 2, 64), jnp.float32),
    ("rows", (2, 2, 192, 8, 128), jnp.float32),
    ("dtype", (2, 2, 128, 2, 128), jnp.bfloat16)])
def test_uncovered_shapes_take_the_reference_and_say_so(monkeypatch, why,
                                                        shape, dtype):
    """A head that is no multiple of 128 lanes, rows that do not divide
    into blocks, a query of another type than the cache: the XLA form
    runs, and on a TPU the fall-back is on record."""
    _, batch, rows, kvh, hd = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((batch, kvh, 2, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal(shape), dtype) for _ in "kv")
    pos = jnp.asarray([5, rows - 1], jnp.int32)
    want = att._decode_attention_reference(q, k, v, 1, pos, jnp.float32)
    monkeypatch.setattr(att, "kernel_fallbacks", [])
    got = att.decode_attention(q, k, v, 1, pos, jnp.float32)
    assert att.kernel_fallbacks == []         # the CPU has no kernel
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    monkeypatch.setattr(jax_backend, "on_tpu", lambda: True)
    got = att.decode_attention(q, k, v, 1, pos, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(att.kernel_fallbacks) == 1
    assert att.kernel_fallbacks[0].startswith("decode q[")


def test_block_rows(monkeypatch):
    assert att.decode_block_rows(1024, 8, 128) is None    # no TPU here
    monkeypatch.setattr(att, "_INTERPRET", True)
    assert att.decode_block_rows(1024, 8, 128) == 128
    assert att.decode_block_rows(1024, 1, 128) == 512
    assert att.decode_block_rows(32768, 32, 128) == 128
    assert att.decode_block_rows(128, 1, 128) == 128
    assert att.decode_block_rows(64, 8, 128) == 64
    assert att.decode_block_rows(192, 8, 128) is None
    assert att.decode_block_rows(1024, 8, 64) is None


# --- the engine with and without the kernel ----------------------------

_FAMILIES = {
    # four KV heads of 128, two blocks of 128 rows
    "llama": (LlamaConfig.tiny(dim=512, n_heads=4, n_kv_heads=4,
                               max_seq_len=256), 256),
    # one KV head under two query heads, one block
    "jamba": (JambaConfig.tiny(dim=256, n_heads=2, n_kv_heads=1,
                               dtype=jnp.float32), 128),
}


def _generate(model, max_seq, prompts, n_tokens):
    engine = ContinuousBatchingEngine(
        EngineConfig(model=model, max_batch=4, max_seq=max_seq))
    requests = [engine.add_request(GenerationRequest(
        prompt_ids=ids, max_tokens=n_tokens)) for ids in prompts]
    while engine.has_work():
        engine.step()
    assert all(r.error is None for r in requests)
    return [r.output_ids for r in requests], engine.stats()


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_engine_greedy_tokens_are_the_same_with_the_kernel(monkeypatch,
                                                           family):
    """3 prompts x 24 tokens through ContinuousBatchingEngine in
    float32 with heads of 128: the kernel (interpret mode) and the XLA
    form choose the same tokens, one slot stays parked throughout, and
    the rows read and skipped add up to steps x slots x max_seq."""
    model, max_seq = _FAMILIES[family]
    rng = np.random.default_rng(3)
    vocab = model.vocab_size
    # the second prompt's answer crosses row 128, a block's edge
    prompts = [rng.integers(0, vocab, n).tolist() for n in (9, 120, 40)]
    if max_seq == 128:
        prompts[1] = prompts[1][:90]
    plain, stats_plain = _generate(model, max_seq, prompts, 24)
    monkeypatch.setattr(att, "_INTERPRET", True)
    kernel, stats = _generate(model, max_seq, prompts, 24)
    assert kernel == plain and all(len(ids) == 24 for ids in kernel)
    for got, engaged in ((stats_plain, False), (stats, True)):
        whole = got["decode_steps"] * 4 * max_seq
        assert got["decode_steps"] >= 23
        assert (got["decode_kv_rows_read"]
                + got["decode_kv_rows_skipped"]) == whole
        if not engaged or max_seq == 128:
            assert got["decode_kv_rows_skipped"] == 0
        else:
            # a parked slot and two short ones read one block of two
            assert whole // 2 <= got["decode_kv_rows_read"] < whole
