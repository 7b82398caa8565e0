"""Llama model tests: forward/loss/grad, sharded-vs-unsharded parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import (
    LlamaConfig,
    chunked_cross_entropy,
    llama_forward,
    llama_init,
    llama_loss,
    llama_sharding_rules,
)
from ray_tpu.ops import attention
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.sharding import shard_pytree


def _data(cfg, batch=4, seq=32):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0,
                                 cfg.vocab_size)
    return tokens, targets


def test_forward_shapes():
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, _ = _data(cfg)
    logits = llama_forward(params, tokens, cfg)
    assert logits.shape == (4, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_gqa_head_counts():
    cfg = LlamaConfig.tiny(n_heads=4, n_kv_heads=1)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg)
    loss = llama_loss(params, tokens, targets, cfg)
    assert bool(jnp.isfinite(loss))


def test_sharded_matches_unsharded():
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg, batch=8)
    mesh = make_mesh(MeshSpec(data=2, fsdp=2, model=2))
    sharded = shard_pytree(params, mesh, llama_sharding_rules("fsdp_tp"))
    batch_sh = NamedSharding(mesh, P(("data", "fsdp")))
    t_s = jax.device_put(tokens, batch_sh)
    y_s = jax.device_put(targets, batch_sh)
    loss_sharded = jax.jit(
        lambda p, t, y: llama_loss(p, t, y, cfg))(sharded, t_s, y_s)
    loss_ref = llama_loss(params, tokens, targets, cfg)
    np.testing.assert_allclose(float(loss_sharded), float(loss_ref),
                               rtol=1e-4)


def test_grad_step_improves_loss():
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg)

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(
            lambda p_: llama_loss(p_, tokens, targets, cfg))(p)
        p = jax.tree.map(lambda a, g: a - 0.1 * g, p, grads)
        return p, loss

    losses = []
    for _ in range(5):
        params, loss = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_param_count_formula():
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_chunked_cross_entropy_matches_dense():
    """Chunked CE (no [B,S,V] materialization) must match the dense
    loss in value AND gradients, with and without a mask."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    cfg = LlamaConfig.tiny(vocab_size=97)  # odd vocab, exercises padding
    cfg_chunked = dataclasses.replace(cfg, ce_chunk_tokens=13)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 21), 0,
                                cfg.vocab_size)
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 21), 0,
                                 cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (2, 21))
            > 0.3).astype(jnp.float32)

    for m in (None, mask):
        dense, dense_grads = jax.value_and_grad(
            lambda p: llama_loss(p, tokens, targets, cfg, mask=m))(params)
        chunked, chunked_grads = jax.value_and_grad(
            lambda p: llama_loss(p, tokens, targets, cfg_chunked,
                                 mask=m))(params)
        assert jnp.allclose(dense, chunked, rtol=2e-4, atol=2e-4), (
            float(dense), float(chunked), m is not None)
        flat_d = ravel_pytree(dense_grads)[0]
        flat_c = ravel_pytree(chunked_grads)[0]
        assert jnp.allclose(flat_d, flat_c, rtol=5e-3, atol=5e-4), (
            "grad mismatch", float(jnp.abs(flat_d - flat_c).max()))


def _checkpointed_cross_entropy(hidden, lm_head, targets, mask=None, *,
                                chunk_tokens):
    """The loss as it was until PR 62, kept as the plain reference: the
    chunks in a rematerialized scan, so autodiff runs each chunk's head
    product a second time in the backward pass."""
    dim = hidden.shape[-1]
    n = targets.size
    chunk = min(chunk_tokens, n)
    pad = (-n) % chunk
    flat_m = (jnp.ones((n,), jnp.float32) if mask is None
              else mask.reshape(-1).astype(jnp.float32))
    flat_h = jnp.pad(hidden.reshape(n, dim), ((0, pad), (0, 0)))
    flat_t = jnp.pad(targets.reshape(n), (0, pad))
    flat_m = jnp.pad(flat_m, (0, pad))

    def body(carry, inp):
        h_c, t_c, m_c = inp
        logits = (h_c @ lm_head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[:, None], axis=-1)[:, 0]
        return carry + jnp.sum((lse - tgt) * m_c), None

    total, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((), jnp.float32),
        (flat_h.reshape(-1, chunk, dim), flat_t.reshape(-1, chunk),
         flat_m.reshape(-1, chunk)))
    return total / jnp.maximum(jnp.sum(flat_m), 1.0)


def _bf16_ulp(x):
    """The distance between neighbouring bfloat16 values (8 bits of
    significand) at the magnitude of ``x``."""
    return 2.0 ** (np.floor(np.log2(x)) - 7)


@pytest.mark.parametrize("scale", [1.0, 3.0], ids=["grad", "grad_of_3x"])
@pytest.mark.parametrize("chunk", [13, 64], ids=["42_in_13s", "one_chunk"])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_chunked_cross_entropy_matches_the_checkpointed_scan(
        dtype, masked, chunk, scale):
    """The custom_vjp forms dlogits in the forward chunk: the loss is the
    checkpointed scan's to the bit, and so is dH in bfloat16 (the same
    operands into the same product); dW sums the chunks in float32 where
    the scan's transpose summed them in the weights' dtype."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    hidden = jax.random.normal(keys[0], (2, 21, 64)).astype(dtype)
    lm_head = (jax.random.normal(keys[1], (64, 97)) / 8).astype(dtype)
    targets = jax.random.randint(keys[2], (2, 21), 0, 97)
    mask = ((jax.random.uniform(keys[3], (2, 21)) > 0.3).astype(jnp.float32)
            if masked else None)

    def loss_and_grads(fn):
        loss, (d_h, d_w) = jax.value_and_grad(
            lambda h, w: scale * fn(h, w, targets, mask,
                                    chunk_tokens=chunk), argnums=(0, 1))(
                hidden, lm_head)
        assert (d_h.dtype, d_w.dtype) == (dtype, dtype)
        return (float(loss), np.asarray(d_h, np.float32),
                np.asarray(d_w, np.float32))

    loss, d_h, d_w = loss_and_grads(chunked_cross_entropy)
    want_loss, want_d_h, want_d_w = loss_and_grads(
        _checkpointed_cross_entropy)
    assert loss == want_loss
    if dtype == jnp.float32:
        np.testing.assert_allclose(d_h, want_d_h, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(d_w, want_d_w, rtol=1e-5, atol=1e-6)
        return
    if scale == 1.0:
        np.testing.assert_array_equal(d_h, want_d_h)
        ulps = 1
    else:  # g * bf16(dlogits) against bf16(g * dlogits) in every product
        ulps = 4
        assert np.abs(d_h - want_d_h).max() <= ulps * _bf16_ulp(
            np.abs(want_d_h).max())
    assert np.abs(d_w - want_d_w).max() <= ulps * _bf16_ulp(
        np.abs(want_d_w).max())


def products_carrying(jaxpr, size):
    """dot_generals of a jaxpr, its sub-jaxprs included, with a
    dimension of ``size`` (the vocabulary, the FFN's width) among their
    operands' or result's. A scan's body counts once: a layer."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                size in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            n += 1
        n += sum(products_carrying(getattr(sub, "jaxpr", sub), size)
                 for sub in jax_core.jaxprs_in_params(eqn.params))
    return n


@pytest.mark.parametrize("differentiated, products", [
    (True, 3), (False, 1)], ids=["value_and_grad", "loss"])
def test_chunked_loss_runs_the_head_products_the_mathematics_needs(
        differentiated, products):
    """A train step holds logits, dH = dlogits W^T and dW = H^T dlogits
    and no second logits (4 until PR 62); the loss alone holds one."""
    cfg = LlamaConfig.tiny(vocab_size=97, remat=True, ce_chunk_tokens=13)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg, batch=2, seq=21)

    def loss(p):
        return llama_loss(p, tokens, targets, cfg)

    traced = jax.make_jaxpr(
        jax.value_and_grad(loss) if differentiated else loss)(params)
    assert products_carrying(traced.jaxpr, cfg.vocab_size) == products


def test_chunked_loss_under_fsdp4_matches_one_device():
    """lm_head and its gradient P(None, "fsdp"), the batch over fsdp:
    the loss and every gradient equal the unsharded ones."""
    cfg = LlamaConfig.tiny(ce_chunk_tokens=48)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg, batch=8, seq=16)  # 128 tokens: 3 chunks
    mesh = make_mesh(MeshSpec(fsdp=4))
    sharded = shard_pytree(params, mesh, llama_sharding_rules("fsdp"))
    assert sharded["lm_head"].sharding.spec == P(None, "fsdp")
    batch_sh = NamedSharding(mesh, P(("data", "fsdp")))

    def loss_and_grads(p, t, y):
        return jax.value_and_grad(
            lambda p_: llama_loss(p_, t, y, cfg))(p)

    loss, grads = jax.jit(loss_and_grads)(
        sharded, jax.device_put(tokens, batch_sh),
        jax.device_put(targets, batch_sh))
    want_loss, want_grads = jax.jit(loss_and_grads)(params, tokens, targets)
    assert grads["lm_head"].sharding.spec == P(None, "fsdp")
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


@pytest.fixture
def flash_interpreted(monkeypatch):
    # the Pallas kernels themselves, in interpreter mode on the CPU
    monkeypatch.setattr(attention, "_INTERPRET", True)


def _flash_sized(preset=LlamaConfig.tiny, **kw):
    """The smallest shapes the flash kernels cover (heads of 128, a
    sequence of 128), grouped-query, FFN wider than the model."""
    return preset(dim=256, n_heads=2, n_kv_heads=1, hidden_dim=384,
                  **kw)


@pytest.mark.parametrize("ce_chunk_tokens", [0, 64])
@pytest.mark.parametrize("preset", [LlamaConfig.tiny, LlamaConfig.tiny_moe],
                         ids=["dense", "moe"])
@pytest.mark.parametrize("attn", ["reference", "flash"])
def test_remat_gives_the_loss_and_gradients_of_no_remat(
        flash_interpreted, attn, preset, ce_chunk_tokens):
    """Keeping a residual or recomputing it is the same program text on
    the same inputs: exactly equal in float32."""
    cfg = _flash_sized(preset, attention=attn, remat=True,
                       ce_chunk_tokens=ce_chunk_tokens)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg, batch=2, seq=128)

    def loss_and_grads(c):
        return jax.value_and_grad(
            lambda p: llama_loss(p, tokens, targets, c))(params)

    loss, grads = loss_and_grads(cfg)
    want_loss, want_grads = loss_and_grads(
        dataclasses.replace(cfg, remat=False))
    assert float(loss) == float(want_loss)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("ce_chunk_tokens", [0, 64])
@pytest.mark.parametrize("attn", ["reference", "flash"])
def test_remat_runs_each_ffn_product_once(flash_interpreted, attn,
                                          ce_chunk_tokens):
    """A layer of a train step holds nine products that carry the FFN's
    width: gate, up and down, and dH and dW of each. Eleven (until
    PR 64) are gate and up run again in the backward pass: a name no
    longer reaches the checkpoint's policy."""
    cfg = _flash_sized(attention=attn, remat=True, n_layers=3,
                       ce_chunk_tokens=ce_chunk_tokens)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg, batch=2, seq=128)
    for c in (cfg, dataclasses.replace(cfg, remat=False)):
        traced = jax.make_jaxpr(jax.value_and_grad(
            lambda p: llama_loss(p, tokens, targets, c)))(params)
        assert products_carrying(traced.jaxpr, cfg.hidden_dim) == 9


@pytest.mark.parametrize("preset", [LlamaConfig.tiny, LlamaConfig.tiny_moe],
                         ids=["dense", "moe"])
def test_remat_keeps_of_an_ffn_its_two_pre_activations(preset):
    """What the policy keeps of `_ffn` besides its arguments: of a
    dense one `h @ w1` (NOT silu of it) and `h @ w3` (NOT the product),
    of a routed one nothing."""
    from ray_tpu.models.llama import REMAT_SAVED, _ffn

    cfg = _flash_sized(preset, remat=True)
    layer = jax.tree.map(lambda a: a[0], llama_init(
        jax.random.PRNGKey(0), cfg)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.dim),
                          cfg.dtype)
    ffn = jax.checkpoint(
        lambda p, h_: _ffn(p, h_, cfg)[0],
        policy=jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED))
    # the backward function closes over what the forward pass kept
    kept = [np.asarray(a) for a in jax.tree.leaves(jax.vjp(ffn, layer, h)[1])
            if a.shape[:-1] == h.shape[:-1]]
    want = [h] if cfg.moe_experts else [
        h, h @ layer["w1"], h @ layer["w3"]]
    assert len(kept) == len(want)
    for got, w in zip(kept, want):
        np.testing.assert_array_equal(got, np.asarray(w))


def test_remat_keeps_the_named_residuals_and_one_flash_forward(
        flash_interpreted):
    """What the checkpointed block hands its backward pass: its input,
    q, k and v at their own head counts (k and v NOT repeated to
    n_heads), the kernel's output and row sums, the stream after the
    attention projection, and of the FFN its two pre-activations and
    nothing else as wide; so no second forward kernel in the backward
    scan."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = _flash_sized(attention="flash", remat=True, n_layers=3)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    tokens, targets = _data(cfg, batch=2, seq=128)

    def loss(p):
        return llama_loss(p, tokens, targets, cfg)

    computed = [aval.shape for aval, why in saved_residuals(loss, params)
                if "from the argument" not in why]
    # the layer scan stacks what each block keeps: [n_layers, ...]
    kept = sorted(shape[1:] for shape in computed
                  if shape[:1] == (cfg.n_layers,))
    assert sum(shape[-1:] == (cfg.hidden_dim,) for shape in computed) == 2
    b, s, hd = 2, 128, cfg.head_dim
    assert kept == sorted([
        (b, s, cfg.dim),                    # the block's input
        (b, s, cfg.n_heads, hd),            # q, after rope
        (b, s, cfg.n_kv_heads, hd),         # k, after rope
        (b, s, cfg.n_kv_heads, hd),         # v
        (b, cfg.n_heads, s, hd),            # flash_fwd's out
        (b, cfg.n_heads, s, 1),             # flash_fwd's lse
        (b, s, cfg.dim),                    # x + attn @ wo
        (b, s, cfg.hidden_dim),             # h @ w1, before silu
        (b, s, cfg.hidden_dim),             # h @ w3
    ])

    def calls(c):
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: llama_loss(p, tokens, targets, c)))(params))
        return [text.count(f"name={k}")
                for k in ("flash_fwd", "flash_dq", "flash_dkv")]

    # one forward scan body, one backward scan body
    assert calls(cfg) == [1, 1, 1]
    assert calls(dataclasses.replace(cfg, remat=False)) == [1, 1, 1]
