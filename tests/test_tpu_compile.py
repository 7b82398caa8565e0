"""Compile-only checks for a TPU v5e, with no chip: each Pallas kernel
and the programs chip_smoke.py runs are lowered from abstract arguments
and compiled against ``v5e:2x2`` topology devices (libtpu ships the
compiler). Catches what only the TPU compiler says — scoped-VMEM
overflow, "Mosaic kernels cannot be automatically partitioned" — before
any chip time is spent. Skipped where the topology cannot be had."""

import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
from abstract_engine import (abstract_engine, lowering_for_tpu as _for_tpu,
                             mesh_of as _mesh, on as _on, replicated,
                             v5e_devices)
from ray_tpu.accelerators import jax_backend
from ray_tpu.models.llama import (
    LlamaConfig, llama_decode_step, llama_init, llama_init_cache,
    llama_prefill)
from ray_tpu.ops import attention, quant_matmul, rmsnorm
from test_models import products_carrying

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def v5e():
    try:
        return v5e_devices()
    except Exception as exc:  # noqa: BLE001 — no libtpu, other jax
        pytest.skip(f"no v5e:2x2 compile-only topology here: {exc!r}")


@pytest.fixture(autouse=True)
def lowering_for_tpu():
    with _for_tpu():
        yield


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _kernels(lowered):
    return jax_backend.pallas_kernels(lowered.as_text())


def _reachable(text, name):
    """The HLO text of computation ``name`` and of all it calls."""
    bodies = dict(re.findall(r"^(%[\w.\-]+) \(.*?\{\n(.*?)^\}", text,
                             re.M | re.S))
    seen, todo = {}, [name]
    while todo:
        name = todo.pop()
        if name in bodies and name not in seen:
            seen[name] = bodies[name]
            todo += re.findall(r"%[\w.\-]+", bodies[name])
    return "\n".join(seen.values())


def _assert_sampler_branches(compiled):
    """The compiled decode program holds the sampler as a conditional:
    its cheap branch (no live slot samples) computes nothing at all,
    the other holds the top-k (a sort, or the compiler's ``TopK`` over
    the widest vocabulary)."""
    text = compiled.as_text()
    (cheap, dear), = re.findall(
        r" conditional\(.*branch_computations=\{(%[\w.\-]+), (%[\w.\-]+)\}"
        r".*op_name=\"jit\(decode\)/sampler/cond\"", text)
    cheap, dear = _reachable(text, cheap), _reachable(text, dear)
    assert " sort(" in dear or 'custom_call_target="TopK"' in dear
    for costly in (" sort(", "custom-call(", " while(", " fusion("):
        assert costly not in cheap, costly


def test_rms_block_rows_fit_vmem(v5e):
    mesh = _mesh(v5e, 1)
    for d, rows in ((2560, 512), (4096, 256), (8192, 128)):
        assert rmsnorm._block_rows(8192, d, 2) == rows
        lowered = jax.jit(lambda x, w: rmsnorm.rms_norm(x, w, 1e-5)).lower(
            _on(mesh, P(), (8192, d), jnp.bfloat16),
            _on(mesh, P(), (d,), jnp.bfloat16))
        assert _kernels(lowered) == [
            f"rms_norm(tensor<8192x{d}xbf16>, tensor<{d}xbf16>)"]
        lowered.compile()
    # whole input as one block; float32 rows take twice the room
    assert rmsnorm._block_rows(8, 4096, 2) == 8
    assert rmsnorm._block_rows(8192, 4096, 4) == 128
    assert rmsnorm._block_rows(8192, 4000, 2) is None


def test_flash_and_int8_kernels_compile(v5e):
    mesh = _mesh(v5e, 1)
    qkv = _on(mesh, P(), (2, 2048, 32, 128), jnp.bfloat16)
    lowered = jax.jit(jax.grad(
        lambda q, k, v: attention.flash_attention(q, k, v, True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))).lower(qkv, qkv, qkv)
    assert [k.split("(")[0] for k in _kernels(lowered)] == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    lowered.compile()
    lowered = jax.jit(quant_matmul.int8_matmul).lower(
        _on(mesh, P(), (8, 4096), jnp.bfloat16),
        _on(mesh, P(), (4096, 14336), jnp.int8),
        _on(mesh, P(), (14336,), jnp.float32))
    assert [k.split("(")[0] for k in _kernels(lowered)] == ["int8_matmul"]
    lowered.compile()
    assert attention.kernel_fallbacks == []


# The train cells' two shapes and one long sequence with gradients (all
# three kernels), the serving cells' prefill buckets forward only (32
# heads: Mistral, Granite; 20: Jamba). The long one holds the module's
# promise that VMEM stays bounded at any sequence length: the walked
# operand is resident by major blocks, inside the default scoped limit.
@pytest.mark.parametrize("shape, with_grads", [
    ((4, 2048, 32, 128), True), ((2, 2048, 32, 128), True),
    ((1, 32768, 8, 128), True)] + [
    ((1, s, h, 128), False) for h in (32, 20) for s in (128, 256, 512, 1024)])
def test_flash_kernels_compile_at_the_callers_shapes(v5e, shape, with_grads):
    qkv = _on(_mesh(v5e, 1), P(), shape, jnp.bfloat16)
    fn = lambda q, k, v: attention.flash_attention(q, k, v, True)  # noqa: E731
    if with_grads:
        fn = jax.grad(lambda q, k, v: attention.flash_attention(
            q, k, v, True).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    lowered = jax.jit(fn).lower(qkv, qkv, qkv)
    assert [k.split("(")[0] for k in _kernels(lowered)] == (
        ["flash_dkv", "flash_dq", "flash_fwd"] if with_grads
        else ["flash_fwd"])
    lowered.compile()
    assert attention.kernel_fallbacks == []


def _compile_train_step(devices, *, chips, n_layers, batch, seq=2048):
    """chip_smoke's trainer step, from abstract state sharded as its
    loop shards it. Returns (kernels, bytes per chip), having checked
    that the traced step holds the head's three products and no fourth
    and a layer's nine FFN products and no eleven, and that the lowered
    step calls each flash kernel ONCE: the layer scan's forward body
    holds flash_fwd and its backward body flash_dq and
    flash_dkv, because remat keeps flash_fwd's output and row sums
    (llama.REMAT_SAVED). A second flash_fwd means a name no longer
    reaches the checkpoint's policy."""
    cfg = LlamaConfig.llama2_7b(n_layers=n_layers, max_seq_len=seq,
                                ce_chunk_tokens=4096)
    mesh = _mesh(devices, chips)
    init, shardings, train_step = chip_smoke.train_programs(
        cfg, mesh, optax.adamw(1e-3))
    params, opt_state = _abstract(
        jax.eval_shape(init, jax.random.PRNGKey(0)), shardings)
    tokens = _on(mesh, P(("data", "fsdp")), (batch, seq), jnp.int32)
    traced = jax.jit(train_step, donate_argnums=(0, 1)).trace(
        params, opt_state, tokens, tokens)
    # the head's products: a chunk's logits, dH and dW, all in the loss's
    # forward rule (llama._chunked_nll_fwd); a fourth is a chunk's logits
    # computed again
    assert products_carrying(traced.jaxpr.jaxpr, cfg.vocab_size) == 3
    # a layer's FFN: gate, up and down, dH and dW of each; gate and up
    # a second time (11) if remat no longer keeps them
    assert products_carrying(traced.jaxpr.jaxpr, cfg.hidden_dim) == 9
    lowered = traced.lower()
    text = lowered.as_text()
    assert [text.count(f'kernel_name = "{k}"') for k in
            ("flash_fwd", "flash_dq", "flash_dkv")] == [1, 1, 1]
    memory = lowered.compile().memory_analysis()
    gib = 2.0**30
    print(f"train step on {chips} chip(s), {n_layers} layers, {batch} x "
          f"{seq}: arguments {memory.argument_size_in_bytes / gib:.2f} + "
          f"temporaries {memory.temp_size_in_bytes / gib:.2f} GiB a chip")
    return _kernels(lowered), (memory.argument_size_in_bytes
                               + memory.temp_size_in_bytes)


@pytest.mark.slow
def test_one_chip_train_step_compiles(v5e):
    kernels, bytes_per_chip = _compile_train_step(
        v5e, chips=1, n_layers=4, batch=4)
    assert [k.split("(")[0] for k in kernels] == [
        "flash_dkv", "flash_dq", "flash_fwd", "rms_norm"]
    assert bytes_per_chip < HBM_BYTES
    assert attention.kernel_fallbacks == []


@pytest.mark.slow
def test_fsdp4_train_step_compiles_with_per_shard_kernels(v5e):
    kernels, bytes_per_chip = _compile_train_step(
        v5e, chips=4, n_layers=16, batch=8)
    # batch 8 over fsdp=4: every kernel sees 2 sequences
    shard = "tensor<2x32x2048x128xbf16>"
    assert f"flash_fwd({shard}, {shard}, {shard})" in kernels
    assert any(k.startswith(f"flash_dq({shard}") for k in kernels)
    assert any(k.startswith(f"flash_dkv({shard}") for k in kernels)
    assert "rms_norm(tensor<4096x4096xbf16>, tensor<4096xbf16>)" in kernels
    assert bytes_per_chip < HBM_BYTES


def _abstract_params(mesh, cfg):
    return replicated(mesh, jax.eval_shape(lambda k: llama_init(k, cfg),
                                           jax.random.PRNGKey(0)))


def _lower_decode_step(mesh, cfg, params, *, batch, seq):
    """``llama_decode_step`` with both caches donated, as the engine's
    decode program calls it."""
    cache_k, cache_v = replicated(mesh, jax.eval_shape(
        lambda: llama_init_cache(cfg, batch, seq)))
    ints = _on(mesh, P(), (batch,), jnp.int32)
    return jax.jit(
        lambda p, tok, ck, cv, pos: llama_decode_step(p, tok, ck, cv,
                                                      pos, cfg),
        donate_argnums=(2, 3)).lower(params, ints, cache_k, cache_v, ints)


@pytest.mark.slow
def test_serving_programs_compile(v5e):
    """The engine's prefill (buckets 128 and 1024) and decode programs
    at the smoke's serving size: 16 layers, batch 8, seq 1024."""
    cfg = LlamaConfig.llama2_7b(n_layers=16, max_seq_len=1024)
    mesh = _mesh(v5e, 1)
    params = _abstract_params(mesh, cfg)
    for bucket in (128, 1024):
        lowered = jax.jit(lambda p, t: llama_prefill(p, t, cfg)).lower(
            params, _on(mesh, P(), (1, bucket), jnp.int32))
        assert [k.split("(")[0] for k in _kernels(lowered)] == [
            "flash_fwd", "rms_norm"]
        lowered.compile()
    lowered = _lower_decode_step(mesh, cfg, params, batch=8, seq=1024)
    assert [k.split("(")[0] for k in _kernels(lowered)] == [
        "decode_attention", "rms_norm"]
    memory = lowered.compile().memory_analysis()
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < HBM_BYTES
    assert attention.kernel_fallbacks == []


def test_decode_step_attends_over_the_cache_in_place(v5e):
    """The decode program of the two serving cells (Mistral-7B widths,
    16 layers, batch 32, seq 1024, caches donated) holds no copy of the
    cache: the ``decode_attention`` kernel takes the stacked caches as
    they are (a bitcast to rows of 128 lanes), K and V are not expanded
    to 32 heads, no layer is sliced out or handed back through a fresh
    buffer, the temporaries stay under 64 MiB and both caches alias
    their donated inputs."""
    cfg = LlamaConfig(vocab_size=32768, dim=4096, n_layers=16, n_heads=32,
                      n_kv_heads=8, hidden_dim=14336, max_seq_len=1024,
                      rope_theta=1e6)
    mesh = _mesh(v5e, 1)
    params = _abstract_params(mesh, cfg)
    lowered = _lower_decode_step(mesh, cfg, params, batch=32, seq=1024)
    # the kernel takes the stacked caches as rows of (position, KV head)
    stacked = "tensor<16x32x8192x128xbf16>"
    assert _kernels(lowered) == [
        "decode_attention(tensor<1xi32>, tensor<32xi32>, "
        "tensor<32x32x128xbf16>, tensor<32x1024xi32>, "
        f"{stacked}, {stacked})",
        "rms_norm(tensor<32x4096xbf16>, tensor<4096xbf16>)"]
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    cache_bytes = 2 * 16 * 32 * 1024 * 8 * 128 * 2
    assert memory.alias_size_in_bytes == cache_bytes
    assert memory.temp_size_in_bytes < 64 * 2**20
    text = compiled.as_text()
    assert "[32,1024,8,4,128]" not in text
    # no layer sliced out of the stack, no score over all 1024 rows
    assert "[1,32,1024,8,128]" not in text
    assert "[32,8,4,1024]" not in text
    whole_cache = re.escape("bf16[16,32,1024,8,128]")
    assert not re.search(
        rf"= {whole_cache}\S* (copy|custom-call)\(", text)


@pytest.mark.parametrize("want_lp", [False, True])
def test_engine_decode_program_feeds_its_state_back(v5e, want_lp):
    """The engine's own ``decode`` / ``decode_lp`` program at the two
    serving cells' sizes, its per-slot inputs and the sampler's counter
    one packed [7, 32] int32 state that it also returns: both caches
    still alias their donated inputs, the temporaries stay under 64
    MiB (the sampler's and, with logprobs, the log-softmax's [32, 32768]
    rows; no copy of a cache), ``decode_attention`` and ``rms_norm``
    are the kernels, and the state comes back
    with the shape and type it went in with, so the next step can take
    it as it is."""
    cfg = LlamaConfig(vocab_size=32768, dim=4096, n_layers=16, n_heads=32,
                      n_kv_heads=8, hidden_dim=14336, max_seq_len=1024,
                      rope_theta=1e6)
    lowered = abstract_engine(cfg, 32, 1024, v5e).lower_decode(
        want_lp=want_lp)
    assert [k.split("(")[0] for k in _kernels(lowered)] == [
        "decode_attention", "rms_norm"]
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * 16 * 32 * 1024 * 8 * 128 * 2
    assert memory.temp_size_in_bytes < 64 * 2**20
    out_state = jax.tree.leaves(lowered.out_info)[0]
    assert (out_state.shape, out_state.dtype) == ((7, 32), jnp.int32)
    assert "[32,1024,8,4,128]" not in compiled.as_text()
    _assert_sampler_branches(compiled)


def test_jamba_serving_programs_compile_at_published_widths(v5e):
    """The Jamba cell's engine programs as the chip gets them
    (AI21-Jamba2-3B whole: 26 Mamba layers and 2 of attention, batch
    32, seq 1024): the decode program through the engine's family seam
    holds ``decode_attention`` beside ``rms_norm``, aliases the whole
    cache of two kinds (donated, so no state and no row is copied) and
    keeps its temporaries under 64 MiB; a prefill program holds its
    bucket's scan kernel
    beside flash attention, and everything fits one chip."""
    from ray_tpu.models.jamba import JambaConfig
    from ray_tpu.ops import selective_scan
    built = abstract_engine(JambaConfig(max_seq_len=1024), 32, 1024, v5e)
    lowered = built.lower_decode()
    assert [k.split("(")[0] for k in _kernels(lowered)] == [
        "decode_attention", "rms_norm"]
    compiled = lowered.compile()
    _assert_sampler_branches(compiled)
    memory = compiled.memory_analysis()
    # the K/V rows of 2 layers and the state of 26: all of it in place
    kv = 2 * 2 * 32 * 1024 * 128 * 2
    state = 26 * 32 * 5120 * (16 * 4 + 3 * 2)
    assert kv + state <= memory.alias_size_in_bytes <= 1.2 * (kv + state)
    assert memory.temp_size_in_bytes < 64 * 2**20
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < HBM_BYTES // 2
    lowered = built.lower_prefill(256)
    assert [k.split("(")[0] for k in _kernels(lowered)] == [
        "flash_fwd", "rms_norm", "selective_scan_256"]
    memory = lowered.compile().memory_analysis()
    assert memory.temp_size_in_bytes < 512 * 2**20
    assert selective_scan.kernel_fallbacks == []
    assert attention.kernel_fallbacks == []


def test_granite_serving_programs_compile_at_published_widths(v5e):
    """The Granite cell's engine programs as the chip gets them
    (granite-4.0-h-small's first period of ten layers, 36 of 72 experts
    and half the vocabulary held, batch 32, seq 2560): the decode
    program through the engine's family seam holds ``decode_attention``
    and ``ssd_update`` (in the layer scan) beside ``rms_norm``, aliases
    the whole cache of two kinds (donated: no state and no row is
    copied, the kernel's aliased stack included), hands its device
    counts on without donating them and keeps its temporaries under 64
    MiB; a prefill program holds flash attention and no other Pallas
    kernel (the grouped matmul is XLA's own ``ragged_dot``), and
    everything fits one chip."""
    from ray_tpu.models.granite import GraniteConfig
    from ray_tpu.ops import ssd_update
    cfg = GraniteConfig(
        vocab_size=50176, layer_types=GraniteConfig().layer_types[:10],
        experts_held=(0, 36), max_seq_len=2560)
    built = abstract_engine(cfg, 32, 2560, v5e)
    assert 9.4e9 < built.weight_bytes < 9.6e9
    lowered = built.lower_decode()
    # rms_norm twice: over the model's width and over d_inner
    assert sorted({k.split("(")[0] for k in _kernels(lowered)}) == [
        "decode_attention", "rms_norm", "ssd_update"]
    # the recurrence's one pass stands inside the layer scan: once for
    # each of the period's two runs of Mamba layers, not once a layer
    assert lowered.as_text().count('kernel_name = "ssd_update"') == 2
    assert ssd_update.head_block(128, 64, 128) == 32
    compiled = lowered.compile()
    _assert_sampler_branches(compiled)
    memory = compiled.memory_analysis()
    # the K/V rows of 1 layer and the state of 9: all of it in place,
    # the kernel's aliased stack too (1.21 GB that no pass copies)
    kv = 2 * 32 * 2560 * 8 * 128 * 2
    state = 9 * 32 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert kv + state <= memory.alias_size_in_bytes <= 1.2 * (kv + state)
    assert memory.temp_size_in_bytes < 64 * 2**20
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 0.75 * HBM_BYTES
    for bucket in (256, 1024):
        lowered = built.lower_prefill(bucket)
        assert sorted({k.split("(")[0] for k in _kernels(lowered)}) == [
            "flash_fwd", "rms_norm"]
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1536 * 2**20
        # no layer's experts are sliced out of their stack into a copy
        # (453 MB a layer): the grouped matmul reads the stack itself
        made = re.findall(r"= bf16\[36,4096,1536\]\S* (\S+?)\(",
                          compiled.as_text())
        assert set(made) <= {"bitcast", "parameter"}, set(made)
    assert attention.kernel_fallbacks == []


def test_lfm2_serving_programs_compile_at_published_widths(v5e):
    """The LFM2 cell's engine programs as the chip gets them
    (LFM2-8B-A1B's first fourteen layers, all 32 experts of the twelve
    routed ones, batch 32, seq 1536): heads of 64 reach both attention
    kernels (the prefill's padded to 128 lanes, the decode step's two
    KV heads a row) and nothing falls back; the decode program aliases
    the whole cache, hands its eight device counts on and keeps its
    temporaries under 64 MiB; a prefill reads the expert stack where
    it lies; everything fits one chip."""
    from ray_tpu.models.lfm2 import Lfm2Config
    cfg = Lfm2Config(layer_types=Lfm2Config().layer_types[:14],
                     max_seq_len=1536)
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_moe_layers) \
        == (11, 3, 12)
    built = abstract_engine(cfg, 32, 1536, v5e)
    assert 9.55e9 < built.weight_bytes < 9.65e9
    lowered = built.lower_decode()
    assert sorted({k.split("(")[0] for k in _kernels(lowered)}) == [
        "decode_attention", "rms_norm"]
    compiled = lowered.compile()
    _assert_sampler_branches(compiled)
    memory = compiled.memory_analysis()
    # the K/V rows of 3 layers and 11 layers' two columns: in place
    kv = 2 * 3 * 32 * 1536 * 8 * 64 * 2
    state = 11 * 32 * 2 * 2048 * 2
    assert kv + state <= memory.alias_size_in_bytes <= 1.2 * (kv + state)
    assert memory.temp_size_in_bytes < 64 * 2**20
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < 0.75 * HBM_BYTES
    for bucket in (128, 1024):
        lowered = built.lower_prefill(bucket)
        assert sorted({k.split("(")[0] for k in _kernels(lowered)}) == [
            "flash_fwd", "rms_norm"]
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1536 * 2**20
        # no layer's experts are sliced out of their stack into a copy
        # (470 MB a layer): the grouped matmul reads the stack itself
        made = re.findall(r"= bf16\[32,2048,3584\]\S* (\S+?)\(",
                          compiled.as_text())
        assert set(made) <= {"bitcast", "parameter"}, set(made)
    assert attention.kernel_fallbacks == []


def test_mla_serving_programs_compile_at_published_widths(v5e):
    """The Kimi cell's engine programs as the chip gets them
    (Kimi-K2.7-Code's first seven layers, 12 of a routed layer's 384
    experts, an eighth of the vocabulary, batch 32, seq 4608): a prefill
    runs the expanded form through flash_fwd (keys of 192 over values
    of 128, padded to 256 lanes), a decode step the absorbed form
    through decode_attention over the latent rows, and nothing falls
    back; the decode program aliases the whole cache, hands its eight
    device counts on and keeps its temporaries under 64 MiB; the 4096
    bucket's temporaries are stated; everything fits one chip."""
    from ray_tpu.models.mla import MlaConfig
    cfg = MlaConfig(vocab_size=20480, n_layers=7, experts_held=(0, 12),
                    max_seq_len=4608)
    built = abstract_engine(cfg, 32, 4608, v5e)
    assert 9.65e9 < built.weight_bytes < 9.75e9
    latent = 7 * 32 * 4608 * 640 * 2
    assert [x.shape for x in built.cache] == [(7, 32, 4608, 1, 640)]
    assert attention.decode_block_rows(4608, 1, 640) == 512
    lowered = built.lower_decode()
    assert sorted({k.split("(")[0] for k in _kernels(lowered)}) == [
        "decode_attention", "rms_norm"]
    compiled = lowered.compile()
    _assert_sampler_branches(compiled)
    memory = compiled.memory_analysis()
    print("mla decode:", memory)
    assert latent <= memory.alias_size_in_bytes <= 1.01 * latent
    assert memory.temp_size_in_bytes < 64 * 2**20
    held = memory.argument_size_in_bytes       # weights, cache, bias
    assert 0.6 * HBM_BYTES < held < 0.66 * HBM_BYTES
    for bucket in (1024, 4096):
        lowered = built.lower_prefill(bucket)
        assert sorted({k.split("(")[0] for k in _kernels(lowered)}) == [
            "flash_fwd", "rms_norm"]
        compiled = lowered.compile()
        memory = compiled.memory_analysis()
        print(f"mla prefill_{bucket}:", memory)
        # the many-rows expert form walks the held experts' (row, pick)
        # pairs a chunk of 256 places at a time and makes no array
        # over all 8 x bucket pairs (32 768 at 4096; at 1024 ``wo``
        # is an [8192, 7168] of its own): 813 MiB of temporaries at 4096,
        # 206 at 1024 (1.99 and 0.50 GiB until PR 70), most of them
        # layer 0's dense feed-forward (its [bucket, 36864] float32);
        # they fit beside the weights, the cache and a control's
        # changed expert stack (2.1 GB)
        assert memory.temp_size_in_bytes < bucket * 0.22 * 2**20
        text = compiled.as_text()
        assert "[32768,7168]" not in text and "[32768,4096]" not in text
        assert held + memory.temp_size_in_bytes + 2.2e9 < 0.98 * HBM_BYTES
        # no layer's experts are sliced out of their stack into a copy
        # (1.06 GB a layer): the grouped matmul reads the stack itself
        made = re.findall(r"= bf16\[12,7168,4096\]\S* (\S+?)\(", text)
        assert set(made) <= {"bitcast", "parameter"}, set(made)
    assert attention.kernel_fallbacks == []
