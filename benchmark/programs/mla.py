"""The latent-attention family (ray_tpu.models.mla; ``model_type``
kimi_k2, the DeepSeek-V3 block): multi-head latent attention with YaRN
rotary on a shared key in every layer, ``first_k_dense_replace`` leading
dense feed-forwards and then a routed one with a shared expert, its
router a sigmoid with a selection bias; embedding and head two matrices;
served as one rank of an expert-parallel group (``experts_held`` of the
router's ``router_outputs`` experts, a slice of the vocabulary). The
kimi-k2.7-code configuration file names it. Serving only: the expert
layer's serving form holds no training batch."""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import BenchError

# what the CPU rehearsal runs in place of the published sizes
# (MlaConfig.tiny's): one leading dense layer and three routed ones, 4
# heads of 16 + 8 over values of 16, a latent of 32, 16 experts of which
# the first 4 are held, top-3, a bias that moves picks, a rotary
# stretched 4 times over 128 positions
_REHEARSAL = dict(
    vocab_size=512, dim=64, n_layers=4, n_dense_layers=1, n_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, dense_dim=96, n_experts=16, experts_held=(0, 4),
    top_k=3, expert_dim=32, shared_expert_dim=32, routed_scaling=1.5,
    router_bias_std=0.1, rope_theta=100.0, rope_factor=4.0,
    rope_original_max=128, rope_beta_fast=4.0, attention="reference")


def _model_kwargs(config: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """The published keys, as MlaConfig names them."""
    yarn = config["rope_scaling"]
    if (config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]
            or config["topk_method"] != "noaux_tc"
            or config["n_group"] != 1 or config["topk_group"] != 1):
        raise BenchError("the program's router is a sigmoid with a "
                         "selection bias over one group, its picked "
                         "scores renormalised")
    if (yarn["type"] != "yarn" or config["attention_bias"]
            or config["tie_word_embeddings"]
            or config["num_key_value_heads"]
            != config["num_attention_heads"]
            or config["moe_layer_freq"] != 1
            or config["num_nextn_predict_layers"]):
        raise BenchError("the program's latent attention has YaRN rotary, "
                         "no bias and as many key heads as query heads; "
                         "every layer past the leading dense ones is "
                         "routed, embedding and head are two matrices and "
                         "no layer predicts a further token")
    first, count = config["experts_held"]
    if count != config["n_routed_experts"]:
        raise BenchError("experts_held does not hold n_routed_experts "
                         "experts")
    kw = dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_dim=config["intermediate_size"],
        n_experts=config["router_outputs"], experts_held=(first, count),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_expert_dim=(config["n_shared_experts"]
                           * config["moe_intermediate_size"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        router_bias_std=float(config["router_bias_std"]),
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(yarn["factor"]),
        rope_original_max=yarn["original_max_position_embeddings"],
        rope_beta_fast=float(yarn["beta_fast"]),
        rope_beta_slow=float(yarn["beta_slow"]),
        rope_mscale=float(yarn["mscale"]),
        rope_mscale_all_dim=float(yarn["mscale_all_dim"]),
        norm_eps=float(config["rms_norm_eps"]), attention="flash")
    if rehearse:
        import jax.numpy as jnp
        kw.update(_REHEARSAL, dtype=jnp.float32)
    return kw


def serving_model(config: Dict[str, Any], max_seq: int, rehearse: bool):
    try:
        from ray_tpu.models.mla import MlaConfig
    except ImportError as exc:
        raise BenchError("the program has no latent-attention family: "
                         f"{exc}") from exc
    return MlaConfig(max_seq_len=max_seq, **_model_kwargs(config, rehearse))


def training(config: Dict[str, Any], sizes: Dict[str, Any],
             rehearse: bool) -> Dict[str, Any]:
    raise BenchError("the latent-attention family has no training path "
                     "yet: the expert layer's serving form holds no "
                     "training batch")


def vocab_size(config: Dict[str, Any], rehearse: bool) -> int:
    return _REHEARSAL["vocab_size"] if rehearse else config["vocab_size"]


def kernels(program_name: str) -> List[str]:
    """What the engine's programs hold on a TPU: a prefill program flash
    attention (the expanded form: keys of 192 over values of 128, padded
    to 256 lanes) and rms_norm; the decode programs decode_attention
    (the absorbed form over the latent rows) and rms_norm. The
    projections into and out of the latent and the expert layer are
    plain XLA (the grouped matmul is ``jax.lax.ragged_dot``)."""
    if program_name.startswith("prefill_"):
        return ["flash_fwd", "rms_norm"]
    if program_name == "train_step":
        raise BenchError("the latent-attention family has no training "
                         "path yet")
    return ["decode_attention", "rms_norm"]


def routed(config: Dict[str, Any]) -> bool:
    """Judged by reference_check.routed_report under the margin, the
    floor and the limits of its own configuration file (``check.limits``;
    ``check.calibration`` has the readings). The margin is of the
    selection scores ``sigmoid(l) + b``, what this router picks by."""
    return True


def _changed(engine, stack: str, name: str, change) -> None:
    import jax

    p = engine.params
    engine.params = {**p, stack: {**p[stack],
                                  name: jax.jit(change)(p[stack][name])}}


# what expert_zeroed asks the router with: prompts of the check's kind
# (a BOS, then letters), seeded
_PROBE_TOKENS = 512


def expert_zeroed(engine) -> None:
    """ONE held expert's output projection is zero in every routed
    layer: the program drops what that expert would add to the tokens
    routed to it. Which one: in each layer the held expert that most
    positions of a probe prompt pick (the reference's router over 512
    seeded tokens of the check's alphabet). Under seeded weights a held
    expert's share of the picks runs from none to a tenth (the rows of a
    sequence share a direction that a router column meets or does not;
    my chip runs, PR 69: zeroing expert 0 read the sound program's
    numbers on 2 seeds of 6), and an expert no token is routed to can be
    dropped without a wrong output: no comparison could tell."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mla as reference

    kw = reference.kwargs_from(engine.config.model)
    probe = jnp.concatenate([
        jnp.array([256]), jax.random.randint(
            jax.random.PRNGKey(0), (_PROBE_TOKENS - 1,), 97, 123)])
    picks = jax.jit(lambda p, t: reference.forward(p, t, **kw)[2])(
        engine.params, probe)                          # [layers, held]
    most = jnp.argmax(picks, axis=1)
    _changed(engine, "moe", "w_out_e",
             lambda w: w.at[jnp.arange(w.shape[0]), most].set(0))


def bias_dropped(engine) -> None:
    """The selection bias is zero in every routed layer: the program
    picks by the scores alone."""
    _changed(engine, "moe", "router_bias", lambda b: b * 0)


def rope_lanes_zeroed(engine) -> None:
    """The rotary lanes of every latent row are zero: ``W_kva``'s last
    ``qk_rope_head_dim`` columns are, in every layer, so a score is its
    latent part alone and no key carries a position."""
    rope = engine.config.model.qk_rope_dim
    _changed(engine, "attn", "w_kva", lambda w: w.at[..., -rope:].set(0))


def mscale_dropped(engine) -> None:
    """The scores are scaled by the key width's ``** -0.5`` without the
    square of YaRN's attention factor: ``W_qb`` is divided by it in every
    layer, which is the same sum."""
    from ray_tpu.ops.rope import yarn_mscale

    c = engine.config.model
    m2 = yarn_mscale(c.rope_factor, c.rope_mscale_all_dim) ** 2
    _changed(engine, "attn", "w_qb", lambda w: (w / m2).astype(w.dtype))


def weights_fp8(engine) -> None:
    """The precision under the one the configuration states: every
    matrix that every token meets (the attention stack's five, the dense
    feed-forward's and the shared experts' two each) rounded to fp8's 4
    exponent and 3 mantissa bits under a scale for each output channel.
    The held experts keep bf16 (a second copy of their stacks does not
    fit the chip, and a token meets one of them in one layer in eight).
    ``reduce_precision`` and not a cast there and back, which the TPU's
    compiler may take out as excess precision it may keep."""
    import jax
    import jax.numpy as jnp

    def fp8(w):
        w32 = w.astype(jnp.float32)
        # 240: the largest number 4 exponent bits hold beside an infinity
        scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 240.0
        return (jax.lax.reduce_precision(w32 / scale, exponent_bits=4,
                                         mantissa_bits=3)
                * scale).astype(w.dtype)

    for stack, names in (("attn", ("w_qa", "w_qb", "w_kva", "w_kvb", "wo")),
                         ("dense", ("w_in", "w_out")),
                         ("moe", ("w_in_s", "w_out_s"))):
        for name in names:
            _changed(engine, stack, name, fp8)


def controls(config: Dict[str, Any]) -> Dict[str, Any]:
    """Four leave out one thing this family adds each (one of the 12
    held experts, the router's selection bias, the rotary part of the
    latent row, YaRN's factor on the scores); the fifth computes in the
    precision under the configuration's."""
    return {"expert_zeroed": expert_zeroed, "bias_dropped": bias_dropped,
            "rope_lanes_zeroed": rope_lanes_zeroed,
            "mscale_dropped": mscale_dropped, "weights_fp8": weights_fp8}
