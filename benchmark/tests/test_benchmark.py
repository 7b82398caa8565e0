"""Checks of the yardstick itself. Run by hand, from the root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repo's tier-1 tests.
"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, ops, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _mix():
    return harness.load_json("traffic", "chat-short-steady.json")


# -- traffic ------------------------------------------------------------

def test_same_seed_same_schedule():
    a = traffic.open_loop_schedule(_mix(), 3000000011, 30.0)
    b = traffic.open_loop_schedule(_mix(), 3000000011, 30.0)
    assert a == b


def test_another_seed_same_work_in_another_order():
    a = traffic.open_loop_schedule(_mix(), 1, 30.0)
    b = traffic.open_loop_schedule(_mix(), 2, 30.0)
    assert [r["due"] for r in a] != [r["due"] for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    for key in ("max_tokens",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    gaps = lambda rs: sorted(round(y["due"] - x["due"], 9)  # noqa: E731
                             for x, y in zip([{"due": 0.0}] + rs, rs))
    assert gaps(a) == gaps(b)


def test_initial_burst_comes_first_and_adds_to_the_rate():
    mix = harness.load_json("traffic", "chat-short-saturated.json")
    rs = traffic.open_loop_schedule(mix, 5, 40.0)
    burst = mix["initial_burst"]
    assert len(rs) == burst + round(mix["rate_rps"] * 40.0)
    assert [round(r["due"], 6) for r in rs[:3]] == [0.01, 0.02, 0.03]
    assert sum(r["due"] <= 0.01 * burst for r in rs) >= burst
    assert [r["due"] for r in rs] == sorted(r["due"] for r in rs)


def test_order_in_rounds_deals_every_stratum_once_a_round():
    mix = harness.load_json("traffic", "chat-short-saturated.json")
    k = mix["order"]["strata"]
    a = traffic.open_loop_schedule(mix, 3000000011, 40.0)
    b = traffic.open_loop_schedule(mix, 3000000012, 40.0)
    plain = traffic.open_loop_schedule(
        {**mix, "order": {"strata": 1}}, 3000000011, 40.0)
    for key in (lambda r: r["max_tokens"], lambda r: len(r["prompt"])):
        # the same set as under a plain shuffle, in an order of the seed's
        assert sorted(map(key, a)) == sorted(map(key, b)) \
            == sorted(map(key, plain))
        assert list(map(key, a)) != list(map(key, b))
        grid = sorted(map(key, a))
        n = len(grid)
        cuts = [grid[n * i // k] for i in range(k)] + [grid[-1] + 1]
        for start in range(0, n - k + 1, k):
            round_ = sorted(map(key, a[start:start + k]))
            assert all(cuts[i] <= v <= cuts[i + 1]
                       for i, v in enumerate(round_)), (start, round_)
    # any 16 consecutive requests ask for about the same number of tokens
    sums = [sum(r["max_tokens"] for r in a[i:i + k])
            for i in range(0, len(a) - k + 1, k)]
    assert max(sums) - min(sums) < 0.2 * sums[0], sums
    plain_sums = [sum(r["max_tokens"] for r in plain[i:i + k])
                  for i in range(0, len(plain) - k + 1, k)]
    assert max(plain_sums) - min(plain_sums) > max(sums) - min(sums)


def test_order_without_strata_is_the_plain_shuffle():
    """A mix file without "order" gets the schedule it always got."""
    import hashlib
    got = traffic.open_loop_schedule(_mix(), 7, 40.0)
    assert hashlib.sha256(json.dumps(got).encode()).hexdigest()[:16] \
        == "c4c1ad43fc7dbfa3"


def test_schedule_fits_window_and_limits():
    mix = _mix()
    rs = traffic.open_loop_schedule(mix, 7, 40.0)
    assert len(rs) == round(mix["rate_rps"] * 40.0)
    assert all(0 < r["due"] < 40.0 for r in rs)
    assert all(mix["prompt_bytes"]["min"] <= len(r["prompt"])
               <= mix["prompt_bytes"]["max"] for r in rs)
    assert all(mix["output_tokens"]["min"] <= r["max_tokens"]
               <= mix["output_tokens"]["max"] for r in rs)
    assert all(r["prompt"].isascii() for r in rs)


def test_token_rows_depend_on_seed_and_row_only():
    rows = {"id": [0, 1, 5]}
    a = traffic.token_rows(9, 16, 1000)(rows)
    b = traffic.token_rows(9, 16, 1000)({"id": [5]})
    c = traffic.token_rows(10, 16, 1000)(rows)
    assert (a["tokens"][2] == b["tokens"][0]).all()
    assert (a["tokens"][:, 1:] == a["targets"][:, :-1]).all()
    assert (a["tokens"] != c["tokens"]).any()


# -- arithmetic ---------------------------------------------------------

@pytest.mark.parametrize("q,want", [(0.5, 5), (0.9, 9), (0.95, 10),
                                    (1.0, 10), (0.05, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert harness.percentile(list(range(10, 0, -1)), q) == want


def test_open_loop_latency_runs_from_the_due_time():
    records = [
        # sent 0.2 s late: the wait is the system's, TTFT from `due`
        {"due": 1.0, "sent": 1.2, "token_times": [1.5, 1.6, 1.8],
         "finished": True, "error": None},
        {"due": 2.0, "sent": 2.0, "token_times": [2.1, 2.15],
         "finished": True, "error": None},
        # failed: counts as the worst anybody had
        {"due": 3.0, "sent": 3.0, "token_times": [], "finished": False,
         "error": "closed before [DONE]"},
    ]
    lat = harness.open_loop_latencies(records)
    assert lat["ttft_s"] == pytest.approx([0.5, 0.1, 0.5])
    assert sorted(lat["gaps_s"]) == pytest.approx([0.05, 0.1, 0.2])
    assert lat["lag_worst_s"] == pytest.approx(0.2)
    assert lat["lag_mean_s"] == pytest.approx(0.2 / 3)
    assert harness.tokens_inside(records, 1.7) == 2


# -- operations ---------------------------------------------------------

def test_ops_mistral_7b_l4_by_hand():
    config = harness.load_json("configs", "mistral-7b-v0.3-L4.json")
    # wq, wo: 4096 x 4096 each; wk, wv: 4096 x 1024 each; three FFN
    # matrices of 4096 x 14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert ops.layer_matmul_params(config) == layer
    head = 4096 * 32768
    want = 6 * (4 * layer + head) + 6 * 4 * 2048 * 4096
    assert ops.train_flops_per_token(config, 2048) == want
    assert want == pytest.approx(6.241e9, rel=1e-3)


def test_ops_mixtral_counts_active_experts():
    config = {"hidden_size": 4096, "num_attention_heads": 32,
              "num_key_value_heads": 8, "intermediate_size": 14336,
              "num_local_experts": 8, "num_experts_per_tok": 2}
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert ops.layer_matmul_params(config) == \
        attn + 2 * 3 * 4096 * 14336 + 4096 * 8
    assert ops.layer_matmul_params(config, active=False) == \
        attn + 8 * 3 * 4096 * 14336 + 4096 * 8


def test_flash_counts_and_bound():
    fwd = ops.flash_call("flash_fwd", 4, 32, 2048, 128)
    assert fwd["flops"] == 4 * 4 * 32 * 2048 * 2048 * 128 / 2
    assert ops.flash_call("flash_dq", 4, 32, 2048, 128)["flops"] == \
        1.5 * fwd["flops"]
    assert ops.flash_call("flash_dkv", 4, 32, 2048, 128)["flops"] == \
        2.0 * fwd["flops"]
    peaks = harness.peaks_for("TPU v5 lite")
    floor = ops.least_seconds(fwd, peaks)
    assert floor["bound"] == "compute"
    assert floor["seconds"] == pytest.approx(fwd["flops"] / 197e12)
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")


# -- trace --------------------------------------------------------------

def test_interval_arithmetic():
    merged = trace_reduce.merge([(0, 2), (1, 3), (5, 6), (5.5, 5.8)])
    assert merged == [(0, 3), (5, 6)]
    assert trace_reduce.total(merged) == 4
    assert trace_reduce.clip(merged, (2, 5.5)) == [(2, 3), (5, 5.5)]


def test_reduce_small_recorded_trace():
    """Three calls of a jitted 512x512 matmul-and-sum recorded on the
    CPU, each inside a `my_span` annotation with 10 ms of sleep."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small_cpu.xplane.pb")
    reduced = trace_reduce.reduce_trace(path)
    assert reduced["devices"] == 1
    seconds, calls = trace_reduce.op_seconds(reduced, "dot_general")
    assert calls == 3 and seconds > 0
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["device_ops"][0][0].startswith("dot_general")
    assert len(reduced["device_ops"]) <= 10
    assert len(reduced["idle_gaps"]) <= 10
    # the three sleeps are most of the window
    assert sum(s for _, s in reduced["idle_gaps"]) > 0.02


# -- BENCHMARK.json -----------------------------------------------------

def test_benchmark_json_names_files_and_characters():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert set(c["reduced"]) == set(data["reduced"])
        for key in ("source", "reduced", "assumed", "departures",
                    "deployment", "reference", "program"):
            assert key in data, (c["name"], key)
        assert data["source"] == c["source"]
        program = harness.program_for(data["program"])
        for function in ("serving_model", "training", "vocab_size",
                         "kernels", "routed"):
            assert callable(getattr(program, function)), (c["name"],
                                                          function)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        mix = harness.load_json("traffic", w["traffic"] + ".json")
        harness.runner_for(mix["kind"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        harness.reader_for(spec["reader"])
        assert m["moves"] in e2e
        # the metric it moves is reported wherever this one is
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)


def test_every_cell_reports_enough():
    for w in _bench()["workloads"]:
        cell = harness.load_cell(w["name"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"]


# -- the plain reference ------------------------------------------------

@pytest.mark.parametrize("experts,capacity_factor", [
    (0, 2.0),
    # 4 experts, top-2: at that size no expert can overflow, so the
    # program drops nothing and has to agree
    (4, 2.0),
    # 8 experts overflow at the program's default; at 4.0 an expert's
    # capacity is every token of the step, nothing is dropped, and
    # this is the case that has to agree
    (8, 4.0)])
def test_reference_agrees_with_the_program_on_the_cpu(experts,
                                                      capacity_factor):
    """Tiny sizes, float32: the program's loss and the reference's on
    the same seeded weights, dense (grouped-query) and with experts."""
    import jax

    from benchmark.reference import mistral as ref
    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    cfg = LlamaConfig.tiny(vocab_size=300, moe_experts=experts,
                           moe_capacity_factor=capacity_factor,
                           moe_aux_weight=0.0)
    params = llama_init(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 33), 0, 300)
    x, y = tokens[:, :-1], tokens[:, 1:]
    got = float(llama_loss(params, x, y, cfg, None))
    want = sum(float(ref.loss(params, x[i], y[i], **ref.kwargs_from(cfg)))
               for i in range(2)) / 2
    assert got == pytest.approx(want, abs=1e-4)


def test_router_margins_come_with_the_logits():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mistral as ref
    from ray_tpu.models.llama import LlamaConfig, llama_init

    tokens = jax.random.randint(jax.random.PRNGKey(8), (33,), 0, 300)
    for experts in (0, 8):
        cfg = LlamaConfig.tiny(vocab_size=300, moe_experts=experts)
        params = llama_init(jax.random.PRNGKey(7), cfg)
        kw = ref.kwargs_from(cfg)
        logits, margins = ref.logits_and_margins(params, tokens, **kw)
        assert (logits == ref.logits(params, tokens, **kw)).all()
        assert (margins == ref.router_margins(params, tokens, **kw)).all()
        assert margins.shape == (33,) and (margins >= 0).all()
        # no router, nothing to flip
        assert bool(jnp.isinf(margins).all()) == (experts == 0)
    # one layer, first position: attention returns the token's own
    # value, so the router's input can be written out by hand
    cfg = LlamaConfig.tiny(vocab_size=300, moe_experts=8, n_layers=1)
    params = llama_init(jax.random.PRNGKey(7), cfg)
    layer = jax.tree.map(lambda w: w[0].astype(jnp.float32),
                         params["layers"])
    norm = lambda x, w: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x) + cfg.norm_eps) * w
    x = params["embedding"][tokens[0]].astype(jnp.float32)
    v = jnp.repeat((norm(x, layer["attn_norm"]) @ layer["wv"]).reshape(
        cfg.n_kv_heads, -1), cfg.n_heads // cfg.n_kv_heads, 0)
    x = x + v.reshape(-1) @ layer["wo"]
    ranked = jnp.sort(norm(x, layer["mlp_norm"]) @ layer["router"])
    margin = ref.router_margins(params, tokens, **ref.kwargs_from(cfg))[0]
    assert float(margin) == pytest.approx(float(ranked[-2] - ranked[-3]),
                                          abs=1e-5)


# -- the comparison for a model with a router ---------------------------

def test_routed_report_on_numbers_written_out():
    from benchmark import reference_check as rc

    far, near = rc.ROUTER_MARGIN * 2, rc.ROUTER_MARGIN / 2
    # a flipped token reads units and decides nothing, decided or not
    ok = rc.routed_report([0.01] * 190 + [3.0, 2.0],
                          [far] * 190 + [near, far])
    assert ok["ok"] and ok["worst"] == 3.0 and ok["tokens"] == 192
    assert ok["decided_share"] == pytest.approx(191 / 192)
    assert ok["undecided_worst"] == 3.0 and ok["undecided_mean"] == 3.0
    assert ok["limits"] == rc.ROUTED_LIMITS
    # a fault in every token fails, however small the worst
    every = rc.routed_report([2.5 * rc.LOGPROB_MEAN_TOL] * 192, [far] * 192)
    assert not every["ok"] and every["worst"] < rc.LOGPROB_TOL
    # a fault in a share of the decided tokens fails
    share = rc.ROUTED_OVER_SHARE_MAX * 2
    n = round(192 * share)
    some = rc.routed_report([0.001] * (192 - n) + [rc.LOGPROB_TOL * 1.01] * n,
                            [far] * 192)
    assert not some["ok"]
    assert some["decided_mean"] <= rc.LOGPROB_MEAN_TOL
    # too few decided tokens to judge by: not ok, whatever they read
    few = rc.routed_report([0.001] * 192, [near] * 190 + [far] * 2)
    assert not few["ok"]
    assert few["decided_share"] < rc.ROUTED_DECIDED_SHARE_MIN
    assert not rc.routed_report([0.001] * 192, [near] * 192)["ok"]


def test_dense_report_is_worst_and_mean():
    from benchmark import reference_check as rc

    report = rc.dense_report([0.01, 0.02, 0.03])
    assert report["ok"] and report["tokens"] == 3
    assert report["worst"] == 0.03
    assert report["mean"] == pytest.approx(0.02)
    assert not rc.dense_report([0.01] * 17 + [rc.LOGPROB_TOL * 1.1])["ok"]
    assert not rc.dense_report([rc.LOGPROB_MEAN_TOL * 1.1] * 18)["ok"]


def _routed_model(**kw):
    """Where routing has near ties and a CPU can hold the run: 8
    experts, top-2, a few hundred positions. 4.0 is the capacity
    factor at which the program drops nothing."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    base = dict(vocab_size=2048, dim=256, n_layers=4, n_heads=8,
                n_kv_heads=2, hidden_dim=896, moe_experts=8, moe_top_k=2,
                moe_capacity_factor=4.0, max_seq_len=512,
                dtype=jnp.bfloat16, attention="reference", remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _served(seed, model, wrong_weights=None):
    """The program serves 3 x 64 tokens as check_serving has it do; the
    reference scores them on the weights as the seed gives them, as a
    top-2 model. -> (differences, margins)."""
    import jax

    from benchmark import reference_check as rc
    from benchmark.reference import mistral as ref
    from ray_tpu.llm.engine import ContinuousBatchingEngine, EngineConfig
    from ray_tpu.models.llama import llama_init

    params = jax.jit(llama_init, static_argnums=1)(
        jax.random.PRNGKey(seed), model)
    engine = ContinuousBatchingEngine(
        EngineConfig(model=model, max_batch=8, max_seq=512, seed=seed),
        params=wrong_weights(params) if wrong_weights else params)
    try:
        generated = rc.generate(engine, [100, 200, 300], 64, seed)
    finally:
        engine.close()
    return rc.differences(generated, params, ref,
                          _routed_model(dtype=model.dtype))


def _w2_of_two_experts_swapped(params):
    layers = dict(params["layers"])
    w2 = layers["w2"]
    layers["w2"] = w2.at[:, 0].set(w2[:, 1]).at[:, 1].set(w2[:, 0])
    return {**params, "layers": layers}


def _experts_in_8_bits(params):
    """fp8 (4 exponent bits, 3 of mantissa) with a scale for each
    output channel: the nearest precision under bf16 that the
    comparison can tell from it. (int8 with such a scale, 127 even
    steps, reads 1.4 to 1.9 times the sound program, on the chip as
    here: no limit with room over a sound seed catches it.)"""
    import jax
    import jax.numpy as jnp
    layers = dict(params["layers"])
    for name in ("w1", "w3", "w2"):
        w = layers[name].astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 240.0
        layers[name] = (jax.lax.reduce_precision(
            w / scale, exponent_bits=4, mantissa_bits=3)
            * scale).astype(layers[name].dtype)
    return {**params, "layers": layers}


def _gates_not_renormalised(x, router, k):
    import jax
    import jax.numpy as jnp
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(vals)
    return gates, idx, jnp.zeros((), jnp.float32)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_routed_comparison_passes_the_drop_free_program(seed):
    from benchmark import reference_check as rc

    report = rc.routed_report(*_served(seed, _routed_model()))
    assert report["ok"], report
    assert report["tokens"] == 192
    # near-tie routing is there: tokens the dense limit would fail
    assert report["worst"] > rc.LOGPROB_TOL


WRONG = {
    # the program's default capacity, prompts padded to 128, 256, 512
    "capacity_2_padded_bucket": (dict(moe_capacity_factor=2.0), None),
    "top_1_for_top_2": (dict(moe_top_k=1), None),
    "gates_not_renormalised": ({}, None),
    "w2_of_two_experts_swapped": ({}, _w2_of_two_experts_swapped),
    "experts_in_8_bits": ({}, _experts_in_8_bits),
}


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_routed_comparison_fails_a_wrong_program(fault, monkeypatch):
    from benchmark import reference_check as rc
    from ray_tpu.parallel import moe

    if fault == "gates_not_renormalised":
        monkeypatch.setattr(moe, "top_k_gating", _gates_not_renormalised)
    overrides, wrong_weights = WRONG[fault]
    report = rc.routed_report(
        *_served(1, _routed_model(**overrides), wrong_weights))
    assert not report["ok"], report


def test_drop_free_float32_program_matches_every_token():
    """Where a fault in one token in a hundred is caught: in float32
    the two choose the same experts, and every token has to agree."""
    import jax.numpy as jnp

    diffs, margins = _served(1, _routed_model(dtype=jnp.float32))
    assert len(diffs) == 192 and max(diffs) <= 1e-4
    assert min(margins) < 0.01      # near ties were among them
