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
                    "deployment", "reference"):
            assert key in data, (c["name"], key)
        assert data["source"] == c["source"]
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

def test_reference_agrees_with_the_program_on_the_cpu():
    """Tiny sizes, float32: the program's loss and the reference's on
    the same seeded weights, dense (grouped-query) and with experts
    (4 experts, top-2: at that size no expert can overflow, so the
    program drops nothing and has to agree)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mistral as ref
    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    for experts in (0, 4):
        cfg = LlamaConfig.tiny(vocab_size=300, moe_experts=experts,
                               moe_aux_weight=0.0)
        params = llama_init(jax.random.PRNGKey(7), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 33), 0, 300)
        x, y = tokens[:, :-1], tokens[:, 1:]
        got = float(llama_loss(params, x, y, cfg, None))
        want = sum(float(ref.loss(params, x[i], y[i],
                                  **ref.kwargs_from(cfg)))
                   for i in range(2)) / 2
        assert got == pytest.approx(want, abs=1e-4), experts
