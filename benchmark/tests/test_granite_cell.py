"""The Granite configuration and its cell: the configuration file
against the catalog, the program module's five functions, the traffic,
the two counter shares, a rehearsal of the whole serving path. On the
CPU; nothing here gives a device number."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, reference_check  # noqa: E402
from benchmark.readers import counter_share  # noqa: E402
from benchmark.runners import serve_open_loop  # noqa: E402

CELL = "granite4h-agent-steady"
CONFIG = "granite-4.0-h-small-L10-ep2"
TRAFFIC = "agent-long-answers-steady"
# the serving cell whose metrics this one reports too, all but the scan
# kernel's
LIKE = "jamba2-3b-chat-steady"
NOT_LIKE = ("scan_roofline", "scan_time_share")
# sanity counters of the expert layer, through the reader that is there
SHARES = {"expert_held_share": "Model and sharding",
          "expert_hit_share": "Engine"}
SEED = 3000000011
REDUCED = {"num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"}


def cell(rehearse: bool = False):
    return harness.load_cell(CELL, rehearse)


def _args(**kw):
    base = dict(seed=SEED, seconds=40.0, rehearse=False, sweep=None,
                trace=0, dump=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_configuration_file_holds_the_catalog_rows_numbers():
    config = harness.load_json("configs", CONFIG + ".json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-small")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][:10]
        for key, value in config["published"].items():
            if key != "parameters":
                assert row["config"][key] == value, key
    assert set(config["reduced"]) == REDUCED
    # a whole period, 36 >= 8 experts, half >= an eighth of the rows
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"].count("attention") == 1
    assert (config["num_local_experts"], config["router_outputs"],
            config["experts_held"]) == (36, 72, [0, 36])
    assert config["vocab_size"] * 2 == config["published"]["vocab_size"]
    assert (config["program"], config["reference"]) == ("granite",
                                                        "granite")
    # 384 tokens, and the limits of a router of many small experts
    # with the runs they were read from
    check = config["check"]
    assert (check["prompt_lens"], check["new_tokens"]) \
        == ([100, 200, 300] * 2, 64)
    assert set(check["limits"]) == {"router_margin", "decided_mean",
                                    "decided_share_at_least",
                                    "decided_median"}
    assert set(check["calibration"]["controls"]) == set(
        harness.program_for("granite").controls(config))


def test_build_gives_the_published_widths_and_this_ranks_share():
    from ray_tpu.models.granite import GraniteConfig

    built = serve_open_loop.build(cell(), _args())
    model = built.engine.model
    assert model == GraniteConfig(
        vocab_size=50176, layer_types=GraniteConfig().layer_types[:10],
        experts_held=(0, 36), max_seq_len=2560)
    assert (model.n_mamba_layers, model.n_attn_layers) == (9, 1)
    assert (model.n_experts, model.top_k, model.expert_dim,
            model.shared_expert_dim) == (72, 10, 768, 1536)
    assert (built.engine.max_batch, built.engine.max_seq) == (32, 2560)
    # 384 tokens, judged as a router's, by the file's margin and floor
    assert (built.check_lens, built.check_tokens) \
        == ([100, 200, 300] * 2, 64)
    assert built.routed and built.drain
    limits = built.check_limits
    assert (limits["router_margin"], limits["decided_share_at_least"]) \
        == (0.005, 0.2)
    assert limits == {**reference_check.ROUTED_LIMITS,
                      **cell()["config_file"]["check"]["limits"]}


def test_build_rehearsing_keeps_both_kinds_of_layer_and_the_share():
    import jax.numpy as jnp

    built = serve_open_loop.build(cell(True), _args(rehearse=True))
    model = built.engine.model
    assert model.layer_kinds == ("mamba", "mamba", "attn", "mamba")
    assert (model.n_experts, model.experts_held, model.top_k) \
        == (8, (0, 4), 3)
    assert model.dtype == jnp.float32 and model.attention == "reference"
    assert (built.engine.max_batch, built.engine.max_seq) == (4, 256)


def test_the_program_modules_five_functions_answer():
    granite = harness.program_for("granite")
    config = harness.load_json("configs", CONFIG + ".json")
    assert granite.serving_model(config, 2560, False).max_seq_len == 2560
    with pytest.raises(harness.BenchError, match="no training path"):
        granite.training(config, {}, False)
    assert granite.vocab_size(config, False) == 50176
    assert granite.vocab_size(config, True) == 512
    assert granite.kernels("prefill_256") == granite.kernels("prefill_1024") \
        == ["flash_fwd", "rms_norm"]
    assert granite.kernels("decode") == granite.kernels("decode_lp") \
        == ["decode_attention", "rms_norm", "ssd_update"]
    with pytest.raises(harness.BenchError):
        granite.kernels("train_step")
    # the routed report judges it, by the file's limits (PERF.md 3)
    assert granite.routed(config)
    with pytest.raises(harness.BenchError):
        granite.serving_model(
            {**config, "position_embedding_type": "rope"}, 2560, False)
    with pytest.raises(harness.BenchError):
        granite.serving_model({**config, "experts_held": [0, 72]}, 2560,
                              False)


def test_the_drivers_questions_keep_the_family_off_jax():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark import harness\n"
        "c = harness.load_json('configs', %r + '.json')\n"
        "p = harness.program_for(c['program'])\n"
        "assert p.vocab_size(c, False) == 50176\n"
        "p.kernels('prefill_128'), p.kernels('decode'), p.routed(c)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        % (ROOT, CONFIG))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_traffic_is_the_issues_mix():
    mix = cell()["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["gap"] == {"dist": "lognormal", "median": 1.0, "sigma": 1.0}
    chat = harness.load_json("traffic", "chat-short-steady.json")
    assert mix["prompt_bytes"] == chat["prompt_bytes"]
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.6, "min": 64, "max": 1536}
    assert mix["order"] == {"strata": 16}
    assert (mix["temperature"], mix["shared_prefix"], mix["drain"],
            mix["drain_timeout_s"]) == (0.0, "none", True, 90.0)
    assert (mix["trace_after_s"], mix["trace_seconds"]) == (10.0, 3.0)
    assert 0 < mix["rate_rps"] < 10 and "sweep" in mix["rate_from"].lower()
    # the longest request fits the cache
    assert 760 + 1 + 1536 < cell()["config_file"]["serving"]["max_seq"]


def test_cell_reports_what_the_jamba_cell_does_and_the_two_shares():
    mine = cell()
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert [m["name"] for m in mine["end_to_end"]] \
        == [m["name"] for m in harness.load_cell(LIKE)["end_to_end"]]
    names = [m["name"] for m in mine["per_layer"]]
    assert names == [m["name"] for m in harness.load_cell(LIKE)["per_layer"]
                     if m["name"] not in NOT_LIKE] + list(SHARES)
    for m in mine["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["layer"] == SHARES[m["name"]]
        assert (m["source"], m["moves"]) == ("program_counter", "itl_p90_ms")
    for name in names:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.reader_for(spec["reader"])


@pytest.mark.parametrize("name,labels", [
    ("expert_held_share", ('where="held"', 'where="absent"')),
    ("expert_hit_share", ('state="hit"', 'state="idle"'))])
def test_counter_shares_from_hand_made_series(name, labels):
    spec = harness.load_json("layer_metrics", name + ".json")
    assert spec["reader"] == "counter_share"
    part, rest = spec["args"]["whole"]
    assert spec["args"]["part"] == part
    assert labels[0] in part and labels[1] in rest
    observed = {"series_before": {part: 100.0, rest: 300.0},
                "series_after": {part: 400.0, rest: 1000.0}}
    assert counter_share.read(observed, **spec["args"]) == pytest.approx(30.0)
    # the parent has no such series; an untraced run has no series
    assert counter_share.read({"series_before": {}, "series_after": {}},
                              **spec["args"]) is None
    assert counter_share.read({"series_after": None},
                              **spec["args"]) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_the_cpu(trace, tmp_path):
    """benchmark/run.py's path: HTTP proxy -> replica -> engine of the
    Granite family."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", str(SEED),
         "--seconds", "4", "--trace", str(trace), "--dump", str(tmp_path)],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and not line["correct"]
    check = next(l for l in done.stdout.splitlines()
                 if "reference check:" in l)
    report = json.loads(check.split("reference check:", 1)[1])
    assert report["ok"] and report["tokens"] == 384
    assert report["worst"] < 1e-4
    # the file's limits, not the constants, judged it
    given = cell()["config_file"]["check"]["limits"]
    assert {k: report["limits"][k] for k in given} == given
    assert report["router_margin"] == given["router_margin"]
    # the last line ends with every number compared, beside its limit:
    # a rehearsal is not correct by being one, and by nothing here
    assert list(line)[-1] == "compared"
    got = line["compared"]
    assert got["decided_mean"] == [report["decided_mean"],
                                   given["decided_mean"]]
    assert got["decided_share"] == [report["decided_share"],
                                    given["decided_share_at_least"]]
    for name in ("check", "kernels_ok", "platform_ok"):
        assert got[name] == [1, 1]
    for name in ("wrong_counts", "unsent", "replica_replaced", "fallbacks",
                 "compiled_in_window_s"):
        assert got[name] == [0, 0]
    assert got["lag_worst_s"][0] >= 0 and got["ttft_max_ms"][0] > 0
    for name, (value, limit) in got.items():
        assert f"benchmark: compared {name}: {value} limit {limit}" \
            in done.stderr
    if trace:
        # a rehearsal's line holds no metric; the dumped series do. 8
        # experts of which 4 are held; a few rows of 3 picks
        with open(tmp_path / (CELL + ".serve.json")) as f:
            shares = harness.per_layer_values(
                {"per_layer": cell()["per_layer"][-2:]}, json.load(f))
        assert 30 < shares["expert_held_share"]["value"] < 70
        assert 0 < shares["expert_hit_share"]["value"] <= 100


def test_controls_through_the_harness_on_the_cpu():
    """``run.py --control``: the reference check alone, the program
    made wrong by name. In float32 the sound program agrees to 1e-4
    and the dropped expert reads tenths; the state in bf16 reads under
    the limits here and is what the chip's calibration is for."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", str(SEED),
         "--control", "none,expert_zeroed,state_bf16"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith('{"control"')]
    assert [l["control"] for l in lines] == ["none", "expert_zeroed",
                                             "state_bf16"]
    # the last line is a control's report and no cell's result
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last == lines[-1]
    assert not {"correct", "attempted", "failed", "metrics"} & set(last)
    assert "window" not in done.stdout and "generator lag" not in done.stdout
    sound, zeroed, rounded = (l["report"] for l in lines)
    assert sound["ok"] and sound["worst"] < 1e-4 and sound["tokens"] == 384
    assert not zeroed["ok"] and zeroed["mean"] > 100 * sound["mean"]
    assert zeroed["decided_mean"] > zeroed["limits"]["decided_mean"]
    assert sound["mean"] < rounded["mean"] < zeroed["mean"]
    for report in (sound, zeroed, rounded):
        assert [r["router_margin"] for r in report["readings"]["routed"]] \
            == [0.005, 0.01, 0.02, 0.04]
        assert report["readings"]["dense"]["mean"] == report["mean"]
        assert all(l["seed"] == SEED % (2**31 - 1) for l in lines)


def test_a_control_the_program_does_not_have_is_refused():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--control", "experts_in_int4"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert done.returncode == 1 and not done.stdout.strip()
    assert "has no control" in done.stderr
