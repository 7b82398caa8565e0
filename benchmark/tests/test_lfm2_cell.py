"""The LFM2 configuration and its cell: the configuration file against
the catalog, the program module's functions and controls, the traffic,
the cell's metric lists, the two new metrics' readers (with and without
anything to read), a rehearsal of the whole serving path. On the CPU;
nothing here gives a device number."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, ops_lfm2, reference_check  # noqa: E402
from benchmark.readers import counter_share, decode_read_floor  # noqa: E402
from benchmark.runners import serve_open_loop  # noqa: E402

CELL = "lfm2-8b-tools-steady"
CONFIG = "lfm2-8b-a1b-L14"
TRAFFIC = "tool-answers-steady-lfm2"
# the serving cell whose metrics this one reports too, all but the
# share of picks that fell on held experts (100% where none is absent)
LIKE = "granite4h-agent-steady"
NOT_LIKE = ("expert_held_share",)
# the stall watch's two, which the Mistral steady cell reports and the
# Granite cell's pinned list lacks: the same replica and stepper here
STALLS = ("replica_stall_share.lat", "stepper_held_share.lat")
# what this cell alone reports
OWN = {"router_bias_moved_share": "Model and sharding",
       "decode_read_floor_share": "Engine"}
SEED = 3000000011
REDUCED = {"num_hidden_layers", "layer_types"}


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
                       if r["name"] == "LFM2-8B-A1B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][:14]
        assert config["published"]["num_hidden_layers"] \
            == row["config"]["num_hidden_layers"]
    assert set(config["reduced"]) == REDUCED
    # the 2 leading dense layers, then 3 whole periods with all experts
    assert config["num_hidden_layers"] == 14 == len(config["layer_types"])
    assert config["layer_types"][:2] == ["conv", "conv"]
    assert config["layer_types"][2:] == ["full_attention", "conv", "conv",
                                         "conv"] * 3
    assert (config["num_dense_layers"], config["num_experts"],
            config["num_experts_per_tok"]) == (2, 32, 4)
    assert (config["program"], config["reference"], config["chips"]) \
        == ("lfm2", "lfm2", 1)
    assert config["serving"] == {"max_batch": 32, "max_seq": 1536,
                                 "max_ongoing_requests": 256}
    for key in ("head_dim", "tie_word_embeddings", "in_proj_order",
                "input_linear", "initialisation", "state", "tokenizer"):
        assert key in config["assumed"], key
    assert 0 < config["router_bias_std"] < 0.2
    assert str(config["router_bias_std"]) in \
        config["assumed"]["initialisation"]
    # 4 800 M parameters, 9.6 GB: the file's arithmetic is the shapes'
    assert 4.79e9 < ops_lfm2.model_params(config) < 4.81e9
    check = config["check"]
    assert (check["prompt_lens"], check["new_tokens"]) \
        == ([100, 200, 300] * 2, 64)
    assert set(check["limits"]) <= set(reference_check.ROUTED_LIMITS)
    assert "router_margin" in check["limits"]
    assert set(check["calibration"]["controls"]) == set(
        harness.program_for("lfm2").controls(config))


def test_build_gives_the_published_widths_and_the_cut():
    from ray_tpu.models.lfm2 import Lfm2Config

    built = serve_open_loop.build(cell(), _args())
    model = built.engine.model
    config = cell()["config_file"]
    assert model == Lfm2Config(
        layer_types=Lfm2Config().layer_types[:14], max_seq_len=1536,
        router_bias_std=config["router_bias_std"])
    assert (model.n_conv_layers, model.n_attn_layers, model.n_moe_layers,
            model.n_dense_layers) == (11, 3, 12, 2)
    assert (model.dim, model.head_dim, model.dense_dim, model.expert_dim,
            model.n_experts, model.top_k) == (2048, 64, 7168, 1792, 32, 4)
    assert (built.engine.max_batch, built.engine.max_seq) == (32, 1536)
    assert (built.check_lens, built.check_tokens) \
        == ([100, 200, 300] * 2, 64)
    assert built.routed and built.drain
    assert built.check_limits == {**reference_check.ROUTED_LIMITS,
                                  **config["check"]["limits"]}


def test_build_rehearsing_keeps_every_kind_of_layer():
    import jax.numpy as jnp

    built = serve_open_loop.build(cell(True), _args(rehearse=True))
    model = built.engine.model
    assert model.layer_kinds == ("conv+dense", "attn+moe", "conv+moe",
                                 "conv+moe", "attn+moe")
    assert (model.n_experts, model.top_k, model.routed_scaling) \
        == (8, 3, 1.5)
    assert model.dtype == jnp.float32 and model.attention == "reference"
    assert (built.engine.max_batch, built.engine.max_seq) == (4, 256)


def test_the_program_modules_functions_answer():
    lfm2 = harness.program_for("lfm2")
    config = harness.load_json("configs", CONFIG + ".json")
    assert lfm2.serving_model(config, 1536, False).max_seq_len == 1536
    with pytest.raises(harness.BenchError, match="no training path"):
        lfm2.training(config, {}, False)
    assert lfm2.vocab_size(config, False) == 65536
    assert lfm2.vocab_size(config, True) == 512
    assert lfm2.kernels("prefill_128") == lfm2.kernels("prefill_1024") \
        == ["flash_fwd", "rms_norm"]
    assert lfm2.kernels("decode") == lfm2.kernels("decode_lp") \
        == ["decode_attention", "rms_norm"]
    with pytest.raises(harness.BenchError):
        lfm2.kernels("train_step")
    assert lfm2.routed(config)
    assert sorted(lfm2.controls(config)) == [
        "bias_dropped", "conv_tap_zeroed", "expert_zeroed"]
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("norm_topk_prob", False),
                       ("num_hidden_layers", 24)):
        with pytest.raises(harness.BenchError):
            lfm2.serving_model({**config, key: value}, 1536, False)


def test_program_module_stays_off_jax_and_fails_cleanly_without_the_family():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark import harness\n"
        "c = harness.load_json('configs', %r + '.json')\n"
        "p = harness.program_for(c['program'])\n"
        "assert p.vocab_size(c, False) == 65536\n"
        "p.kernels('prefill_128'), p.kernels('decode'), p.routed(c)\n"
        "p.controls(c)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        # the parent: no ray_tpu.models.lfm2 to import
        "sys.modules['ray_tpu.models.lfm2'] = None\n"
        "try:\n"
        "    p.serving_model(c, 1536, False)\n"
        "except harness.BenchError as exc:\n"
        "    assert 'no LFM2 family' in str(exc)\n"
        "else:\n"
        "    raise AssertionError('no BenchError')\n"
        % (ROOT, CONFIG))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_traffic_is_the_issues_mix():
    mix = cell()["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["gap"] == {"dist": "lognormal", "median": 1.0, "sigma": 1.0}
    chat = harness.load_json("traffic", "chat-short-steady.json")
    assert mix["prompt_bytes"] == chat["prompt_bytes"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.7, "min": 96,
        "max": 760}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.6, "min": 32, "max": 768}
    assert mix["order"] == {"strata": 16}
    assert (mix["temperature"], mix["shared_prefix"], mix["drain"],
            mix["drain_timeout_s"]) == (0.0, "none", True, 60.0)
    assert (mix["trace_after_s"], mix["trace_seconds"]) == (8.0, 3.0)
    # the issue's rule: 0.8 x the knee the sweep found (6.0), which is
    # also where 8% of the window's steps admit a prompt
    assert mix["rate_rps"] == 4.8 and "sweep" in mix["rate_from"].lower()
    assert "0.8 x the knee of 6.0" in mix["rate_from"]
    # the longest request fits the cache
    assert 760 + 1 + 768 <= cell()["config_file"]["serving"]["max_seq"]


def test_cell_reports_what_the_granite_cell_does_and_its_own_two():
    mine = cell()
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert [m["name"] for m in mine["end_to_end"]] \
        == [m["name"] for m in harness.load_cell(LIKE)["end_to_end"]] \
        == ["ttft_p50_ms", "itl_p90_ms", "setup_s"]
    names = [m["name"] for m in mine["per_layer"]]
    assert names == [m["name"] for m in harness.load_cell(LIKE)["per_layer"]
                     if m["name"] not in NOT_LIKE] + list(STALLS) + list(OWN)
    steady = [m["name"] for m in
              harness.load_cell("mistral7b-chat-steady")["per_layer"]]
    assert set(STALLS) <= set(steady)
    for m in mine["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["layer"] == OWN[m["name"]]
        assert (m["source"], m["moves"], m["unit"], m["better"]) \
            == ("program_counter", "itl_p90_ms", "%", "higher")
    for name in names:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.reader_for(spec["reader"])
    # no other cell reports the new two
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not set(OWN) & {
                m["name"] for m in harness.load_cell(w["name"])["per_layer"]}


# what an engine of the parent exports and an untraced run observes: no
# such series, an empty trace
_NOTHING = [{"series_before": {}, "series_after": {}, "trace": {"ops": {}}},
            {"series_after": None, "trace": None},
            {"series_before": {"ray_tpu_engine_step_seconds_count"
                               "{phase=\"decode\"}": 5.0},
             "series_after": {"ray_tpu_engine_step_seconds_count"
                              "{phase=\"decode\"}": 9.0},
             "trace": {"ops": {}}}]


def test_router_bias_moved_share_from_hand_made_series():
    spec = harness.load_json("layer_metrics",
                             "router_bias_moved_share.json")
    assert spec["reader"] == "counter_share"
    moved, kept = spec["args"]["whole"]
    assert spec["args"]["part"] == moved
    assert 'bias="moved"' in moved and 'bias="kept"' in kept
    observed = {"series_before": {moved: 100.0, kept: 900.0},
                "series_after": {moved: 250.0, kept: 1750.0}}
    assert counter_share.read(observed, **spec["args"]) \
        == pytest.approx(15.0)
    for nothing in _NOTHING:
        assert counter_share.read(nothing, **spec["args"]) is None


def test_decode_read_floor_share_from_hand_made_series():
    spec = harness.load_json("layer_metrics",
                             "decode_read_floor_share.json")
    assert spec["reader"] == "decode_read_floor"
    config = harness.load_json("configs", CONFIG + ".json")
    steps = 1000.0
    # a step: 30 of a layer's 32 experts hit, 20 slots at 300 rows
    # (three blocks of 128 each) and 12 parked on a block
    hit = steps * 12 * 30
    rows = steps * (20 * 384 + 12 * 128)
    series = {
        'ray_tpu_engine_expert_slots_total{state="hit"}': hit,
        'ray_tpu_engine_expert_slots_total{state="idle"}':
            steps * 12 * 32 - hit,
        'ray_tpu_engine_decode_kv_rows_total{kind="read"}': rows,
        'ray_tpu_engine_decode_kv_rows_total{kind="skipped"}':
            steps * 32 * 1536 - rows,
        'ray_tpu_engine_step_seconds_count{phase="decode"}': 900.0,
        'ray_tpu_engine_step_seconds_sum{phase="decode"}': 900.0 * 0.015}
    observed = {"series_before": {k: 0.0 for k in series},
                "series_after": series, "trace": None,
                "cell": {"config_file": config}}
    floor = ops_lfm2.decode_floor_bytes(config, 12 * 30,
                                        20 * 384 + 12 * 128)
    # mixers, dense layers, routers and head: 0.88 GB; 360 experts of
    # 22 MB: 7.9 GB; 9216 rows of 3 layers at 2 KiB: 57 MB
    assert 0.85e9 < floor["always"] < 0.9e9
    assert floor["experts"] == 360 * 3 * 2048 * 1792 * 2
    assert floor["kv"] == 9216 * 3 * 2048
    want = 100.0 * sum(floor.values()) / 819e9 / 0.015
    got = decode_read_floor.read(observed, **spec["args"])
    assert got == pytest.approx(want) and 60 < got < 80
    # every expert hit and every row read is the most it can say, and
    # that is what a program that reads everything is held to
    full = ops_lfm2.decode_floor_bytes(config, 12 * 32, 32 * 1536)
    assert sum(full.values()) < 2 * ops_lfm2.model_params(config) + 0.4e9
    for nothing in _NOTHING:
        assert decode_read_floor.read(
            {**nothing, "cell": {"config_file": config}},
            **spec["args"]) is None
    # and a cell of another family, whose file has no such keys
    other = harness.load_cell("granite4h-agent-steady")
    for nothing in _NOTHING:
        assert decode_read_floor.read({**nothing, "cell": other},
                                      **spec["args"]) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_the_cpu(trace, tmp_path):
    """benchmark/run.py's path: HTTP proxy -> replica -> engine of the
    LFM2 family."""
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
    assert list(line)[-1] == "compared"
    got = line["compared"]
    assert got["decided_share"] == [report["decided_share"],
                                    given["decided_share_at_least"]]
    for name in ("check", "kernels_ok", "platform_ok"):
        assert got[name] == [1, 1]
    for name in ("wrong_counts", "unsent", "replica_replaced", "fallbacks",
                 "compiled_in_window_s"):
        assert got[name] == [0, 0]
    if trace:
        # a rehearsal's line holds no metric; the dumped series do
        with open(tmp_path / (CELL + ".serve.json")) as f:
            dumped = json.load(f)
        shares = harness.per_layer_values(
            {"per_layer": [m for m in cell()["per_layer"]
                           if m["name"] in (*OWN, "expert_hit_share")]},
            {**dumped, "cell": cell(True)})
        assert 2 < shares["router_bias_moved_share"]["value"] < 60
        assert 0 < shares["expert_hit_share"]["value"] <= 100
        # the floor reader finds its series (the number is a CPU's and
        # the tiny model's: read, not judged)
        assert shares["decode_read_floor_share"]["value"] > 0
        assert dumped["stats_after"]["router_picks"]["moved"] > 0


def test_controls_through_the_harness_on_the_cpu():
    """``run.py --control``: the reference check alone, the program made
    wrong by name. In float32 the sound program agrees to 1e-4 and each
    control reads hundreds of times that and is not ``ok``."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", str(SEED), "--control",
         "none,expert_zeroed,bias_dropped,conv_tap_zeroed"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith('{"control"')]
    assert [l["control"] for l in lines] == [
        "none", "expert_zeroed", "bias_dropped", "conv_tap_zeroed"]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last == lines[-1]
    assert not {"correct", "attempted", "failed", "metrics"} & set(last)
    sound, *wrong = (l["report"] for l in lines)
    assert sound["ok"] and sound["worst"] < 1e-4 and sound["tokens"] == 384
    for report in wrong:
        assert not report["ok"] and report["mean"] > 100 * sound["mean"]
        assert report["decided_mean"] > report["limits"]["decided_mean"]
