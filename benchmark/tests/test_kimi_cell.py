"""The Kimi-K2.7-Code configuration and its cell: the configuration file
against the catalog, the program module's functions and controls, the
traffic, the cell's metric lists, the two new metrics' readers (with
and without anything to read), the operation counts against the
issue's arithmetic, a rehearsal of the whole serving path. On the CPU;
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

from benchmark import harness, ops_kimi, reference_check  # noqa: E402
from benchmark.readers import latent_step_floor, prefill_mfu  # noqa: E402
from benchmark.runners import serve_open_loop  # noqa: E402

CELL = "kimi-k2-code-context-steady"
CONFIG = "kimi-k2.7-code-L7-ep32"
TRAFFIC = "code-context-steady-kimi"
# the serving cell whose metrics this one reports too, all but its own
# two (pinned to it by its test)
LIKE = "lfm2-8b-tools-steady"
NOT_LIKE = ("router_bias_moved_share", "decode_read_floor_share")
# what this cell alone reports, and the end-to-end metric each moves
OWN = {"latent_step_floor_share": "itl_p90_ms",
       "prefill_mfu": "ttft_p50_ms"}
CONTROLS = ["bias_dropped", "expert_zeroed", "mscale_dropped",
            "rope_lanes_zeroed", "weights_fp8"]
SEED = 3000000011
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}


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
                       if r["name"] == "Kimi-K2.7-Code")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert config[key] == value, key
        for key in REDUCED:
            assert config["published"][key] == row["config"][key]
    assert set(config["reduced"]) == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED
    # layer 0 dense and six routed layers, 12 of 384 experts, an eighth
    # of the vocabulary: the guide's floors are 4, 8 and an eighth
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["router_outputs"],
            config["experts_held"], config["vocab_size"]) \
        == (7, 1, 12, 384, [0, 12], 20480)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (config["program"], config["reference"], config["chips"]) \
        == ("mla", "mla", 1)
    assert config["serving"] == {"max_batch": 32, "max_seq": 4608,
                                 "max_ongoing_requests": 256}
    for key in ("rotary_pairing", "yarn", "kv_b_proj", "input_linear",
                "router", "shared_expert", "initialisation", "cache",
                "tokenizer"):
        assert key in config["assumed"], key
    assert "32" in config["deployment"] and "rank 0" in config["deployment"]
    assert "data-parallel attention" in config["deployment"]
    assert 0 < config["router_bias_std"] < 0.05
    assert str(config["router_bias_std"]) in \
        config["assumed"]["initialisation"]
    check = config["check"]
    assert check["new_tokens"] == 64
    # two prompts and more are 2048 tokens or longer: a decode step
    # then reads five blocks of 512 latent rows
    assert sum(n >= 2048 for n in check["prompt_lens"]) >= 2
    assert max(check["prompt_lens"]) <= config["serving"]["max_seq"] // 2
    assert set(check["limits"]) <= set(reference_check.ROUTED_LIMITS)
    assert "router_margin" in check["limits"]
    assert set(check["calibration"]["controls"]) == set(CONTROLS)


def test_the_counts_are_the_issues_arithmetic():
    """ISSUE 69's parameters, worked from the shapes: attention 101.12 M
    a layer, layer 0 with its dense feed-forward 497.5 M, a routed layer
    676.4 M, embedding and head 146.8 M each, 4 850 M in all; what a
    decode step reads whatever the experts and the cache, 3.06 GB; an
    expert 88.1 MB; a latent row 1152 B; the 1 385 M parameters a
    prefill's every token meets."""
    config = harness.load_json("configs", CONFIG + ".json")
    p = ops_kimi.params(config)
    assert p["attn"] - (7168 + 1536 + 512) == (
        7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
        + 8192 * 7168)
    assert round(p["attn"] / 1e6, 1) == 101.1
    assert round((p["attn"] + p["dense"]) / 1e6, 1) == 497.5
    routed_layer = (p["attn"] + p["router"] + p["shared"]
                    + 12 * p["expert"])
    assert round(routed_layer / 1e6, 1) == 676.4
    assert round(p["embedding"] / 1e6, 1) == 146.8
    assert p["head"] == p["embedding"] + 7168
    assert ops_kimi.layer_counts(config) == {"attn": 7, "dense": 1,
                                             "moe": 6}
    total = ops_kimi.model_params(config)
    assert 4.849e9 < total < 4.852e9
    assert round(2 * total / 1e9, 2) == 9.70
    assert round(ops_kimi.always_params(config) / 1e6) == 1385
    floor = ops_kimi.decode_floor_bytes(config, 1.0, 1.0)
    assert round(floor["always"] / 1e9, 2) == 3.06
    assert round(floor["experts"] / 1e6, 1) == 88.1
    assert floor["latent"] == 7 * 1152
    # a prompt of 2048 tokens: 5.67 TFLOP outside the experts and the
    # head, 0.60 of attention at the true widths
    ops = ops_kimi.prefill_floor_ops(config, 2048, 1)
    attention = 7 * 64 * 320 * 2048 ** 2
    assert ops == pytest.approx(2 * 2048 * ops_kimi.always_params(config)
                                + 2 * p["head"] + attention)
    assert 0.09 < attention / ops < 0.10
    # two prompts of 2048 need what one needs twice, and fewer
    # operations than one of 4096
    assert ops_kimi.prefill_floor_ops(config, 4096, 2) \
        == pytest.approx(2 * ops)
    assert ops_kimi.prefill_floor_ops(config, 4096, 1) > 2 * ops


def test_build_gives_the_published_widths_and_the_cut():
    from ray_tpu.models.mla import MlaConfig

    built = serve_open_loop.build(cell(), _args())
    model = built.engine.model
    config = cell()["config_file"]
    assert model == MlaConfig(
        vocab_size=20480, n_layers=7, experts_held=(0, 12),
        max_seq_len=4608, router_bias_std=config["router_bias_std"])
    assert (model.dim, model.n_heads, model.q_lora_rank,
            model.kv_lora_rank, model.qk_nope_dim, model.qk_rope_dim,
            model.v_head_dim, model.dense_dim, model.expert_dim,
            model.shared_expert_dim, model.n_experts, model.top_k) \
        == (7168, 64, 1536, 512, 128, 64, 128, 18432, 2048, 2048, 384, 8)
    assert (model.n_dense_layers, model.n_moe_layers) == (1, 6)
    assert model.sm_scale == pytest.approx(0.144680, rel=1e-5)
    assert (built.engine.max_batch, built.engine.max_seq) == (32, 4608)
    assert built.check_lens == config["check"]["prompt_lens"]
    assert built.check_tokens == 64 and built.routed and built.drain
    assert built.check_limits == {**reference_check.ROUTED_LIMITS,
                                  **config["check"]["limits"]}


def test_build_rehearsing_keeps_every_kind_of_layer():
    import jax.numpy as jnp

    built = serve_open_loop.build(cell(True), _args(rehearse=True))
    model = built.engine.model
    assert (model.n_layers, model.n_dense_layers, model.n_moe_layers) \
        == (4, 1, 3)
    assert (model.n_experts, model.experts_held, model.top_k,
            model.routed_scaling) == (16, (0, 4), 3, 1.5)
    assert model.rope_factor == 4.0 and model.router_bias_std > 0
    assert model.dtype == jnp.float32 and model.attention == "reference"
    assert (built.engine.max_batch, built.engine.max_seq) == (4, 512)


def test_the_program_modules_functions_answer():
    mla = harness.program_for("mla")
    config = harness.load_json("configs", CONFIG + ".json")
    assert mla.serving_model(config, 4608, False).max_seq_len == 4608
    with pytest.raises(harness.BenchError, match="no training path"):
        mla.training(config, {}, False)
    assert mla.vocab_size(config, False) == 20480
    assert mla.vocab_size(config, True) == 512
    assert mla.kernels("prefill_1024") == mla.kernels("prefill_4096") \
        == ["flash_fwd", "rms_norm"]
    assert mla.kernels("decode") == mla.kernels("decode_lp") \
        == ["decode_attention", "rms_norm"]
    with pytest.raises(harness.BenchError):
        mla.kernels("train_step")
    assert mla.routed(config)
    assert sorted(mla.controls(config)) == CONTROLS
    yarn = config["rope_scaling"]
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("norm_topk_prob", False), ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("n_routed_experts", 384),
                       ("rope_scaling", {**yarn, "type": "linear"})):
        with pytest.raises(harness.BenchError):
            mla.serving_model({**config, key: value}, 4608, False)


def test_program_module_stays_off_jax_and_fails_cleanly_without_the_family():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark import harness\n"
        "c = harness.load_json('configs', %r + '.json')\n"
        "p = harness.program_for(c['program'])\n"
        "assert p.vocab_size(c, False) == 20480\n"
        "p.kernels('prefill_1024'), p.kernels('decode'), p.routed(c)\n"
        "p.controls(c)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        # the parent: no ray_tpu.models.mla to import
        "sys.modules['ray_tpu.models.mla'] = None\n"
        "try:\n"
        "    p.serving_model(c, 4608, False)\n"
        "except harness.BenchError as exc:\n"
        "    assert 'no latent-attention family' in str(exc)\n"
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
    assert mix["prompt_bytes"] == {"dist": "lognormal", "median": 2048,
                                   "sigma": 0.6, "min": 512, "max": 4000}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.7, "min": 16, "max": 512}
    assert mix["order"] == {"strata": 16}
    assert (mix["temperature"], mix["shared_prefix"], mix["drain"],
            mix["drain_timeout_s"]) == (0.0, "none", True, 90.0)
    assert (mix["trace_after_s"], mix["trace_seconds"]) == (8.0, 3.0)
    # the issue's rule: the lower of two limbs, rounded down to 0.1,
    # and the sweep's rows beside it
    assert mix["rate_rps"] == round(mix["rate_rps"], 1)
    for word in ("sweep", "(a)", "(b)", "0.8 x the knee"):
        assert word in mix["rate_from"], word
    # the longest request fits the cache, with its BOS
    assert 4000 + 1 + 512 <= cell()["config_file"]["serving"]["max_seq"]
    # the buckets the prompts land in: 1024, 2048 and 4096
    from benchmark import traffic
    schedule = traffic.open_loop_schedule(mix, SEED, 40.0)
    buckets = serve_open_loop._buckets(
        [len(r["prompt"]) + 1 for r in schedule], 4608)
    assert [1 << (n - 1).bit_length() for n in buckets] \
        == [1024, 2048, 4096]


def test_cell_reports_what_the_lfm2_cell_does_and_its_own_two():
    mine = cell()
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(mine["why"]) <= 200
    assert [m["name"] for m in mine["end_to_end"]] \
        == [m["name"] for m in harness.load_cell(LIKE)["end_to_end"]] \
        == ["ttft_p50_ms", "itl_p90_ms", "setup_s"]
    names = [m["name"] for m in mine["per_layer"]]
    assert names == [m["name"] for m in harness.load_cell(LIKE)["per_layer"]
                     if m["name"] not in NOT_LIKE] + list(OWN)
    assert "expert_held_share" not in names
    for wanted in ("prefill_pad_share", "decode_kv_read_share",
                   "expert_hit_share", "replica_stall_share.lat",
                   "stepper_held_share.lat", "device_idle_share.lat"):
        assert wanted in names
    for m in mine["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["layer"] == "Engine"
        assert (m["source"], m["moves"], m["unit"], m["better"]) \
            == ("program_counter", OWN[m["name"]], "%", "higher")
    for name in names:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.reader_for(spec["reader"])
    # no other cell reports the new two, and each is the last of its list
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(OWN)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    # the driver refuses a `why` or a `source` over 200 characters, on a
    # configuration as on a cell (PR 69's first hand-in: 204)
    for line in (bench["configs"][-1]["why"], bench["configs"][-1]["source"],
                 bench["workloads"][-1]["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not set(OWN) & {
                m["name"] for m in harness.load_cell(w["name"])["per_layer"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"][-1] == CELL


# what an engine of the parent exports and an untraced run observes: no
# such series, an empty trace
_NOTHING = [{"series_before": {}, "series_after": {}, "trace": {"ops": {}}},
            {"series_after": None, "trace": None},
            {"series_before": {"ray_tpu_engine_step_seconds_count"
                               "{phase=\"decode\"}": 5.0,
                               "ray_tpu_engine_step_seconds_sum"
                               "{phase=\"prefill\"}": 1.0},
             "series_after": {"ray_tpu_engine_step_seconds_count"
                              "{phase=\"decode\"}": 9.0,
                              "ray_tpu_engine_step_seconds_sum"
                              "{phase=\"prefill\"}": 3.0},
             "trace": {"ops": {}}}]


def test_latent_step_floor_share_from_hand_made_series():
    spec = harness.load_json("layer_metrics",
                             "latent_step_floor_share.json")
    assert spec["reader"] == "latent_step_floor"
    config = harness.load_json("configs", CONFIG + ".json")
    steps = 1000.0
    # a step: 2 of a layer's 12 held experts hit, 9 slots at 2300 rows
    # (five blocks of 512 each) and 23 parked on a block
    hit = steps * 6 * 2
    rows = steps * (9 * 2560 + 23 * 512)
    series = {
        'ray_tpu_engine_expert_slots_total{state="hit"}': hit,
        'ray_tpu_engine_expert_slots_total{state="idle"}':
            steps * 6 * 12 - hit,
        'ray_tpu_engine_decode_kv_rows_total{kind="read"}': rows,
        'ray_tpu_engine_decode_kv_rows_total{kind="skipped"}':
            steps * 32 * 4608 - rows,
        'ray_tpu_engine_step_seconds_count{phase="decode"}': 900.0,
        'ray_tpu_engine_step_seconds_sum{phase="decode"}': 900.0 * 0.015}
    observed = {"series_before": {k: 0.0 for k in series},
                "series_after": series, "trace": None,
                "cell": {"config_file": config}}
    floor = ops_kimi.decode_floor_bytes(config, 6 * 2, 9 * 2560 + 23 * 512)
    # attention, shared experts, routers, layer 0 and head: 3.06 GB; 12
    # experts of 88.1 MB: 1.06 GB; 34816 rows of 7 layers at 1152 B:
    # 0.28 GB
    assert 3.05e9 < floor["always"] < 3.08e9
    assert floor["experts"] == 12 * 3 * 7168 * 2048 * 2
    assert floor["latent"] == 34816 * 7 * 1152
    want = 100.0 * sum(floor.values()) / 819e9 / 0.015
    got = latent_step_floor.read(observed, **spec["args"])
    assert got == pytest.approx(want) and 33 < got < 38
    # every held expert hit and every row read is the most it can say:
    # what a program that reads everything once is held to
    full = ops_kimi.decode_floor_bytes(config, 6 * 12, 32 * 4608)
    assert sum(full.values()) < 2 * ops_kimi.model_params(config) + 1.2e9
    for nothing in _NOTHING:
        assert latent_step_floor.read(
            {**nothing, "cell": {"config_file": config}},
            **spec["args"]) is None
    # and a cell of another family, whose file has no such keys
    other = harness.load_cell("granite4h-agent-steady")
    for nothing in _NOTHING:
        assert latent_step_floor.read({**nothing, "cell": other},
                                      **spec["args"]) is None


def test_prefill_mfu_from_hand_made_series():
    spec = harness.load_json("layer_metrics", "prefill_mfu.json")
    assert spec["reader"] == "prefill_mfu"
    config = harness.load_json("configs", CONFIG + ".json")
    # 100 prompts of 242 000 real tokens in 16 s of admitting steps, 8
    # of them admitted under the prefill of the one before
    series = {
        'ray_tpu_engine_prefill_tokens_total{kind="real"}': 242000.0,
        'ray_tpu_engine_prefill_tokens_total{kind="pad"}': 81000.0,
        'ray_tpu_engine_admit_launch_seconds_count{overlapped="0"}': 92.0,
        'ray_tpu_engine_admit_launch_seconds_count{overlapped="1"}': 8.0,
        'ray_tpu_engine_step_seconds_count{phase="prefill"}': 59.0,
        'ray_tpu_engine_step_seconds_sum{phase="prefill"}': 16.0}
    observed = {"series_before": {k: 0.0 for k in series},
                "series_after": series, "trace": None,
                "cell": {"config_file": config}}
    ops = (2 * 242000 * ops_kimi.always_params(config)
           + 2 * 100 * ops_kimi.params(config)["head"]
           + 7 * 64 * 320 * 242000.0 ** 2 / 100)
    assert ops == pytest.approx(
        ops_kimi.prefill_floor_ops(config, 242000, 100))
    got = prefill_mfu.read(observed, **spec["args"])
    assert got == pytest.approx(100.0 * ops / (16.0 * 197e12))
    assert 20 < got < 30
    # series that were there before the window count for nothing
    later = {k: 2 * v for k, v in series.items()}
    assert prefill_mfu.read(
        {**observed, "series_before": series, "series_after": later},
        **spec["args"]) == pytest.approx(
            100.0 * ops_kimi.prefill_floor_ops(config, 242000, 100)
            / (16.0 * 197e12))
    for nothing in _NOTHING:
        assert prefill_mfu.read(
            {**nothing, "cell": {"config_file": config}},
            **spec["args"]) is None
    other = harness.load_cell("granite4h-agent-steady")
    for nothing in _NOTHING:
        assert prefill_mfu.read({**nothing, "cell": other},
                                **spec["args"]) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_the_cpu(trace, tmp_path):
    """benchmark/run.py's path: HTTP proxy -> replica -> engine of the
    latent-attention family."""
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
                           if m["name"] in (*OWN, "expert_hit_share",
                                            "decode_kv_read_share")]},
            {**dumped, "cell": cell(True)})
        assert 0 < shares["expert_hit_share"]["value"] <= 100
        # off the TPU the plain form reads every row
        assert shares["decode_kv_read_share"]["value"] == 100
        # the two readers find their series (the numbers are a CPU's
        # and the tiny model's: read, not judged)
        assert shares["latent_step_floor_share"]["value"] > 0
        assert shares["prefill_mfu"]["value"] > 0
        stats = dumped["stats_after"]
        assert list(stats["cache_bytes"]) == ["latent"]
        assert stats["router_picks"]["moved"] > 0
        assert stats["expert_picks"]["absent"] > stats["expert_picks"]["held"]


def test_controls_through_the_harness_on_the_cpu():
    """``run.py --control``: the reference check alone, the program made
    wrong by name. In float32 the sound program agrees to 1e-4 and each
    control reads hundreds of times that."""
    names = ["none"] + CONTROLS
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", str(SEED), "--control",
         ",".join(names)],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith('{"control"')]
    assert [l["control"] for l in lines] == names
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last == lines[-1]
    assert not {"correct", "attempted", "failed", "metrics"} & set(last)
    sound, *wrong = (l["report"] for l in lines)
    assert sound["ok"] and sound["worst"] < 1e-4 and sound["tokens"] == 384
    for report in wrong:
        assert report["mean"] > 100 * sound["mean"]
        assert not report["ok"]
