"""The Jamba configuration, its cell and what the cell adds to the
yardstick: the program module, the operation counts, the three readers.
On the CPU; nothing here gives a device number."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, ops_jamba  # noqa: E402
from benchmark.readers import (counter_share, scan_roofline,  # noqa: E402
                               scan_time_share)
from benchmark.runners import serve_open_loop  # noqa: E402

CELL = "jamba2-3b-chat-steady"
SEED = 3000000011


def _args(**kw):
    base = dict(seed=SEED, seconds=40.0, rehearse=False, sweep=None,
                trace=0, dump=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_configuration_file_holds_the_catalog_rows_numbers():
    config = harness.load_json("configs", "jamba2-3b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "AI21-Jamba2-3B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    assert config["reduced"] == {} and config["num_hidden_layers"] == 28
    assert (config["program"], config["reference"]) == ("jamba", "jamba")
    # sizes, and what PR 59 read of the one control: no limit of its own
    check = config["check"]
    assert (check["prompt_lens"], check["new_tokens"]) \
        == ([100, 200, 300], 64)
    assert "limits" not in check
    assert set(check["calibration"]["controls"]) == {"state_bf16"}


def test_build_gives_the_published_model_uncut():
    from ray_tpu.models.jamba import JambaConfig

    built = serve_open_loop.build(harness.load_cell(CELL), _args())
    model = built.engine.model
    assert model == JambaConfig(max_seq_len=1024)
    assert (model.n_mamba_layers, model.n_attn_layers) == (26, 2)
    assert (built.engine.max_batch, built.engine.max_seq) == (32, 1024)
    assert (built.check_lens, built.check_tokens) == ([100, 200, 300], 64)
    assert not built.routed and built.drain


def test_build_rehearsing_keeps_both_kinds_of_layer():
    import jax.numpy as jnp

    built = serve_open_loop.build(harness.load_cell(CELL, True),
                                  _args(rehearse=True))
    model = built.engine.model
    assert model.layer_kinds == ("mamba", "mamba", "attn", "mamba")
    assert model.dtype == jnp.float32 and model.attention == "reference"
    assert (built.engine.max_batch, built.engine.max_seq) == (4, 128)


def test_family_kernels_routed_and_no_training():
    jamba = harness.program_for("jamba")
    assert jamba.kernels("prefill_256") == [
        "selective_scan_256", "flash_fwd", "rms_norm"]
    assert jamba.kernels("decode") == jamba.kernels("decode_lp") \
        == ["rms_norm"]
    config = harness.load_json("configs", "jamba2-3b.json")
    assert not jamba.routed(config)
    with pytest.raises(harness.BenchError, match="no training path"):
        jamba.training(config, {}, False)
    with pytest.raises(harness.BenchError):
        jamba.serving_model({**config, "num_experts": 16}, 1024, False)
    found = ["selective_scan_256(i32, bf16)", "flash_fwd(bf16)"]
    assert harness.missing_kernels(found, jamba.kernels("prefill_256")) \
        == ["rms_norm"]
    assert harness.missing_kernels(found, jamba.kernels("prefill_512")) \
        == ["selective_scan_512", "rms_norm"]


def test_the_drivers_questions_keep_the_family_off_jax():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark import harness\n"
        "c = harness.load_cell(%r)['config_file']\n"
        "p = harness.program_for(c['program'])\n"
        "assert p.vocab_size(c, False) == 65536\n"
        "assert p.vocab_size(c, True) == 512\n"
        "p.kernels('prefill_128'), p.kernels('decode'), p.routed(c)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        % (ROOT, CELL))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_ops_by_hand():
    config = harness.load_json("configs", "jamba2-3b.json")
    assert ops_jamba.layer_kinds(config) == {"attn": 2, "mamba": 26}
    call = ops_jamba.scan_call(config, 512)
    assert call["flops"] == 9 * 512 * 5120 * 16
    assert call["bytes"] == (512 * 5120 * (2 + 2 + 2) + 2 * 512 * 16 * 4
                             + (3 * 16 * 5120 + 5120) * 4)
    # 31-33 KB a token and layer, as ISSUE 34 and PERF.md say
    assert 30_800 < call["bytes"] / 512 < 33_000
    assert ops_jamba.memory_seconds(call, {"hbm_bytes_per_s": 819e9}) \
        == call["bytes"] / 819e9
    assert 3.02e9 < ops_jamba.model_params(config) < 3.04e9
    assert ops_jamba.state_bytes_per_slot(config) == 26 * (327_680 + 30_720)
    assert ops_jamba.kv_bytes_per_token(config) == 1024
    step = ops_jamba.decode_step_bytes(config, 32, 1024)
    assert 6.05e9 < step["weights"] < 6.08e9
    assert step["state"] == 2 * 32 * 26 * 358_400
    assert step["kv"] == 32 * 1024 * 1024


def _observed(ops, busy_s=2.0, cell=CELL):
    return {"trace": {"ops": ops, "busy_s": busy_s, "window_s": 3.0},
            "cell": harness.load_cell(cell)}


def test_scan_readers_on_a_hand_made_trace():
    config = harness.load_json("configs", "jamba2-3b.json")
    least = (26 * ops_jamba.scan_call(config, 256)["bytes"]
             + 52 * ops_jamba.scan_call(config, 512)["bytes"]) / 819e9
    observed = _observed({
        "selective_scan_256.3 (f32[256,5120], f32[16,5120])":
            {"seconds": 0.002, "count": 26.0},
        "selective_scan_512.7 (f32[512,5120], f32[16,5120])":
            {"seconds": 0.006, "count": 52.0},
        "fusion.12 bf16[32,8192]": {"seconds": 1.0, "count": 100.0}})
    kind = {"device_kind": "TPU v5 lite"}
    assert scan_roofline.read(observed, **kind) == pytest.approx(
        100.0 * least / 0.008)
    assert scan_roofline.read(observed, **kind) < 100.0
    assert scan_time_share.read(observed, **kind) == pytest.approx(0.4)
    # the floor is owed for the prompts' own positions: with 7 of 10
    # prefill positions real, the calls are those of 0.7 of a bucket
    real = 'ray_tpu_engine_prefill_tokens_total{kind="real"}'
    pad = 'ray_tpu_engine_prefill_tokens_total{kind="pad"}'
    counted = dict(observed, series_before={real: 100.0, pad: 50.0},
                   series_after={real: 800.0, pad: 350.0})
    owed = (26 * ops_jamba.scan_call(config, 0.7 * 256)["bytes"]
            + 52 * ops_jamba.scan_call(config, 0.7 * 512)["bytes"]) / 819e9
    assert scan_roofline.read(counted, **kind) == pytest.approx(
        100.0 * owed / 0.008)
    assert owed < least
    # the time share does not depend on the floor
    assert scan_time_share.read(counted, **kind) == pytest.approx(0.4)
    # a program without the kernel (the parent commit, a rehearsal on
    # the CPU), an untraced run: nothing to read, and no error
    other = _observed({"fusion.12": {"seconds": 1.0, "count": 1.0}})
    assert scan_roofline.read(other, **kind) is None
    assert scan_time_share.read(other, **kind) is None
    assert scan_roofline.read({"trace": None}, **kind) is None
    with pytest.raises(harness.BenchError):
        scan_roofline.read(observed, device_kind="TPU v9")


def test_pad_share_from_hand_made_series():
    pad = 'ray_tpu_engine_prefill_tokens_total{kind="pad"}'
    real = 'ray_tpu_engine_prefill_tokens_total{kind="real"}'
    spec = harness.load_json("layer_metrics", "prefill_pad_share.json")
    assert spec["reader"] == "counter_share"
    observed = {"series_before": {pad: 100.0, real: 300.0},
                "series_after": {pad: 400.0, real: 1000.0}}
    assert counter_share.read(observed, **spec["args"]) == pytest.approx(30.0)
    # the parent has no such series; an untraced run has no series
    assert counter_share.read({"series_before": {}, "series_after": {}},
                              **spec["args"]) is None
    assert counter_share.read({"series_after": None},
                              **spec["args"]) is None


def test_cell_reports_the_serving_metrics_and_its_own():
    cell = harness.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == [
        "ttft_p50_ms", "itl_p90_ms", "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    # PR 34's fourteen; later PRs appended theirs
    assert names[11:14] == ["scan_roofline", "scan_time_share",
                            "prefill_pad_share"]
    assert names[14:] == ["stream_held_share", "admit_launch_ms",
                          "stepper_blocked_share.lat",
                          "stepper_emit_share.lat",
                          "stepper_stall_share.lat", "decode_kv_read_share"]
    for name in names:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.reader_for(spec["reader"])
    mix = cell["traffic_file"]
    steady = harness.load_json("traffic", "chat-short-steady.json")
    for key in ("gap", "prompt_bytes", "output_tokens", "temperature",
                "drain", "trace_after_s", "trace_seconds"):
        assert mix[key] == steady[key], key
    assert mix["order"] == {"strata": 16}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_on_the_cpu(trace):
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", str(SEED),
         "--seconds", "4", "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and not line["correct"]
    check = next(l for l in done.stdout.splitlines()
                 if "reference check:" in l)
    report = json.loads(check.split("reference check:", 1)[1])
    assert report["ok"] and report["tokens"] == 192
    assert report["worst"] < 1e-4
