"""What a configuration file's ``program`` module gives the harness:
the Llama-shaped family builds what the runners built before the
module existed (values written out from that code), and a family that
is no LlamaConfig gets a cell built, gated and sized from new files
alone.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import dataclasses
import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, reference_check, traffic  # noqa: E402
from benchmark.runners import serve_open_loop  # noqa: E402

SEED = 3000000011


def _args(**kw):
    base = dict(seed=SEED, seconds=40.0, rehearse=False, sweep=None,
                trace=0, dump=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _fields(obj):
    return {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}


# what harness.model_kwargs returned for the three Mistral files before
# it moved to benchmark/programs/llama.py, and for their rehearsal
_PUBLISHED = dict(vocab_size=32768, dim=4096, n_heads=32, n_kv_heads=8,
                  hidden_dim=14336, rope_theta=1000000.0, norm_eps=1e-05,
                  moe_experts=0, moe_top_k=2, attention="flash")
_REHEARSED = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, hidden_dim=128, rope_theta=1000000.0,
                  norm_eps=1e-05, moe_experts=0, moe_top_k=2,
                  attention="reference")


# -- the Llama-shaped family builds what the runners built ---------------

@pytest.mark.parametrize("workload", ["mistral7b-chat-steady",
                                      "mistral7b-chat-saturated"])
def test_build_gives_the_engine_config_the_runner_built(workload):
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMConfig

    cell = harness.load_cell(workload)
    built = serve_open_loop.build(cell, _args())
    model = LlamaConfig(max_seq_len=1024, n_layers=16, **_PUBLISHED)
    engine = EngineConfig(model=model, max_batch=32, max_seq=1024,
                          seed=SEED % (2**31 - 1))
    assert _fields(built.engine.model) == _fields(model)
    assert _fields(built.engine) == _fields(engine)
    assert _fields(built.llm) == _fields(LLMConfig(
        model_id="mistral-7b-v0.3-L16", engine=engine, use_tpu=True,
        tpu_chips_per_replica=1, max_ongoing_requests=256))
    assert built.engine.seed == 852516364 and not built.routed
    assert (built.check_lens, built.check_tokens) == ([100, 200, 300], 6)
    mix = cell["traffic_file"]
    assert built.rates == [mix["rate_rps"]]
    assert built.schedules == [traffic.open_loop_schedule(mix, SEED, 40.0)]
    assert built.drain == bool(mix.get("drain", True))
    assert built.no_eos == {"257": -100}


def test_build_rehearsing_keeps_the_kind_at_tiny_sizes():
    import jax.numpy as jnp

    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models.llama import LlamaConfig

    cell = harness.load_cell("mistral7b-chat-steady", rehearse=True)
    built = serve_open_loop.build(cell, _args(rehearse=True, seconds=6.0))
    model = LlamaConfig(max_seq_len=128, dtype=jnp.float32, remat=False,
                        **_REHEARSED)
    assert _fields(built.engine) == _fields(EngineConfig(
        model=model, max_batch=4, max_seq=128, seed=SEED % (2**31 - 1)))
    assert not built.llm.use_tpu
    assert built.check_lens == [64, 64, 64]     # max_seq // 2
    assert built.schedules == [traffic.open_loop_schedule(
        cell["traffic_file"], SEED, 6.0, {"prompt": 0.1, "output": 0.1})]


@pytest.mark.parametrize("name,layers,rehearse", [
    ("mistral-7b-v0.3-L4", 4, False),
    ("mistral-7b-v0.3-L16-fsdp4", 16, False),
    ("mistral-7b-v0.3-L4", 4, True),
    ("mistral-7b-v0.3-L16-fsdp4", 16, True)])
def test_training_gives_the_model_the_runner_built(name, layers, rehearse):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, llama_sharding_rules

    config = harness.load_json("configs", name + ".json")
    if rehearse:
        config.update(config["rehearse"])
        want = LlamaConfig(max_seq_len=64, remat=False, ce_chunk_tokens=0,
                           dtype=jnp.float32, **_REHEARSED)
    else:
        want = LlamaConfig(max_seq_len=2048, remat=True,
                           ce_chunk_tokens=4096, n_layers=layers,
                           **_PUBLISHED)
    family = harness.program_for(config["program"]).training(
        config, config["training"], rehearse)
    assert set(family) == {"model", "init", "loss", "sharding_rules"}
    assert _fields(family["model"]) == _fields(want)
    assert harness.program_for(config["program"]).vocab_size(
        config, rehearse) == want.vocab_size
    assert family["sharding_rules"] == llama_sharding_rules("fsdp")


def test_llama_family_kernels_and_routed():
    llama = harness.program_for("llama")
    assert llama.kernels("prefill_128") == ["flash_fwd", "rms_norm"]
    assert llama.kernels("decode") == ["rms_norm"]
    assert llama.kernels("train_step") == ["flash_fwd", "flash_dq",
                                           "flash_dkv", "rms_norm"]
    dense = harness.load_json("configs", "mistral-7b-v0.3-L16.json")
    assert not llama.routed(dense)
    assert llama.routed({**dense, "num_local_experts": 8})
    assert llama.serving_model({**dense, "num_local_experts": 8,
                                "num_experts_per_tok": 2}, 1024,
                               False).moe_experts == 8
    with pytest.raises(harness.BenchError):
        llama.serving_model({**dense, "sliding_window": 4096}, 1024, False)


def test_what_the_train_driver_asks_of_a_family_keeps_it_off_jax():
    """The process that starts a train job never imported JAX before
    the program modules; doing so cost 7 s of setup_s on the chip's
    machine (PERF.md section 6, PR 29)."""
    import subprocess
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark import harness\n"
        "from benchmark.runners import train_job\n"
        "c = harness.load_cell('mistral7b-train-2k')['config_file']\n"
        "p = harness.program_for(c['program'])\n"
        "assert p.vocab_size(c, False) == 32768\n"
        "assert p.vocab_size(c, True) == 512\n"
        "p.kernels('train_step'), p.routed(c)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % ROOT)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_check_sizes_default_and_from_the_configuration_file():
    sizes = reference_check.check_sizes
    assert sizes({}, False) == ([100, 200, 300], 6)
    assert sizes({}, True) == ([100, 200, 300], 64)
    assert sizes({"check": {"new_tokens": 64}}, False) == \
        ([100, 200, 300], 64)
    assert sizes({"check": {"prompt_lens": [96, 480], "new_tokens": 48}},
                 True) == ([96, 480], 48)
    # a file's "check" holds sizes, limits and how the limits were found
    for name in os.listdir(os.path.join(harness.HERE, "configs")):
        check = harness.load_json("configs", name).get("check", {})
        assert set(check) <= {"prompt_lens", "new_tokens", "limits",
                              "calibration"}, name
        assert "calibration" in check or "limits" not in check, name


def test_check_limits_default_to_the_constants():
    rc = reference_check
    assert rc.check_limits({}, False) == {"worst": rc.LOGPROB_TOL,
                                          "mean": rc.LOGPROB_MEAN_TOL}
    assert rc.check_limits({"check": {"new_tokens": 8}}, True) == {
        "router_margin": rc.ROUTER_MARGIN,
        "decided_share_at_least": rc.ROUTED_DECIDED_SHARE_MIN,
        "decided_mean": rc.LOGPROB_MEAN_TOL,
        "decided_median": rc.ROUTED_MEDIAN_MAX,
        "decided_over_share": rc.ROUTED_OVER_SHARE_MAX,
        "over": rc.LOGPROB_TOL}
    # the Mistral files have none: judged as before there were any
    for workload in ("mistral7b-chat-steady", "mistral7b-chat-saturated"):
        built = serve_open_loop.build(harness.load_cell(workload), _args())
        assert built.check_limits == {"worst": 0.15, "mean": 0.04}
        report = rc.dense_report([0.01, 0.02], built.check_limits)
        assert report["limits"] == rc.dense_report([0.01, 0.02])["limits"] \
            == {"worst": 0.15, "mean": 0.04}


@pytest.mark.parametrize("routed,given", [
    (False, {"wurst": 0.1}), (False, {"router_margin": 0.01}),
    (False, {"median": 0.01}), (True, {"worst": 0.1}),
    (True, {"decided_share": 0.2})])
def test_check_limits_refuse_a_key_the_report_does_not_gate_on(routed,
                                                               given):
    with pytest.raises(harness.BenchError, match="check.limits"):
        reference_check.check_limits({"check": {"limits": given}}, routed)


def test_a_files_limits_reach_the_report():
    rc = reference_check
    dense = rc.check_limits({"check": {"limits": {"mean": 0.001}}}, False)
    assert dense == {"worst": rc.LOGPROB_TOL, "mean": 0.001}
    assert rc.dense_report([0.002] * 18)["ok"]
    tight = rc.dense_report([0.002] * 18, dense)
    assert not tight["ok"] and tight["limits"] == dense
    # a router of many small experts: its own margin, floor and level
    routed = rc.check_limits({"check": {"limits": {
        "router_margin": 0.01, "decided_share_at_least": 0.1,
        "over": 0.02}}}, True)
    diffs = [0.001] * 150 + [0.03] * 42
    margins = [0.02] * 40 + [0.005] * 110 + [0.02] * 42
    theirs = rc.routed_report(diffs, margins, routed)
    assert theirs["router_margin"] == 0.01 and theirs["limits"] == routed
    assert theirs["decided_share"] == pytest.approx(82 / 192)
    # 42 of the 82 decided read over the file's level: a fault
    assert theirs["decided_over_share"] == pytest.approx(42 / 82)
    assert not theirs["ok"]
    # by the constants nothing is decided at all, whatever it reads
    ours = rc.routed_report(diffs, margins)
    assert ours["decided_share"] == 0.0 and not ours["ok"]
    # every cell's file builds, and its limits are what build hands on
    for workload, kind in (("jamba2-3b-chat-steady", rc.DENSE_LIMITS),
                           ("granite4h-agent-steady", rc.ROUTED_LIMITS)):
        cell = harness.load_cell(workload)
        built = serve_open_loop.build(cell, _args())
        given = cell["config_file"]["check"].get("limits", {})
        assert built.check_limits == {**kind, **given}
        # a router's margin and floor are its own; what a token may
        # read is never looser than the constant
        for key in set(given) - {"router_margin", "decided_share_at_least"}:
            assert given[key] <= kind[key], key


def test_control_readings_hold_the_dense_and_the_routed_statistics():
    rc = reference_check
    got = rc.control_readings([0.01, 0.03, 0.02, 0.5],
                              [0.003, 0.008, 0.03, 0.05])
    assert got["dense"] == {"worst": 0.5, "mean": pytest.approx(0.14),
                            "median": pytest.approx(0.025)}
    assert [r["router_margin"] for r in got["routed"]] \
        == list(rc.CONTROL_MARGINS)
    assert [r["decided_share"] for r in got["routed"]] \
        == [0.75, 0.5, 0.5, 0.25]
    assert got["routed"][-1]["decided_over_share"] == 1.0


def test_result_line_ends_with_what_was_compared():
    compared = {"worst": [0.2, 0.15], "check": [0, 1],
                "lag_worst_s": [0.001, None]}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    plain = json.loads(harness.result_line(True, 3, 0, {}, device))
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device"]
    traced = json.loads(harness.result_line(
        False, 3, 0, {}, device, {"device_ops": [["a", 1.0]]}, compared))
    assert list(traced)[-2:] == ["breakdown", "compared"]
    assert traced["compared"] == compared


def test_programs_give_their_controls_by_name():
    import pickle

    granite = harness.program_for("granite")
    config = harness.load_json("configs", "granite-4.0-h-small-L10-ep2.json")
    assert sorted(granite.controls(config)) == ["expert_zeroed",
                                                "state_bf16"]
    jamba = harness.program_for("jamba")
    assert sorted(jamba.controls(
        harness.load_json("configs", "jamba2-3b.json"))) == ["state_bf16"]
    assert not hasattr(harness.program_for("llama"), "controls")
    # each reaches the worker by name
    for apply in granite.controls(config).values():
        assert pickle.loads(pickle.dumps(apply)) is apply
    # the decode program is held to PR 58's kernel
    assert granite.kernels("decode") == ["decode_attention", "rms_norm",
                                         "ssd_update"]
    assert granite.kernels("prefill_256") == ["flash_fwd", "rms_norm"]


def test_series_of_every_ray_tpu_family_reach_the_readers():
    from ray_tpu.util import metrics

    metrics.Counter("ray_tpu_statecache_test_evictions_total").inc(
        3.0, {"kind": "scan"})
    metrics.Counter("ray_tpu_engine_test_tokens_total").inc(5.0)
    metrics.Counter("other_test_total").inc(1.0)
    got = serve_open_loop.program_series()
    assert got['ray_tpu_statecache_test_evictions_total{kind="scan"}'] == 3.0
    assert got["ray_tpu_engine_test_tokens_total"] == 5.0
    assert not any(k.startswith("other_test") for k in got)
    from benchmark.readers import series
    assert series.delta({"series_before": {}, "series_after": got},
                        "ray_tpu_engine_test_tokens_total") == 5.0


# -- a family that is no LlamaConfig, from new files alone --------------

@dataclasses.dataclass(frozen=True)
class ScanModel:
    """A state-space trunk as a stand-in would describe it: no field of
    LlamaConfig's names but the vocabulary."""
    vocab_size: int
    d_model: int
    n_blocks: int
    d_state: int
    conv_width: int
    max_positions: int


def _scan_program():
    """benchmark/programs/scan.py, as a later PR would add it."""
    module = types.ModuleType("benchmark.programs.scan")

    def serving_model(config, max_seq, rehearse):
        return ScanModel(config["vocab_size"], config["hidden_size"],
                         config["num_hidden_layers"],
                         config["mamba_d_state"], config["mamba_d_conv"],
                         max_seq)

    def training(config, sizes, rehearse):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.sharding import ShardingRules

        model = serving_model(config, sizes["seq"], rehearse)

        def init(key):
            return {"embedding": jax.random.normal(
                key, (model.vocab_size, model.d_model), jnp.float32)}

        def loss(params, tokens, targets, mesh):
            logits = params["embedding"][tokens] @ params["embedding"].T
            picked = jnp.take_along_axis(
                jax.nn.log_softmax(logits, -1), targets[..., None], -1)
            return -picked.mean()

        return {"model": model, "init": init, "loss": loss,
                "sharding_rules": ShardingRules(rules=[(r".*", P())])}

    def kernels(program_name):
        return (["selective_scan", "rms_norm"]
                if program_name.startswith("prefill") else ["rms_norm"])

    module.serving_model, module.training = serving_model, training
    module.kernels, module.routed = kernels, lambda config: False
    module.vocab_size = lambda config, rehearse: config["vocab_size"]
    return module


_SCAN_CONFIG = {
    "source": "https://example.org/scan/config.json",
    "model_type": "scan", "vocab_size": 4096, "hidden_size": 256,
    "num_hidden_layers": 6, "mamba_d_state": 16, "mamba_d_conv": 4,
    "rms_norm_eps": 1e-06,
    "reduced": {}, "assumed": {}, "departures": [], "deployment": "test",
    "chips": 1, "reference": "scan", "program": "scan",
    "check": {"prompt_lens": [96, 480], "new_tokens": 64},
    "serving": {"max_batch": 16, "max_seq": 512,
                "max_ongoing_requests": 64},
    "training": {"batch": 2, "seq": 16, "fsdp": 1, "learning_rate": 0.1},
}


@pytest.fixture
def scan_checkout(tmp_path, monkeypatch):
    """A checkout whose BENCHMARK.json has one cell of the stand-in
    family: a configuration file, a traffic file, a program module.
    Nothing of harness, reference_check or a runner is patched but the
    directory they read."""
    here = tmp_path / "benchmark"
    (here / "configs").mkdir(parents=True)
    (here / "traffic").mkdir()
    (here / "configs" / "scan-small.json").write_text(
        json.dumps(_SCAN_CONFIG))
    shutil.copy(os.path.join(harness.HERE, "traffic",
                             "chat-short-steady.json"),
                here / "traffic" / "chat-short-steady.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "scan-small", "source": _SCAN_CONFIG["source"],
                     "file": "benchmark/configs/scan-small.json",
                     "reduced": [], "why": "stand-in"}],
        "workloads": [{"name": "scan-chat", "config": "scan-small",
                       "traffic": "chat-short-steady", "chips": 1,
                       "why": "stand-in"}],
        "end_to_end": [], "per_layer": []}))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "HERE", str(here))
    monkeypatch.setitem(sys.modules, "benchmark.programs.scan",
                        _scan_program())
    return tmp_path


def test_a_cell_of_another_family_is_built_gated_and_sized(scan_checkout):
    from ray_tpu.models.llama import LlamaConfig

    cell = harness.load_cell("scan-chat")
    assert "rope_theta" not in cell["config_file"]
    built = serve_open_loop.build(cell, _args())
    model = built.engine.model
    assert model == ScanModel(4096, 256, 6, 16, 4, 512)
    assert not isinstance(model, LlamaConfig)
    assert not hasattr(model, "moe_experts")
    assert (built.engine.max_batch, built.engine.max_seq) == (16, 512)
    assert built.llm.engine is built.engine
    assert built.llm.model_id == "scan-small"
    # the check walks the recurrent state 64 steps, not 6, on the
    # prompts the file names (cut to half the cache, as every cell's)
    assert not built.routed
    assert (built.check_lens, built.check_tokens) == ([96, 256], 64)
    # the gate asks for the family's kernel, by the program's name
    stats = {"programs": {
        "prefill_128": ["selective_scan(bf16[1,128,512])",
                        "rms_norm(bf16[128,256])"],
        "decode": ["rms_norm(bf16[16,256])"]}}
    assert serve_open_loop._kernels_ok(stats, built.program, False) == \
        (True, [])
    stats["programs"]["prefill_256"] = ["flash_fwd(bf16[1,256,4,64])",
                                        "rms_norm(bf16[256,256])"]
    assert serve_open_loop._kernels_ok(stats, built.program, False) == \
        (False, ["prefill_256:selective_scan"])
    assert serve_open_loop._kernels_ok({"programs": {}}, built.program,
                                       False)[0] is False


def test_the_train_step_is_built_from_the_familys_functions(scan_checkout):
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.runners import train_job
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    config = harness.load_cell("scan-chat")["config_file"]
    family = harness.program_for(config["program"]).training(
        config, config["training"], False)
    mesh = make_mesh(MeshSpec(fsdp=1), devices=jax.devices()[:1])
    init, shardings, loss_fn, train_step = train_job._programs(
        family, mesh, optax.adamw(config["training"]["learning_rate"]))
    params, opt_state = jax.jit(init, out_shardings=shardings)(
        jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 17), 0, 4096)
    x, y = tokens[:, :-1], tokens[:, 1:]
    before = float(jax.jit(loss_fn)(params, x, y))
    assert before == pytest.approx(
        float(family["loss"](params, x, y, mesh)))
    params, opt_state, loss = jax.jit(train_step)(params, opt_state, x, y)
    assert float(loss) == pytest.approx(before)
    assert float(jax.jit(loss_fn)(params, x, y)) < before
    assert jnp.isfinite(loss)
    assert harness.missing_kernels(
        ["rms_norm(f32[2,16,256])"],
        harness.program_for(config["program"]).kernels("train_step")) == []


# -- what a run's observations come to, with no runtime ------------------

def _seen(built):
    """A window in which every request of the schedule was sent when
    due and streamed exactly the tokens it asked for."""
    records = [{"due": r["due"], "sent": r["due"], "finished": True,
                "error": None,
                "token_times": [r["due"] + 0.1 + 0.05 * i
                                for i in range(r["max_tokens"])]}
               for r in built.schedules[0]]
    device = {"compile_seconds": 61.5, "pid": 4242, "platform": "tpu",
              "device_kind": "TPU v5 lite", "device_ids": [0],
              "peak_bytes_in_use": 9_980_000_000}
    stats = {"device": device, "flash_fallbacks": [], "programs": {
        "prefill_256": ["flash_fwd(bf16[1,256,32,128])",
                        "rms_norm(bf16[256,4096])"],
        "decode": ["rms_norm(bf16[32,4096])"]}}
    return {"ready_s": 30.0, "setup_s": 34.0, "series_window_s": 41.0,
            "series_before": {}, "series_after": {}, "trace": None,
            "stats_before": json.loads(json.dumps(stats)),
            "stats_after": stats,
            "results": [{"records": records, "in_flight_at_end": 0,
                         "ended_s": 41.2}]}


def _short_of_one_token(seen, check):
    seen["results"][0]["records"][5]["token_times"].pop()


def _prefill_without_its_kernel(seen, check):
    seen["stats_after"]["programs"]["prefill_256"].pop(0)


def _reference_disagrees(seen, check):
    check["ok"] = False


def _compiled_inside_the_window(seen, check):
    seen["stats_after"]["device"]["compile_seconds"] += 2.5


def _replica_replaced(seen, check):
    seen["stats_after"]["device"]["pid"] = 4243


def _request_never_sent(seen, check):
    seen["results"][0]["records"][-1].update(sent=None, token_times=[],
                                             finished=False)


# the conjuncts of ``correct`` as the result's last line carries them,
# each beside what it has to be, where nothing fell
_ALL_HELD = {"check": [1, 1], "wrong_counts": [0, 0], "kernels_ok": [1, 1],
             "compiled_in_window_s": [0.0, 0.0], "unsent": [0, 0],
             "replica_replaced": [0, 0], "fallbacks": [0, 0],
             "platform_ok": [1, 1]}


@pytest.mark.parametrize("fault,fell", [
    (None, {}), (_short_of_one_token, {"wrong_counts": 1}),
    (_prefill_without_its_kernel, {"kernels_ok": 0}),
    (_reference_disagrees, {"check": 0}),
    (_compiled_inside_the_window, {"compiled_in_window_s": 2.5}),
    (_replica_replaced, {"replica_replaced": 1}),
    (_request_never_sent, {"unsent": 1})],
    ids=lambda f: f.__name__.strip("_") if callable(f) else "")
def test_result_is_correct_only_if_nothing_is_broken(fault, fell):
    cell = harness.load_cell("mistral7b-chat-steady")
    built = serve_open_loop.build(cell, _args())
    seen = _seen(built)
    check = {**reference_check.dense_report([0.01, 0.03],
                                            built.check_limits),
             "device": {"platform": "tpu", "device_ids": [0]}}
    if fault:
        fault(seen, check)
    out = serve_open_loop._result(cell, _args(), built, check, seen)
    assert out["correct"] is (fault is None)
    # the last line names every number compared beside its limit, last
    # of its keys, so a run that is not correct says by which it fell
    line = json.loads(harness.result_line(
        out["correct"], out["attempted"], out["failed"], {}, out["device"],
        out["breakdown"], out["compared"]))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    got = line["compared"]
    assert list(got)[:2] == ["worst", "mean"]
    assert got["worst"] == [0.03, 0.15]
    assert got["mean"] == [pytest.approx(0.02), 0.04]
    for name, (value, wanted) in _ALL_HELD.items():
        assert got[name] == [pytest.approx(fell.get(name, value)), wanted]
    assert got["ttft_max_ms"] == [pytest.approx(100.0), None]
    assert got["lag_worst_s"][1] is None
    stderr = harness.compared_lines(out["compared"])
    assert stderr.count("\n") == len(got)
    assert "benchmark: compared worst: 0.03 limit 0.15\n" in stderr
    assert out["measured"]["setup_s"] == 34.0
    assert out["measured"]["ttft_p50_ms"] == pytest.approx(100.0)
    assert out["measured"]["itl_p90_ms"] == pytest.approx(50.0)
    assert out["device"]["memory_peak_bytes"] == 9_980_000_000
    if fault is None:
        assert out["attempted"] == len(built.schedules[0]) == 160
        assert out["failed"] == 0
        harness.end_to_end_values(cell, out["measured"])
