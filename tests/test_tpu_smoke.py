"""chip_smoke.py on the CPU mesh: its phase functions called directly at
LlamaConfig.tiny against faked TPU resources (tpu-profile workers
inherit JAX_PLATFORMS=cpu), and its refusal to run without a chip."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
import ray_tpu
from ray_tpu.core.node import compile_cache_dir
from ray_tpu.llm.engine import EngineConfig
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def fake_tpu_host():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4, num_tpus=8)
    yield rt
    ray_tpu.shutdown()


def _run_main(env_update, unset=()):
    env = {k: v for k, v in os.environ.items() if k not in unset}
    env.update(env_update)
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120)


def test_main_refuses_a_cpu_backend_before_phase_0():
    proc = _run_main({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "JAX_PLATFORMS" in proc.stderr
    assert proc.stdout == ""


def test_main_refuses_a_machine_without_chips():
    proc = _run_main({}, unset=("JAX_PLATFORMS", "RTPU_TPU_NUM_CHIPS"))
    assert proc.returncode != 0
    assert "/dev/accel*" in proc.stderr and "/dev/vfio" in proc.stderr
    assert "{" not in proc.stdout


def test_result_line_has_exactly_the_keys_the_caller_parses():
    from ray_tpu.accelerators import jax_backend
    where = jax_backend.device_report()
    assert json.loads(chip_smoke.summary_line(where)) == {
        "ok": True, "device": {"platform": "cpu",
                               "kind": where["device_kind"],
                               "count": len(where["device_ids"])}}
    assert "\n" not in chip_smoke.summary_line(where)


def test_kernel_checks_compare_against_references(monkeypatch):
    from ray_tpu.ops import attention, quant_matmul, rmsnorm
    for mod in (attention, quant_matmul, rmsnorm):
        monkeypatch.setattr(mod, "_INTERPRET", True)
    shapes = ([(1, 256, 2, 128, True)], [(2, 64, 256)], (4, 1024, 512),
              (2, 3, 256, 2, 128, 2))
    report = chip_smoke.check_kernels(*shapes, expect_kernels=False)
    checks = report["checks"]
    for name in ("flash[1,256,2,128]", "flash[1,256,2,128].dq",
                 "flash[1,256,2,128].dk", "flash[1,256,2,128].dv",
                 "rms_norm[2, 64, 256]", "int8_matmul[4,1024,512]",
                 "decode_attention[2, 3, 256, 2, 128, 2]"):
        assert 0 <= checks[name] <= chip_smoke.KERNEL_TOL, (name, checks)
    # interpreter mode lowers to plain HLO: a caller that expects the
    # kernels in the program is told they are not there
    with pytest.raises(chip_smoke.PhaseError, match="holds no"):
        chip_smoke.check_kernels(*shapes, expect_kernels=True)
    monkeypatch.setattr(chip_smoke, "KERNEL_TOL", 1e-9)
    with pytest.raises(chip_smoke.PhaseError, match="max error"):
        chip_smoke.check_kernels(*shapes, expect_kernels=False)


def test_require_kernels():
    found = ["flash_fwd(tensor<1x2xbf16>)", "rms_norm(tensor<8x128xbf16>)"]
    chip_smoke._require_kernels("p", found, ["flash_fwd", "rms_norm"], [])
    with pytest.raises(chip_smoke.PhaseError, match="flash_dq"):
        chip_smoke._require_kernels("p", found, ["flash_dq"], [])
    with pytest.raises(chip_smoke.PhaseError, match="fell back"):
        chip_smoke._require_kernels("p", found, [], ["q[1, 8, 2, 16]"])


def test_compile_cache_dir_resolution(fake_tpu_host):
    assert compile_cache_dir({}, "/checkout") == "/checkout/.jax_cache"
    assert compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/x"}, "/checkout") == "/x"

    def cache_env():
        return os.environ.get("JAX_COMPILATION_CACHE_DIR")

    # a worker that owns chips is told; one held to the CPU is not
    assert ray_tpu.get(ray_tpu.remote(num_tpus=1)(cache_env).remote()) == \
        os.environ.get("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(REPO_ROOT, ".jax_cache"))
    assert ray_tpu.get(ray_tpu.remote(cache_env).remote()) == \
        os.environ.get("JAX_COMPILATION_CACHE_DIR")


@pytest.mark.watchdog(300)
def test_train_phase_on_an_fsdp8_mesh(fake_tpu_host, tmp_path):
    # one worker owning all eight (virtual) devices: the kernels'
    # shard_map wrappers, jit-init into the rules' shardings and the
    # Data -> get_dataset_shard -> iter_device_batches feed
    report = chip_smoke.phase_train(
        LlamaConfig.tiny(attention="flash", remat=True, ce_chunk_tokens=64),
        batch=8, seq=32, steps=4, chips=8, kernels=(),
        storage_path=str(tmp_path))
    assert len(report["losses"]) == 4
    assert report["losses"][-1] < report["losses"][0]
    assert report["mesh"] == {"fsdp": 8}
    assert report["first_report_device"]["platform"] == "cpu"
    assert len(report["device"]["device_ids"]) == 8
    assert report["kernels"] == [] and report["flash_fallbacks"] == []


@pytest.mark.watchdog(300)
def test_serve_phase_with_two_one_chip_replicas(fake_tpu_host):
    config = LLMConfig(engine=EngineConfig(max_batch=4, max_seq=128),
                       use_tpu=True, num_replicas=2)
    assert build_llm_deployment(config).deployment.config \
        .ray_actor_options == {"num_tpus": 1}
    report = chip_smoke.phase_serve(
        config, prompt_lens=[20, 40, 60, 70], max_tokens=8,
        prefill_kernels=(), decode_kernels=())
    assert report["answers"] == [8, 8, 8, 8]
    # each replica sits in a "tpu:1" worker holding a chip of its own
    # (a worker held to the CPU has TPU_VISIBLE_CHIPS="")
    chips = sorted(r["device"]["visible_chips"] for r in report["replicas"])
    assert len(chips) == 2 and len(set(chips)) == 2 and "" not in chips
    assert sum(r["total_generated"] for r in report["replicas"]) == 32
    json.dumps(report)  # what /v1/stats returned is plain JSON
