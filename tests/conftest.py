"""Test fixtures.

Mirrors the reference's test infrastructure (reference:
python/ray/tests/conftest.py:590 ray_start_regular; :680 ray_start_cluster)
and forces JAX onto a virtual 8-device CPU mesh so every sharding test
runs without TPU hardware (SURVEY.md §7 "Testing without TPUs").
"""

import os
import threading

# Must be set before jax initializes a backend; worker subprocesses
# inherit the environment, the config update covers this process in
# case something imported jax already.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture
def ray_start_regular():
    """A fresh single-node runtime per test."""
    import ray_tpu
    if ray_tpu.is_initialized():
        # a failed test elsewhere must not cascade into fixture errors
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4, system_config={"task_max_retries": 0})
    yield rt
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    """One runtime shared by a whole test module (faster)."""
    import ray_tpu
    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    try:
        ray_tpu.shutdown()
    finally:
        # a shutdown that raises mid-teardown (hung serve controller,
        # dead node) must not leave the global runtime set — the next
        # module's fixtures would all error with "already initialized"
        from ray_tpu.core import runtime as runtime_mod
        if runtime_mod.get_runtime_or_none() is not None:
            runtime_mod.set_runtime(None)


@pytest.fixture
def ray_start_cluster():
    """A multi-node simulated cluster; tests add nodes declaratively."""
    from ray_tpu.core.cluster_utils import Cluster
    cluster = Cluster(head_node_args={"resources": {"CPU": 2}})
    yield cluster
    cluster.shutdown()


@pytest.fixture(autouse=True)
def _per_test_watchdog(request):
    """Per-test timeout (pytest-timeout isn't in the image): SIGALRM in
    the main thread interrupts Python-level waits, so a flaky hang in a
    get()/wait() fails the one test instead of stalling the whole run
    (reference: pytest.ini's 180 s default timeout). Long-training
    tests opt into a bigger budget with @pytest.mark.watchdog(N)."""
    import signal

    if threading.current_thread() is not threading.main_thread():
        yield
        return

    marker = request.node.get_closest_marker("watchdog")
    budget = int(marker.args[0]) if marker and marker.args else 150

    def _on_alarm(signum, frame):
        import faulthandler
        import sys
        faulthandler.dump_traceback(file=sys.stderr)
        raise TimeoutError(f"test exceeded {budget} s watchdog")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def cpu_mesh8():
    """An 8-device CPU mesh for sharding tests."""
    import jax
    devices = jax.devices("cpu")
    assert len(devices) >= 8, (
        "conftest must set xla_force_host_platform_device_count=8 before "
        "jax import")
    yield devices[:8]
