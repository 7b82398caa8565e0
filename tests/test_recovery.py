"""Chaos drills against the cluster event plane + recovery timelines.

Reference models: python/ray/tests/test_multinode_failures.py (node
death drills) — here each drill must additionally leave a queryable
causal chain: death event -> retries -> lease grants -> lineage
reconstruction, folded into per-incident detect/reschedule/reconstruct
durations by ``ray_tpu.devtools.recovery``.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import hostratio
import ray_tpu
from ray_tpu.devtools import recovery
from ray_tpu.exceptions import ActorDiedError
from ray_tpu.util import state


def _pin_soft(node_id):
    from ray_tpu.core.task_spec import SchedulingStrategy
    return SchedulingStrategy(kind="NODE_AFFINITY", node_id=node_id,
                              soft=True)


@pytest.fixture
def drill_cluster():
    from ray_tpu.core.cluster_utils import Cluster
    cluster = Cluster(
        head_node_args={"resources": {"CPU": 2}},
        system_config={"head_port": 0, "heartbeat_timeout_s": 2.5,
                       "object_store_memory": 64 * 1024 * 1024})
    yield cluster
    cluster.shutdown()


@pytest.mark.watchdog(300)
def test_node_death_drill_recovery_timeline(drill_cluster):
    """Freeze a node daemon (SIGSTOP: heartbeats stop, TCP stays open)
    so the head declares it dead via the heartbeat timeout — a genuine
    detect phase — then assert the retried task, the reconstructed
    object, and the recovery_report() fold all chain causally from the
    NODE_DEAD event, via the in-process store AND the CLI snapshot."""
    cluster = drill_cluster
    node_id, proc = cluster.add_remote_node(num_cpus=2)
    try:
        @ray_tpu.remote(max_retries=2)
        def produce():
            return np.arange(100_000, dtype=np.float64)  # shm-sized

        obj = produce.options(
            scheduling_strategy=_pin_soft(node_id)).remote()
        ray_tpu.wait([obj], timeout=30)

        @ray_tpu.remote(max_retries=2)
        def slow():
            import time as t

            import ray_tpu as rt
            t.sleep(2.0)
            return rt.get_runtime_context().get_node_id()

        # soft affinity: starts on the doomed node, retry falls back
        ref = slow.options(
            scheduling_strategy=_pin_soft(node_id)).remote()
        time.sleep(0.5)      # let it start there
        t_freeze = time.time()
        os.kill(proc.pid, signal.SIGSTOP)

        # the head must declare the death via the heartbeat timeout
        # (the frozen daemon keeps its TCP socket open)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if state.list_cluster_events(kinds=["NODE_DEAD"]):
                break
            time.sleep(0.1)
        else:
            pytest.fail("frozen node was never declared dead")
        detect_wall = time.time() - t_freeze

        # reschedule: the death-triggered retry lands on the head
        assert ray_tpu.get(ref, timeout=60) == \
            cluster.head_node_id.hex()
        # reconstruct: the only copy died with the node
        value = ray_tpu.get(obj, timeout=60)
        assert float(value.sum()) == float(np.arange(100_000).sum())

        dead = state.list_cluster_events(kinds=["NODE_DEAD"])
        assert len(dead) == 1
        assert dead[0]["node_id"] == node_id.hex()
        # detection had to ride the heartbeat timeout (2.5s), not a
        # connection drop — SIGSTOP keeps the socket open
        assert dead[0]["data"]["detect_s"] > 1.0
        assert detect_wall > 2.0

        report = recovery.recovery_report(journals={})
        incidents = [inc for inc in report["incidents"]
                     if inc["root_kind"] == "NODE_DEAD"]
        assert len(incidents) == 1
        inc = incidents[0]
        # all three recovery phases measured and nonzero
        assert inc["detect_s"] > 1.0
        assert inc["reschedule_s"] > 0.0
        assert inc["reconstruct_s"] > 0.0
        assert inc["mttr_s"] >= inc["detect_s"]
        # causally chained from the death event
        chain_kinds = {ev["kind"] for ev in inc["chain"]}
        assert {"NODE_DEAD", "TASK_RETRY", "LEASE_GRANTED",
                "RECONSTRUCT_START", "RECONSTRUCT_DONE"} <= chain_kinds
        assert inc["chain"][0]["seq"] == inc["root_seq"]
        assert all(ev["caused_by"] is not None
                   for ev in inc["chain"][1:])
        # the heartbeat-miss precursor is attributed, not part of MTTR
        assert inc["precursor"]["kind"] == "NODE_HEARTBEAT_MISS"
        assert node_id.hex() in inc["affected"]["nodes"]
        assert inc["affected"]["objects"]  # the reconstructed oid
        # printable without raising
        assert "NODE_DEAD" in recovery.render(report)

        # same incident through the out-of-process CLI surface. The
        # CLI finds the session through one pointer file a machine,
        # which every live runtime rewrites every 2 s: under xdist
        # another worker's session can own it when the child reads,
        # so ask until this session's snapshot is the one read.
        def cli_until(module_args, seen):
            deadline = time.monotonic() + 30
            while True:
                out = subprocess.run(
                    [sys.executable, "-m", *module_args],
                    capture_output=True, text=True, timeout=60)
                if out.returncode == 0 and seen(out.stdout):
                    return
                if time.monotonic() > deadline:
                    pytest.fail(f"{module_args}: NODE_DEAD never seen: "
                                f"{out.returncode} {out.stdout[-300:]!r} "
                                f"{out.stderr[-300:]!r}")
                time.sleep(0.2)

        cli_until(["ray_tpu.scripts.cli", "events", "--kind", "NODE_DEAD"],
                  lambda stdout: "NODE_DEAD" in stdout)
        # ... and the standalone report CLI folds the same snapshot
        cli_until(["ray_tpu.devtools.recovery", "--json"],
                  lambda stdout: any(
                      i["root_kind"] == "NODE_DEAD"
                      for i in json.loads(stdout)["incidents"]))
    finally:
        proc.send_signal(signal.SIGKILL)  # kills stopped processes too
        proc.wait(timeout=10)


@pytest.mark.watchdog(120)
def test_actor_kill_drill_attaches_timeline(ray_start_regular):
    """Kill an actor's worker process; a submission to the now-dead
    actor must fail with an ActorDiedError carrying the incident
    timeline, and the ACTOR_DEAD event must chain to the WORKER_EXIT
    that caused it."""
    @ray_tpu.remote(max_restarts=0)
    class Victim:
        def pid(self):
            import os as _os
            return _os.getpid()

        def slow(self):
            import time as t
            t.sleep(30)

    victim = Victim.remote()
    pid = ray_tpu.get(victim.pid.remote(), timeout=30)
    running = victim.slow.remote()
    time.sleep(0.5)
    os.kill(pid, signal.SIGKILL)

    with pytest.raises(Exception):  # in-flight call dies with the worker
        ray_tpu.get(running, timeout=60)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if state.list_cluster_events(kinds=["ACTOR_DEAD"]):
            break
        time.sleep(0.1)
    else:
        pytest.fail("ACTOR_DEAD event never recorded")

    with pytest.raises(ActorDiedError) as err:
        ray_tpu.get(victim.pid.remote(), timeout=60)
    assert "recovery timeline" in str(err.value)
    assert "WORKER_EXIT" in str(err.value)

    dead = state.list_cluster_events(kinds=["ACTOR_DEAD"])
    assert dead and dead[-1]["caused_by"] is not None
    exits = state.list_cluster_events(kinds=["WORKER_EXIT"],
                                      severity="ERROR")
    assert any(e["seq"] == dead[-1]["caused_by"] for e in exits)

    report = recovery.recovery_report(journals={})
    incidents = [inc for inc in report["incidents"]
                 if "ACTOR_DEAD" in {e["kind"] for e in inc["chain"]}]
    assert incidents
    assert incidents[0]["root_kind"] == "WORKER_EXIT"


def test_events_disabled_kill_switch(ray_start_regular):
    from ray_tpu.core.config import get_config
    cfg = get_config()
    before = len(state.list_cluster_events(limit=100_000))
    cfg.cluster_events_enabled = False
    try:
        @ray_tpu.remote
        def f():
            return 1

        ray_tpu.get(f.remote())
        assert len(state.list_cluster_events(limit=100_000)) == before
    finally:
        cfg.cluster_events_enabled = True


@pytest.mark.watchdog(300)
def test_events_overhead_ratio_guard(ray_start_regular):
    """Event-plane-enabled vs disabled wall time on a tight task loop
    must stay under a generous ratio bound: the emit is ~1.5us (the
    committed measured row lives in BENCH_core.json)."""
    from ray_tpu.core.config import get_config

    cfg = get_config()
    saved = cfg.cluster_events_enabled
    try:
        hostratio.judge_switched(
            "event plane on / off", 2.0,
            lambda: setattr(cfg, "cluster_events_enabled", False),
            lambda: setattr(cfg, "cluster_events_enabled", True))
    finally:
        cfg.cluster_events_enabled = saved
