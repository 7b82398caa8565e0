"""Flight recorder: ring semantics, clock alignment across processes,
post-mortem journal tails, Perfetto export schema, and the overhead
ratio guard (PR 12)."""

import json
import time

import numpy as np
import pytest

import hostratio
from ray_tpu.util import flight_recorder as fr


@pytest.fixture
def fresh_recorder():
    """Isolate the module-level recorder/store state per test."""
    saved = (fr.RECORDER, fr._STORE, fr._anchor)
    fr._STORE = fr.FlightStore()
    yield
    fr.RECORDER, fr._STORE, fr._anchor = saved


# --- ring semantics ---------------------------------------------------

def test_ring_wraparound_keeps_newest(fresh_recorder):
    rec = fr.enable("test:ring", capacity=16)
    for i in range(40):
        rec.record("io", "ev", 1000 + i, 10, {"i": i})
    events = rec.snapshot()
    # only the newest `capacity` events survive, oldest first
    assert [ev[0] for ev in events] == list(range(24, 40))
    assert events[0][5] == {"i": 24} and events[-1][5] == {"i": 39}
    # incremental snapshot picks up exactly the new suffix
    assert [ev[0] for ev in rec.snapshot(since_seq=37)] == [38, 39]


def test_disabled_recorder_is_inert(fresh_recorder):
    fr.disable()
    assert fr.RECORDER is None and not fr.enabled()
    fr.record("io", "ev", 0, 0)          # cold-path helpers no-op
    fr.instant("io", "mark")
    assert fr.local_tail() is None


def test_store_push_dedups_on_seq(fresh_recorder):
    fr.store_push("worker:aa", [(0, 100, 1, "io", "a", None),
                               (1, 200, 1, "io", "b", None)], 5)
    # a re-push of an overlapping increment must not duplicate
    fr.store_push("worker:aa", [(1, 200, 1, "io", "b", None),
                               (2, 300, 1, "io", "c", None)], 5)
    [(label, offset, events)] = fr.get_store().journals()
    assert label == "worker:aa" and offset == 5
    assert [ev[0] for ev in events] == [0, 1, 2]


# --- export schema ----------------------------------------------------

def test_chrome_events_schema(fresh_recorder):
    rec = fr.enable("test:export", capacity=64)
    t0 = fr.clock_ns()
    rec.record("pipeline", "FWD", t0, 2_000_000,
               {"stage": 0, "mb": 1, "phase": "steady"})
    rec.instant("object", "serve_out", {"bytes": 64})
    fr.store_push("worker:bb", [(0, t0, 1_000, "shuffle", "map_wave",
                                 {"order": 0})], 0)
    all_events = json.loads(json.dumps(fr.chrome_events()))
    meta = [ev for ev in all_events if ev["ph"] == "M"]
    events = [ev for ev in all_events if ev["ph"] != "M"]
    assert len(events) == 3
    pids = {ev["pid"] for ev in events}
    assert pids == {"flight:test:export", "flight:worker:bb"}
    for ev in events:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(ev)
        assert isinstance(ev["ts"], (int, float))
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        else:
            assert ev["ph"] == "i" and ev.get("s") == "t"
    # Perfetto polish: every track leads with process_name/thread_name
    # metadata naming the role instead of the bare journal label.
    proc_names = {ev["pid"]: ev["args"]["name"] for ev in meta
                  if ev["name"] == "process_name"}
    assert set(proc_names) == pids
    assert proc_names["flight:worker:bb"] == "worker-bb"
    thread_rows = {(ev["pid"], ev["tid"]) for ev in meta
                   if ev["name"] == "thread_name"}
    assert ("flight:test:export", "pipeline") in thread_rows
    assert ("flight:worker:bb", "shuffle") in thread_rows


def test_whereis_attribution_from_synthetic_journal(fresh_recorder,
                                                    tmp_path):
    # one stage, two steps: 60% compute → bubble 0.4; S=2, m=8 → 1/9
    journal = {"worker:stage0": [
        (0, 1_000, 4_000_000, "pipeline", "SEND",
         {"stage": 0, "step": 0, "mb": 0, "kind": "act",
          "phase": "steady"}),
        (1, 0, 10_000_000, "pipeline", "stage_step",
         {"stage": 0, "step": 0, "schedule": "1f1b", "S": 2, "m": 8,
          "wall_s": 0.01, "compute_s": 0.006}),
        (2, 12_000_000, 10_000_000, "pipeline", "stage_step",
         {"stage": 0, "step": 1, "schedule": "1f1b", "S": 2, "m": 8,
          "wall_s": 0.01, "compute_s": 0.006}),
        (3, 5_000, 3_000_000, "prefetch", "consumer_wait", None),
        (4, 9_000, 1_000_000, "collective", "allreduce",
         {"dtype": "float32", "wire": 1024, "ratio": 3.9}),
    ]}
    from ray_tpu.devtools import whereis
    report = whereis.attribution(journal)
    assert report["steps"] == 2 and report["stages"] == 1
    assert report["measured_bubble"] == pytest.approx(0.4)
    assert report["theoretical_bubble"] == pytest.approx(1 / 9, abs=1e-3)
    assert report["fractions"]["compute"] == pytest.approx(0.6)
    assert report["fractions"]["comms"] == pytest.approx(0.2)
    assert report["collectives"]["count"] == 1
    assert report["collectives"]["mean_compression_ratio"] == 3.9
    text = whereis.render(report)
    assert "measured bubble: 0.400" in text
    # CLI round-trip through the dump-file format
    dump = tmp_path / "journal.json"
    dump.write_text(json.dumps(
        {"journals": {k: [list(ev) for ev in v]
                      for k, v in journal.items()}}))
    report2 = whereis.attribution(whereis._load_journals(str(dump)))
    assert report2["measured_bubble"] == report["measured_bubble"]


# --- clock alignment across processes ---------------------------------

@pytest.mark.watchdog(180)
def test_clock_alignment_two_workers(monkeypatch):
    """Workers run with a +1.5s injected clock skew; the ping-pong sync
    must fold their journals back into the driver's time domain: every
    aligned worker event lands inside the driver-observed run window
    (tolerance ≪ the injected skew)."""
    import ray_tpu

    monkeypatch.setenv("RTPU_FLIGHT_TEST_SKEW_NS", "1500000000")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, system_config={
        "flight_recorder_enabled": True,
        "flight_flush_interval_s": 0.05,
    })
    try:
        @ray_tpu.remote(num_cpus=0)
        def stamp(tag):
            from ray_tpu.util import flight_recorder
            flight_recorder.instant("test", "stamp", {"tag": tag})
            return tag

        t0 = fr.clock_ns()
        assert sorted(ray_tpu.get([stamp.remote(i)
                                   for i in range(8)])) == list(range(8))
        deadline = time.time() + 10
        while time.time() < deadline:
            merged = fr.merged_journals()
            stamps = [ev for label, events in merged.items()
                      if label.startswith("worker:")
                      for ev in events if ev[4] == "stamp"]
            if len(stamps) >= 8:
                break
            time.sleep(0.1)     # flusher interval is 50ms
        t1 = fr.clock_ns()
        assert len(stamps) >= 8, f"journals never flushed: {merged.keys()}"
        tol_ns = 500_000_000    # 0.5s ≪ the 1.5s injected skew
        for ev in stamps:
            assert t0 - tol_ns <= ev[1] <= t1 + tol_ns, (
                f"unaligned event {ev}: outside [{t0}, {t1}] by "
                f"{max(t0 - ev[1], ev[1] - t1) / 1e6:.1f}ms")
    finally:
        ray_tpu.shutdown()


# --- post-mortem ------------------------------------------------------

def _model_fns():
    import jax.numpy as jnp

    def apply_layer(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def loss_fn(out, tgt):
        return jnp.mean((out - tgt) ** 2)

    return apply_layer, loss_fn


@pytest.mark.watchdog(300)
def test_postmortem_tail_rides_dag_error(monkeypatch):
    """An injected stage failure (PR-10 ("fail", sid, ·) hook) surfaces
    a DAGExecutionError whose message embeds the dead stage's last-N
    journal events."""
    import ray_tpu
    from ray_tpu.dag import DAGExecutionError
    from ray_tpu.train.pipeline import LayeredModel, PipelineRunner

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, system_config={
        "flight_recorder_enabled": True,
        "flight_flush_interval_s": 0.05,
        "task_max_retries": 0,
    })
    try:
        rng = np.random.RandomState(0)
        d = 8
        layers = [{"w": rng.randn(d, d).astype(np.float32) * 0.1,
                   "b": np.zeros(d, dtype=np.float32)}
                  for _ in range(2)]
        x = rng.randn(8, d).astype(np.float32)
        y = rng.randn(8, d).astype(np.float32)
        runner = PipelineRunner(
            LayeredModel(layers, *_model_fns()),
            num_stages=2, num_microbatches=4, schedule="1f1b",
            recv_timeout_s=3.0)
        try:
            assert runner.step(x, y)["loss"] is not None
            runner.inject_failure(1)
            with pytest.raises(DAGExecutionError) as err:
                runner.execute_async(x, y).get(60.0)
            msg = str(err.value)
            assert "flight recorder (last" in msg
            # the tail shows what the stage was doing when it died
            assert "pipeline:" in msg
        finally:
            runner.shutdown()
    finally:
        ray_tpu.shutdown()


@pytest.mark.watchdog(180)
def test_postmortem_tail_on_worker_crash():
    """A worker dying mid-task (os._exit) surfaces the collector's copy
    of its journal in the WorkerCrashedError/ActorUnavailableError."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, system_config={
        "flight_recorder_enabled": True,
        "flight_flush_interval_s": 0.05,
        "task_max_retries": 0,
    })
    try:
        @ray_tpu.remote(max_restarts=0)
        class A:
            def work(self, i):
                fr.instant("test", "work", {"i": i})
                return i

            def crash(self):
                import os
                os._exit(1)

        a = A.remote()
        assert ray_tpu.get([a.work.remote(i) for i in range(4)]) == \
            list(range(4))
        time.sleep(0.3)              # let the flusher push the journal
        a.crash.remote()
        with pytest.raises(Exception) as err:
            ray_tpu.get(a.work.remote(99), timeout=30)
        msg = str(err.value)
        assert "flight recorder (last" in msg and "test:work" in msg
    finally:
        ray_tpu.shutdown()


# --- overhead guard (satellite: ratio-based per PERF.md) --------------

@pytest.mark.watchdog(300)
def test_recorder_overhead_ratio_guard(ray_start_regular):
    """Recorder-enabled vs disabled wall time on a tight task loop must
    stay under a generous ratio bound: the record path is two loads +
    a compare when off, and one tuple store when on (~ns/event)."""
    saved = fr.RECORDER
    try:
        hostratio.judge_switched("recorder on / off", 2.0, fr.disable,
                                 lambda: fr.enable("driver:overhead"))
    finally:
        fr.RECORDER = saved
