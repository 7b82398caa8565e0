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


# --- the stall watch (PR 63) -------------------------------------------
# Clocks and the sleep are the test's: a loaded box can neither make an
# episode nor hide one.

class _Script:
    """A watch whose clock, CPU clock and sleep a script advances:
    ``tick(late_s, cpu_s)`` is one turn in which the sleep took the
    tick and ``late_s`` more while the process's threads took ``cpu_s``
    CPU seconds. While it lives the counts have a carrier (this),
    so the watch ships none itself and ``counts()`` finds them."""

    def __init__(self, role="worker"):
        fr.stall_carriers.add(self)
        self.now_ns, self.cpu = 5_000_000_000, 3.0
        self.late_s = self.cpu_s = 0.0
        self.watch = fr.StallWatch(
            role, clock=lambda: self.now_ns, cpu_clock=lambda: self.cpu,
            wall=lambda: 1_700_000_000.0 + self.now_ns / 1e9,
            sleep=self._sleep)

    def _sleep(self, seconds):
        assert seconds == fr.STALL_TICK_S
        self.now_ns += int((seconds + self.late_s) * 1e9)
        self.cpu += self.cpu_s

    def tick(self, late_s=0.0, cpu_s=0.0):
        self.late_s, self.cpu_s = late_s, cpu_s
        self.watch.tick()

    def counts(self):
        """{(series, tags): value} of what the watch has not shipped."""
        return {(name, tags): value for _, name, tags, value, _
                in self.watch.take_counts()}


@pytest.mark.parametrize("late_s,cpu_s,kind", [
    (2.0, 0.0, "frozen"),      # nothing in the process ran
    (2.0, 0.15, "frozen"),     # under a tenth of the overshoot
    (2.0, 1.9, "starved"),     # a thread ran and the watch could not
    (0.5, 0.06, "starved"),
    (0.099, 0.0, None),        # under 100 ms: no episode
    (0.0, 0.02, None)])
def test_late_wake_is_frozen_or_starved_by_the_cpu_clock(late_s, cpu_s,
                                                         kind):
    script = _Script()
    script.tick()
    t_due = script.now_ns + int(fr.STALL_TICK_S * 1e9)
    script.tick(late_s, cpu_s)
    episodes = script.watch.stalls()
    if kind is None:
        assert episodes == [] and script.counts() == {}
        return
    (ep,) = episodes
    assert (ep["name"], ep["kind"], ep["process"]) == (
        "process.stall", kind, "worker")
    assert ep["seconds"] == pytest.approx(late_s, abs=1e-6)
    assert ep["cpu_s"] == pytest.approx(cpu_s)
    # it starts where the wake was due, on both clocks
    assert abs(ep["t0_ns"] - t_due) <= 1
    assert ep["epoch_s"] == pytest.approx(1_700_000_000.0 + t_due / 1e9,
                                          abs=1e-3)
    assert ep["pid"] > 0
    # a starved second or more says where the threads stand after it
    assert ("after" in ep) == (kind == "starved" and late_s >= 1.0)
    assert script.counts() == {
        ("ray_tpu_process_stall_seconds_total",
         (("process", "worker"),)): pytest.approx(late_s, abs=1e-6),
        ("ray_tpu_process_stalls_total",
         (("kind", kind), ("process", "worker"))): 1.0}


def test_a_busy_process_frozen_is_not_read_as_starved():
    """Thirteen busy cores take 0.26 CPU s in a tick that is on time:
    the same 0.26 s in a wake 2 s late is the tick's usual share, and
    nothing ran in the 2 s."""
    script = _Script()
    for _ in range(3):
        script.tick(0.0, 0.26)
    script.tick(2.0, 0.26)
    (ep,) = script.watch.stalls()
    assert ep["kind"] == "frozen"
    assert (ep["cpu_s"], ep["cpu_usual_s"]) == (
        pytest.approx(0.26), pytest.approx(0.26))


def _parked_thread():
    """A thread that sits in ``_parked_here`` until released."""
    import threading
    release, started = threading.Event(), threading.Event()

    def _parked_here():
        started.set()
        release.wait(30.0)

    thread = threading.Thread(target=_parked_here, daemon=True)
    thread.start()
    assert started.wait(5.0)
    return thread, release


def test_probe_past_the_threshold_is_one_held_episode_of_the_whole_stay():
    script = _Script(role="replica")
    thread, release = _parked_thread()
    place = {"now": None}
    heard = []
    script.watch.add_probe("stepper", lambda: place["now"],
                           lambda: thread.ident, on_held=heard.append)
    try:
        script.tick()
        place["now"] = ("blocked", 41.5)
        script.tick()                       # first seen here
        t_seen = script.now_ns
        for _ in range(12):                 # 240 ms: under the threshold
            script.tick()
        assert script.watch.stalls() == [] and heard == []
        script.tick()                       # 260 ms
        (ep,) = script.watch.stalls()
        assert ep["open"] is True and heard == [script.watch._ring[0]]
        assert (ep["kind"], ep["thread"], ep["phase"], ep["process"]) == (
            "held", "stepper", "blocked", "replica")
        # the stack is the probed thread's, taken once
        assert "_parked_here" in "".join(ep["stack"])
        assert "release.wait(30.0)" in "".join(ep["stack"])
        for _ in range(187):                # it stays 4 s in all
            script.tick()
        assert len(script.watch.stalls()) == 1 and len(heard) == 1
        assert script.counts() == {}        # nothing counted while open
        place["now"] = ("launch", 45.5)     # ``since`` moves: it closes
        script.tick()
    finally:
        release.set()
    (ep,) = script.watch.stalls()
    assert "open" not in ep and ep["t0_ns"] == t_seen
    assert ep["seconds"] == pytest.approx(201 * fr.STALL_TICK_S, abs=1e-6)
    assert ep["phase"] == "blocked" and len(ep["stack"]) >= 1
    assert script.counts() == {
        ("ray_tpu_thread_held_seconds_total",
         (("process", "replica"), ("thread", "stepper"))):
        pytest.approx(ep["seconds"]),
        ("ray_tpu_thread_held_total",
         (("phase", "blocked"), ("process", "replica"),
          ("thread", "stepper"))): 1.0}
    # the next place starts its own count: 240 ms more is no episode
    for _ in range(12):
        script.tick()
    assert len(script.watch.stalls()) == 1


def test_idle_probe_and_a_probe_whose_thread_is_gone_give_none():
    script = _Script()
    thread, release = _parked_thread()
    release.set()
    thread.join(5.0)
    reads = []

    def idle():
        reads.append(script.now_ns)
        return None

    script.watch.add_probe("stepper", idle, lambda: 0)
    gone = script.watch.add_probe("io_loop", lambda: ("dispatch", 7.0),
                                  lambda: thread.ident)
    for _ in range(40):
        script.tick()
    assert len(reads) == 40 and script.watch.stalls() == []
    assert script.counts() == {}
    script.watch.remove_probe(gone)
    assert [p.thread for p in script.watch._probes] == ["stepper"]


def test_probes_come_and_go_under_a_ticking_watch():
    """Eight threads add and remove probes while the watch ticks as
    fast as it can: no probe is lost and none is left."""
    import sys
    import threading
    script = _Script()
    kept, errors, done = [], [], threading.Event()

    def churn(k):
        try:
            for i in range(200):
                probe = script.watch.add_probe(
                    f"t{k}", lambda: None, lambda: None)
                if i % 50 == 0:
                    kept.append(probe)
                else:
                    script.watch.remove_probe(probe)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    def ticking():
        while not done.is_set():
            script.tick()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ticker = threading.Thread(target=ticking, daemon=True)
        ticker.start()
        threads = [threading.Thread(target=churn, args=(k,), daemon=True)
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        done.set()
        ticker.join(10.0)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not ticker.is_alive()
    assert not any(t.is_alive() for t in threads)
    assert len(kept) == 32
    assert sorted(map(id, script.watch._probes)) == sorted(map(id, kept))
    assert script.watch.stalls() == []


def test_the_time_a_process_did_not_run_is_no_threads_stay():
    """A stepper 200 ms into a phase when the process stops for 2 s:
    one frozen episode, no held one, and its stay goes on from 200 ms."""
    script = _Script(role="replica")
    thread, release = _parked_thread()
    script.watch.add_probe("stepper", lambda: ("blocked", 1.0),
                           lambda: thread.ident)
    try:
        for _ in range(11):
            script.tick()                   # seen for 200 ms
        script.tick(2.0, 0.0)
        assert [e["kind"] for e in script.watch.stalls()] == ["frozen"]
        script.tick()                       # 240 ms of its own
        assert len(script.watch.stalls()) == 1
        script.tick()                       # 260 ms
        assert [e["kind"] for e in script.watch.stalls()] == [
            "frozen", "held"]
    finally:
        release.set()


def test_counters_ring_and_journal_agree(fresh_recorder):
    rec = fr.enable("test:stalls", capacity=64)
    script = _Script(role="train_worker")
    thread, release = _parked_thread()
    place = {"now": ("upload", 1.0)}
    script.watch.add_probe("stepper", lambda: place["now"],
                           lambda: thread.ident)
    try:
        script.tick()
        script.tick(0.3, 0.0)
        script.tick(1.2, 1.1)
        for _ in range(20):
            script.tick()
        place["now"] = None
        script.tick()
        script.tick(0.7, 0.0)
    finally:
        release.set()
    ring = script.watch.stalls()
    assert [e["kind"] for e in ring] == ["frozen", "starved", "held",
                                         "frozen"]
    counts = script.counts()
    role = ("process", "train_worker")
    stalled = [e for e in ring if e["name"] == "process.stall"]
    assert counts[("ray_tpu_process_stall_seconds_total", (role,))] == \
        pytest.approx(sum(e["seconds"] for e in stalled))
    assert counts[("ray_tpu_process_stalls_total",
                   (("kind", "frozen"), role))] == 2.0
    assert counts[("ray_tpu_process_stalls_total",
                   (("kind", "starved"), role))] == 1.0
    assert counts[("ray_tpu_thread_held_seconds_total",
                   (role, ("thread", "stepper")))] == \
        pytest.approx(ring[2]["seconds"])
    assert len(counts) == 5 and script.counts() == {}
    # the journal has each under ``proc``, on the journal's clock
    events = [ev for ev in rec.snapshot() if ev[3] == "proc"]
    # (in the order they closed)
    assert [(ev[4], ev[5]["kind"]) for ev in events] == [
        ("process.stall", "frozen"), ("process.stall", "starved"),
        ("thread.held", "held"), ("process.stall", "frozen")]
    for ev in events:
        ep = next(e for e in ring if e["t0_ns"] == ev[1])
        assert ev[2] == int(ep["seconds"] * 1e9)
        assert ev[5]["process"] == "train_worker" and "t0_ns" not in ev[5]
    held = next(ev for ev in events if ev[4] == "thread.held")
    assert held[5]["thread"] == "stepper" and held[5]["phase"] == "upload"
    assert "_parked_here" in held[5]["stack"]
    # and the timeline shows them on the process's track
    names = {e["name"] for e in fr.chrome_events()
             if e.get("cat") == "flight:proc"}
    assert names == {"process.stall", "thread.held"}
    # ... and whereis sums them by journal and kind
    from ray_tpu.devtools import whereis
    report = whereis.attribution(fr.merged_journals())
    assert report["stalls"] == {"test:stalls": {
        "frozen": [2, pytest.approx(1.0)],
        "starved": [1, pytest.approx(1.2)],
        "held:stepper/upload": [1, pytest.approx(ring[2]["seconds"],
                                                 abs=1e-6)]}}
    assert "stalls of test:stalls: frozen x2 1000ms" in \
        whereis.render(report)
    # the ring keeps the last 32
    for _ in range(40):
        script.tick(0.2, 0.0)
    assert len(script.watch.stalls()) == fr.STALL_RING == 32


def test_with_no_carrier_the_watch_ships_an_episodes_counts_itself(
        monkeypatch):
    import weakref

    from ray_tpu.util import metrics
    script = _Script(role="node")
    monkeypatch.setattr(fr, "stall_carriers", weakref.WeakSet())
    batches = []
    real = metrics.record_batch
    monkeypatch.setattr(metrics, "record_batch",
                        lambda items: (batches.append(items), real(items)))
    for _ in range(5):
        script.tick()
    assert batches == []            # nothing while nothing happens
    script.tick(0.25, 0.0)
    script.tick()
    assert len(batches) == 1        # one record_batch an episode
    assert script.counts() == {}
    lines = [line for line in metrics.prometheus_text().splitlines()
             if 'process="node"' in line]
    assert sorted(line.split("{")[0] for line in lines) == [
        "ray_tpu_process_stall_seconds_total",
        "ray_tpu_process_stalls_total"]
    for name in ("ray_tpu_process_stall_seconds_total",
                 "ray_tpu_process_stalls_total"):
        metrics.remove_series(name, {"process": "node"})
    metrics.remove_series("ray_tpu_process_stalls_total",
                          {"process": "node", "kind": "frozen"})


def test_a_starved_second_says_where_the_threads_stand_after_it():
    """What held the interpreter during a stop no thread under the
    lock can see; right after it the holder has just come out of its
    call, and the episode keeps every thread's three innermost frames."""
    script = _Script()
    thread, release = _parked_thread()
    thread.name = "parked-for-the-test"
    try:
        script.tick()
        script.tick(1.6, 1.5)
        script.tick(0.5, 0.4)       # too short to be worth the stacks
    finally:
        release.set()
    long, short = script.watch.stalls()
    assert (long["kind"], short["kind"]) == ("starved", "starved")
    assert "after" not in short
    where = long["after"]["parked-for-the-test"]
    # innermost first: the two waits, then the frame that called them
    assert " wait <- " in where and where.endswith("_parked_here")
    assert __file__ in where
    import threading
    assert threading.current_thread().name not in long["after"]


_STOPPED_CHILD = """
import json, sys, time
from ray_tpu.util import flight_recorder as fr
fr.start_stall_watch("worker")
print("up", flush=True)
sys.stdin.readline()
time.sleep(0.2)
print(json.dumps(fr.stalls()), flush=True)
"""


@pytest.mark.skipif(not hasattr(__import__("signal"), "SIGSTOP"),
                    reason="the platform has no SIGSTOP")
def test_a_stopped_child_reports_one_frozen_episode():
    import os
    import signal
    import subprocess
    import sys
    import threading
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = subprocess.Popen(
        [sys.executable, "-c", _STOPPED_CHILD], cwd=root,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONPATH": root})
    try:
        assert child.stdout.readline().strip() == "up"
        time.sleep(0.2)
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.6)
        os.kill(child.pid, signal.SIGCONT)
        child.stdin.write("\n")
        child.stdin.flush()
        episodes = json.loads(child.stdout.readline())
    finally:
        child.kill()
        child.wait()
    frozen = [e for e in episodes if e["kind"] == "frozen"
              and 0.5 <= e["seconds"] <= 1.5]
    assert len(frozen) == 1, episodes
    assert frozen[0]["process"] == "worker" and frozen[0]["pid"] == child.pid
    assert threading.active_count() >= 1


def test_one_watch_thread_in_the_driver_and_in_every_worker(
        ray_start_regular):
    import threading

    import ray_tpu

    @ray_tpu.remote
    def look():
        import threading
        from ray_tpu.util import flight_recorder
        watch = flight_recorder.stall_watch()
        return [t.name for t in threading.enumerate()], watch.role

    names, role = ray_tpu.get(look.remote())
    assert names.count("rtpu-stall-watch") == 1 and role == "worker"
    assert not [n for n in names if "watchdog" in n]
    mine = [t.name for t in threading.enumerate()]
    assert mine.count("rtpu-stall-watch") == 1
    watch = fr.stall_watch()
    assert watch.role == "driver"
    # a worker that becomes a replica or a train worker says so; no
    # other process changes its name
    fr.rename_worker("replica")
    assert watch.role == "driver"
