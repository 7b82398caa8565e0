"""PR-20 collsan tests: the cross-rank collective-program sanitizer.

Pure halves first — ``fold()`` classification per finding kind,
``stall_findings`` aging, the ``_CollsanStore`` push dedup,
``verify_program`` contracts (shared with pipeline
``validate_schedule``) — then the live runtime wiring under
``RAY_TPU_COLLSAN=1``: a clean multi-rank run reports zero findings, a
seeded rank-divergent run reports exactly the planted one, and the
error-feedback residual staleness fix (size-keyed buffers cleared on
init/destroy) keeps a recreated group bitwise-identical to a fresh
one. Closes with the disabled-path overhead guard (< 2.0x, matching
the BENCH_core.json acceptance row)."""

import json
import os
import time

import numpy as np
import pytest

import hostratio
import ray_tpu
from ray_tpu.devtools import collsan

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_collsan():
    """Isolate the module-global ledger/store/findings state."""
    saved = (collsan.LEDGER, collsan._STORE, collsan._final_findings,
             list(collsan._watchdog_findings))
    collsan.LEDGER = None
    collsan._STORE = None
    collsan._final_findings = None
    collsan._watchdog_findings = []
    yield
    (collsan.LEDGER, collsan._STORE, collsan._final_findings,
     wd) = saved
    collsan._watchdog_findings = wd


def _events(per_rank, group="g", world=None, t=0.0):
    """Enter-event stream from rank -> [op-kind-or-fingerprint, ...]."""
    world = len(per_rank) if world is None else world
    out, idx = [], 0
    for rank, fps in sorted(per_rank.items()):
        for seq, fp in enumerate(fps):
            if isinstance(fp, str):
                fp = collsan.fingerprint(fp)
            out.append((idx, "enter", group, rank, world, seq, fp, t))
            idx += 1
    return out


# --- fold(): one deterministic fixture per finding class -----------------

def test_fold_identical_programs_clean():
    prog = ["allreduce", "barrier", "broadcast", "allgather_flat"]
    events = _events({0: prog, 1: prog, 2: prog})
    assert collsan.fold(events, expect_complete=True) == []


def test_fold_op_mismatch():
    # rank 1's seq-1 op has no counterpart nearby on either side: a
    # flatly different program, not a reorder
    events = _events({0: ["allreduce", "barrier", "allreduce"],
                      1: ["allreduce", "broadcast", "allreduce"]})
    findings = collsan.fold(events, expect_complete=True)
    assert [f["kind"] for f in findings] == ["op_mismatch"]
    f = findings[0]
    assert (f["group"], f["seq"], f["ranks"]) == ("g", 1, [0, 1])
    assert "rank 0" in f["detail"] and "rank 1" in f["detail"]


def test_fold_order_divergence_and_cascade_break():
    # rank 1 swapped barrier/broadcast: each side's "missing" op shows
    # up within the lookahead window -> order_divergence, and the
    # cascading seq-2 difference is suppressed (first divergence only)
    events = _events({0: ["allreduce", "barrier", "broadcast"],
                      1: ["allreduce", "broadcast", "barrier"]})
    findings = collsan.fold(events, expect_complete=True)
    assert [f["kind"] for f in findings] == ["order_divergence"]
    f = findings[0]
    assert f["seq"] == 1
    assert "rank 0 window" in f["detail"]
    assert "seq 2: broadcast" in f["detail"]


def test_fold_reorder_beyond_lookahead_is_op_mismatch():
    # the counterpart op only reappears _REORDER_LOOKAHEAD+1 seqs later:
    # too far to call it a reorder
    far = collsan._REORDER_LOOKAHEAD + 1
    prog0 = ["barrier"] + ["allreduce"] * far + ["barrier"]
    prog1 = ["broadcast"] + ["allreduce"] * far + ["barrier"]
    events = _events({0: prog0, 1: prog1})
    findings = collsan.fold(events, expect_complete=True)
    assert [f["kind"] for f in findings] == ["op_mismatch"]
    assert findings[0]["seq"] == 0


def test_fold_dtype_shape_compression_mismatches():
    fp = collsan.fingerprint
    cases = [
        ("dtype_mismatch",
         fp("allreduce", "float32", 64, (64,)),
         fp("allreduce", "bfloat16", 64, (64,))),
        ("shape_mismatch",
         fp("allreduce", "float32", 64, (64,)),
         fp("allreduce", "float32", 128, (128,))),
        ("shape_mismatch",  # same flat size, different dims
         fp("allreduce", "float32", 64, (8, 8)),
         fp("allreduce", "float32", 64, (64,))),
        ("compression_mismatch",
         fp("allreduce", "float32", 64, (64,), "int8", "leaf-a"),
         fp("allreduce", "float32", 64, (64,), "int8", "leaf-b")),
        ("compression_mismatch",
         fp("allreduce", "float32", 64, (64,), None, None, "ring"),
         fp("allreduce", "float32", 64, (64,), None, None, "tree")),
    ]
    for want, fp0, fp1 in cases:
        events = _events({0: [fp0], 1: [fp1]})
        findings = collsan.fold(events, expect_complete=True)
        assert [f["kind"] for f in findings] == [want], (want, findings)


def test_fold_missing_rank_only_when_complete():
    # rank 2 of world 3 never issued anything
    events = _events({0: ["allreduce", "barrier"],
                      1: ["allreduce", "barrier"]}, world=3)
    assert collsan.fold(events) == []  # live fold: could be flush lag
    findings = collsan.fold(events, expect_complete=True)
    assert [f["kind"] for f in findings] == ["missing_rank"]
    assert findings[0]["ranks"] == [2]
    assert "never issued" in findings[0]["detail"]


def test_fold_missing_rank_trailing_short():
    events = _events({0: ["allreduce", "barrier", "broadcast"],
                      1: ["allreduce", "barrier"]})
    assert collsan.fold(events) == []
    findings = collsan.fold(events, expect_complete=True)
    assert [f["kind"] for f in findings] == ["missing_rank"]
    assert findings[0]["ranks"] == [1]
    assert findings[0]["seq"] == 2
    assert "stopped after seq 1" in findings[0]["detail"]


def test_fold_skips_p2p_groups():
    # send/recv programs legitimately differ per rank
    events = _events({0: ["send", "send"], 1: ["recv"]},
                     group=collsan.P2P_PREFIX + "g")
    assert collsan.fold(events, expect_complete=True) == []


# --- ledger / store ------------------------------------------------------

def test_ledger_seq_per_group_and_exit_tokens():
    led = collsan.Ledger(label="t")
    fp = collsan.fingerprint("allreduce")
    assert led.record_enter("a", 0, 2, fp) == 0
    assert led.record_enter("b", 0, 2, fp) == 0
    assert led.record_enter("a", 0, 2, fp) == 1
    led.record_exit("a", 0, 2, 1, "allreduce")
    kinds = [(ev[1], ev[2], ev[5]) for ev in led.snapshot()]
    assert kinds == [("enter", "a", 0), ("enter", "b", 0),
                     ("enter", "a", 1), ("exit", "a", 1)]
    # idx tickets strictly increase (the store dedup key)
    idxs = [ev[0] for ev in led.snapshot()]
    assert idxs == sorted(set(idxs))


def test_store_push_dedups_replayed_events():
    store = collsan._CollsanStore()
    events = _events({0: ["allreduce", "barrier"]})
    store.push("w0", events)
    store.push("w0", events)                # full replay: no dupes
    store.push("w0", events + _events({0: ["x"]})[-1:])
    assert len(store.journals()["w0"]) == len(events)
    more = [(len(events), "enter", "g", 0, 1, 2,
             collsan.fingerprint("broadcast"), 0.0)]
    store.push("w0", events + more)         # overlap + one new
    assert len(store.journals()["w0"]) == len(events) + 1


# --- stall_findings / watchdog -------------------------------------------

def _stall_events():
    fp = collsan.fingerprint("allreduce", "float32", 32, (32,))
    return [
        (0, "enter", "g", 0, 3, 0, fp, 100.0),
        (1, "enter", "g", 1, 3, 0, fp, 100.5),
        (2, "exit", "g", 1, 3, 0, ("allreduce",), 101.0),
    ]


def test_stall_findings_names_parked_and_missing():
    findings = collsan.stall_findings(_stall_events(), stall_s=30.0,
                                      now=140.0)
    assert len(findings) == 1
    f = findings[0]
    assert (f["kind"], f["group"], f["seq"]) == ("stall", "g", 0)
    assert f["ranks"] == [0]        # rank 1 exited, rank 0 is parked
    assert f["missing"] == [2]      # rank 2 of world 3 never arrived
    assert f["ops"] == ["allreduce"]
    assert f["parked_since"] == 100.0
    assert "parked inside allreduce" in f["detail"]
    assert "never arrived" in f["detail"]


def test_stall_findings_fresh_entries_quiet():
    assert collsan.stall_findings(_stall_events(), stall_s=30.0,
                                  now=110.0) == []


def test_stall_findings_covers_p2p_groups():
    # the order fold skips p2p: groups; the stall scan must not — a
    # parked recv is exactly the hang it exists to name
    fp = collsan.fingerprint("recv", ef_key="0->1/0")
    events = [(0, "enter", collsan.P2P_PREFIX + "g", 1, 2, 0, fp, 100.0)]
    findings = collsan.stall_findings(events, stall_s=30.0, now=200.0)
    assert [f["kind"] for f in findings] == ["stall"]
    assert "recv" in findings[0]["detail"]


def test_watchdog_scan_dedups_by_group_seq(fresh_collsan):
    led = collsan.enable(label="t")
    led.record_enter("g", 0, 2, collsan.fingerprint("barrier"))
    wd = collsan._Watchdog(stall_s=0.0)
    assert len(wd.scan_once(now=time.time() + 5)) == 1
    assert wd.scan_once(now=time.time() + 10) == []  # already reported
    assert len(collsan._watchdog_findings) == 1
    # report() folds the watchdog finding in exactly once
    kinds = [f["kind"] for f in collsan.report()]
    assert kinds.count("stall") == 1


def test_report_serves_final_findings_after_teardown(fresh_collsan):
    assert collsan.report() == []
    collsan._final_findings = [{"kind": "op_mismatch", "group": "g",
                                "seq": 0, "ranks": [0, 1],
                                "detail": "x"}]
    assert collsan.report() == collsan._final_findings
    assert collsan.report() is not collsan._final_findings  # a copy


# --- capture (profdiff input) --------------------------------------------

def test_capture_folds_traffic_per_group_op(fresh_collsan):
    events = _events(
        {0: [collsan.fingerprint("allreduce", "float32", 1000, (1000,)),
             collsan.fingerprint("allreduce", "float32", 1000, (1000,)),
             collsan.fingerprint("barrier")]},
        world=1)
    cap = collsan.capture(events)
    assert cap["kind"] == "rtpu-collsan"
    row = cap["groups"]["g"]["allreduce"]
    assert row == {"count": 2, "bytes": 8000}  # 2 * 1000 * 4B
    assert cap["groups"]["g"]["barrier"] == {"count": 1, "bytes": 0}

    from ray_tpu.devtools import profdiff
    norm = profdiff.normalize(cap)
    assert norm["phases"]["g/allreduce"] == 8000.0
    assert norm["counts"]["g/allreduce"] == 2


# --- verify_program ------------------------------------------------------

def _valid_program():
    return {
        0: [{"op": "allreduce", "key": "grads"},
            {"op": "send", "chan": "0->1", "key": 0},
            {"op": "send", "chan": "0->1", "key": 1},
            {"op": "barrier", "key": None}],
        1: [{"op": "allreduce", "key": "grads"},
            {"op": "recv", "chan": "0->1", "key": 0},
            {"op": "recv", "chan": "0->1", "key": 1},
            {"op": "barrier", "key": None}],
    }


def test_verify_program_valid():
    assert collsan.verify_program(_valid_program(), world=2) == []


def test_verify_program_group_order_divergence():
    prog = _valid_program()
    prog[1][0], prog[1][3] = prog[1][3], prog[1][0]
    (violation,) = collsan.verify_program(prog, world=2)
    assert "diverges" in violation and "op #0" in violation
    assert "allreduce" in violation and "barrier" in violation


def test_verify_program_key_divergence():
    prog = _valid_program()
    prog[1][0]["key"] = "other-grads"
    (violation,) = collsan.verify_program(prog, world=2)
    assert "diverges" in violation


def test_verify_program_unpaired_and_reordered_p2p():
    prog = _valid_program()
    del prog[1][2]                       # recv for key 1 never issued
    (violation,) = collsan.verify_program(prog, world=2)
    assert "chan '0->1'" in violation and "unpaired" in violation

    prog = _valid_program()
    prog[1][1]["key"], prog[1][2]["key"] = 1, 0   # FIFO violated
    (violation,) = collsan.verify_program(prog, world=2)
    assert "reordered" in violation


def test_verify_program_world_membership():
    prog = {0: [{"op": "barrier", "key": None}],
            3: [{"op": "barrier", "key": None}]}
    violations = collsan.verify_program(prog, world=2)
    assert any("rank 1 missing" in v for v in violations)
    assert any("rank 3 outside world 2" in v for v in violations)


def test_verify_program_peak_live_bytes():
    prog = {0: [{"op": "alloc", "bytes": 100},
                {"op": "alloc", "bytes": 200},
                {"op": "free", "bytes": 100},
                {"op": "alloc", "bytes": 50}]}
    assert collsan.verify_program(prog, max_live_bytes=300) == []
    (violation,) = collsan.verify_program(prog, max_live_bytes=250)
    assert "peak live bytes 300" in violation
    # per-rank bounds: an uncovered rank is unbounded
    assert collsan.verify_program(prog, max_live_bytes={1: 10}) == []
    assert collsan.verify_program(prog, max_live_bytes={0: 299}) != []


# --- pipeline schedules are verified programs ----------------------------

def test_schedules_lower_to_valid_programs():
    from ray_tpu.train.pipeline import schedule as sched
    for s, m in [(1, 1), (2, 2), (3, 4), (4, 8), (5, 5), (8, 8)]:
        for name in sched.SCHEDULES:
            sched.validate_schedule(s, m, name)  # goldens still hold
            program = sched.schedule_program(
                sched.build_schedule(s, m, name))
            assert collsan.verify_program(program, world=s) == []


def test_tampered_schedule_program_is_rejected():
    from ray_tpu.train.pipeline import schedule as sched
    program = sched.schedule_program(sched.build_schedule(3, 4, "1f1b"))
    # drop stage 1's first activation recv: the 0->1 channel unbalances
    victim = next(op for op in program[1]
                  if op["op"] == "recv" and op["chan"] == "act 0->1")
    program[1].remove(victim)
    violations = collsan.verify_program(program, world=3)
    assert any("act 0->1" in v for v in violations)


# --- live runtime wiring -------------------------------------------------

@pytest.fixture
def collsan_runtime(monkeypatch):
    """A runtime started with the sanitizer armed (env must be set
    before init so workers inherit it and the driver ledger+watchdog
    come up)."""
    monkeypatch.setenv("RAY_TPU_COLLSAN", "1")
    monkeypatch.setenv("RTPU_COLLSAN_STALL_S", "30")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4, system_config={"task_max_retries": 0})
    yield rt
    ray_tpu.shutdown()


def _sync_worker_cls(group):
    @ray_tpu.remote(num_cpus=0)
    class CsanWorker:
        def __init__(self, rank, world):
            from ray_tpu.parallel import collective
            self.rank, self.world, self.group = rank, world, group
            collective.init_collective_group(world, rank, group)

        def clean_round(self):
            from ray_tpu.parallel import collective
            x = np.ones(256, dtype=np.float32) * (self.rank + 1)
            out = collective.allreduce(x, "sum", self.group)
            collective.barrier(self.group)
            b = collective.broadcast(x * 3 if self.rank == 0 else None,
                                     0, self.group)
            return float(out[0]), float(b[0])

        def divergent_round(self):
            # rank 0 broadcasts while its peer runs a barrier: both are
            # one _exchange rendezvous, so the round completes (no
            # hang) and the mismatch is purely collsan's to report
            from ray_tpu.parallel import collective
            if self.rank == 0:
                collective.broadcast(np.ones(4, np.float32), 0,
                                     self.group)
            else:
                collective.barrier(self.group)
            return True

        def destroy(self):
            from ray_tpu.parallel import collective
            collective.destroy_collective_group(self.group)

    return CsanWorker


def _wait_for(cond, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def test_clean_run_reports_nothing(collsan_runtime):
    cls = _sync_worker_cls("csan-clean")
    workers = [cls.remote(i, 3) for i in range(3)]
    out = ray_tpu.get([w.clean_round.remote() for w in workers])
    assert {s for s, _ in out} == {6.0}     # 1+2+3, allreduced
    assert {b for _, b in out} == {3.0}     # rank 0's broadcast
    ray_tpu.get([w.destroy.remote() for w in workers])
    # worker flushers push every 0.25s; wait for every journal to land
    # IN FULL — judging expect_complete on a half-flushed rank would
    # fabricate the very missing_rank finding the fold guards against.
    # Each rank enters+exits 4 collectives (allreduce, barrier,
    # broadcast, the destroy barrier).
    _wait_for(lambda: len([ev for ev in collsan.merged_events()
                           if ev[2] == "csan-clean"]) == 3 * 2 * 4,
              10, "all worker journals, fully flushed")
    assert collsan.report(expect_complete=True) == []
    # every rank stamped the same four-op program
    cap = collsan.capture()
    ops = cap["groups"]["csan-clean"]
    assert ops["allreduce"]["count"] == 3
    assert ops["barrier"]["count"] == 6    # explicit + destroy barrier
    assert ops["broadcast"]["count"] == 3


def test_divergent_run_reports_op_mismatch(collsan_runtime):
    cls = _sync_worker_cls("csan-div")
    workers = [cls.remote(i, 2) for i in range(2)]
    assert all(ray_tpu.get([w.divergent_round.remote()
                            for w in workers]))

    def _mismatches():
        return [f for f in collsan.report()
                if f["kind"] == "op_mismatch" and f["group"] == "csan-div"]
    findings = _wait_for(_mismatches, 10, "the planted op_mismatch")
    f = findings[0]
    assert (f["seq"], f["ranks"]) == (0, [0, 1])
    assert "broadcast" in f["detail"] and "barrier" in f["detail"]


def test_shutdown_folds_final_findings(monkeypatch):
    monkeypatch.setenv("RAY_TPU_COLLSAN", "1")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, system_config={"task_max_retries": 0})
    cls = _sync_worker_cls("csan-final")
    workers = [cls.remote(i, 2) for i in range(2)]
    assert all(ray_tpu.get([w.divergent_round.remote()
                            for w in workers]))
    _wait_for(lambda: [f for f in collsan.report()
                       if f["group"] == "csan-final"], 10, "finding")
    ray_tpu.shutdown()
    # ledger and store are gone with the session; the shutdown fold
    # keeps the diagnosis available to post-mortem report() calls
    assert collsan.LEDGER is None
    final = [f for f in collsan.report() if f["group"] == "csan-final"]
    assert final and final[0]["kind"] == "op_mismatch"


# --- error-feedback residual staleness (satellite 1) ---------------------

def test_ef_buffers_are_size_keyed(ray_start_regular):
    from ray_tpu.parallel import collective
    a = collective._ef_buffer("efg", "leaf", 100)
    b = collective._ef_buffer("efg", "leaf", 50)
    assert a is not b and a.size == 100 and b.size == 50
    a[:] = 1.0
    assert collective._ef_buffer("efg", "leaf", 100) is a
    res = collective.error_feedback_residual("efg", "leaf")
    assert res is not None and res.size in (100, 50)
    res[:] = -1.0                      # a copy: the buffer is untouched
    assert float(a[0]) == 1.0
    collective.reset_error_feedback("efg")
    assert collective.error_feedback_residual("efg", "leaf") is None


def test_init_collective_group_clears_prior_residuals(ray_start_regular):
    from ray_tpu.parallel import collective
    collective._ef_buffer("efg2", "leaf", 64)[:] = 0.5
    collective._ef_buffer("other", "leaf", 64)[:] = 0.5
    collective.init_collective_group(1, 0, "efg2")
    try:
        # the skipped-destroy path: a same-named incarnation must not
        # inherit residuals, while other groups keep theirs
        assert collective.error_feedback_residual("efg2", "leaf") is None
        assert collective.error_feedback_residual("other", "leaf") \
            is not None
    finally:
        collective._groups.pop("efg2", None)
        collective.reset_error_feedback("other")


def test_recreated_group_matches_fresh_group_bitwise(ray_start_regular):
    """The regression: destroy + re-init at a different tensor size
    must start from zero residual — a stale buffer from the previous
    incarnation would bias the first compressed allreduce."""
    group = "ef-stale"

    @ray_tpu.remote(num_cpus=0)
    class EfWorker:
        def __init__(self, rank, world, name):
            from ray_tpu.parallel import collective
            self.rank, self.world, self.name = rank, world, name
            collective.init_collective_group(world, rank, name)

        def round(self, size, seed_off=0):
            from ray_tpu.parallel import collective
            rng = np.random.default_rng(self.rank + seed_off)
            g = rng.standard_normal(size).astype(np.float32)
            out = collective.allreduce(g, "sum", self.name,
                                       compression="int8",
                                       ef_key="leaf")
            return out[:8].tolist()

        def residual_nonzero(self):
            from ray_tpu.parallel import collective
            r = collective.error_feedback_residual(self.name, "leaf")
            return r is not None and bool(np.any(r != 0))

        def destroy_and_reinit(self):
            from ray_tpu.parallel import collective
            collective.destroy_collective_group(self.name)
            assert collective.error_feedback_residual(
                self.name, "leaf") is None
            collective.init_collective_group(self.world, self.rank,
                                             self.name)
            return True

    workers = [EfWorker.remote(i, 2, group) for i in range(2)]
    ray_tpu.get([w.round.remote(4097) for w in workers])
    # the first incarnation left real error-feedback state behind
    assert any(ray_tpu.get([w.residual_nonzero.remote()
                            for w in workers]))
    assert all(ray_tpu.get([w.destroy_and_reinit.remote()
                            for w in workers]))
    recreated = ray_tpu.get([w.round.remote(2048) for w in workers])

    control = [EfWorker.remote(i, 2, "ef-ctl") for i in range(2)]
    fresh = ray_tpu.get([w.round.remote(2048) for w in control])
    # same grads, zero starting residual on both sides -> the
    # deterministic quantizer must produce bitwise-equal results
    assert recreated == fresh


# --- overhead guards (satellite 5) ---------------------------------------

def test_disabled_hot_path_overhead_guard(ray_start_regular):
    """A/B of the world-1 allreduce stamp path (a stamp is ~2us);
    mirrors ``perf.py --collsan`` and the BENCH_core.json acceptance
    bound (enabled/disabled < 2.0)."""
    from ray_tpu.parallel import collective
    collective.init_collective_group(1, 0, "csan-ovh")
    x = np.ones(65536, dtype=np.float32)

    def allreduces(n=300):
        t0 = time.perf_counter()
        for _ in range(n):
            collective.allreduce(x, "sum", "csan-ovh")
        return time.perf_counter() - t0

    try:
        saved = collsan.LEDGER
        allreduces(50)
        hostratio.judge_switched(
            "collsan on / off", 2.0, collsan.disable,
            lambda: collsan.enable("test:ovh"),  # fresh, empty ledger
            timed=allreduces, rounds=5)
    finally:
        collsan.LEDGER = saved
        collective._groups.pop("csan-ovh", None)


def test_bench_core_has_collsan_overhead_row():
    with open(os.path.join(REPO_ROOT, "BENCH_core.json")) as f:
        rows = json.load(f)
    row = next(r for r in rows if r.get("bench") == "collsan_overhead")
    assert row["enabled_over_disabled"] < 2.0
    assert row["seconds_disabled"] > 0
