"""refsan: the distributed object-lifetime sanitizer (PR 14).

Covers the fold's finding classes on synthetic event streams, the two
historical-bug regressions (the PR-11 early-release class via the
eviction canary, the PR-13 release-before-grace class via the ledger),
the hostile-eviction stress staying clean on fixed code, and the
overhead ratio guard for the disabled hot path.
"""

import time

import numpy as np
import pytest

import hostratio
from ray_tpu.devtools import refsan


@pytest.fixture
def fresh_refsan():
    """Isolate the module-level ledger/collector state per test."""
    saved = (refsan.LEDGER, refsan._STORE, refsan._final_findings)
    refsan._STORE = refsan._RefsanStore()
    refsan._final_findings = None
    yield
    (refsan.LEDGER, refsan._STORE, refsan._final_findings) = saved


def _ev(seq, oid, holder, kind, extra=None):
    return (seq, oid, holder, kind, 0, extra)


# --- fold semantics on synthetic streams -------------------------------

def test_fold_negative_count(fresh_refsan):
    oid = "aa" * 8
    # a double-drop: add, drop-to-zero, drop again on the gone count
    events = [
        _ev(0, oid, "t", refsan.KIND_REF_ADD,
            {"count": 1, "role": "owner"}),
        _ev(1, oid, "t", refsan.KIND_REF_DROP,
            {"count": 0, "role": "owner"}),
        _ev(2, oid, "t", refsan.KIND_REF_DROP_MISSING,
            {"count": -1, "role": "owner"}),
    ]
    [f] = refsan.fold(events)
    assert f["kind"] == "negative_count" and f["oid"] == oid
    # a drop with NO witnessed add is a cross-epoch artifact (a ref
    # surviving a runtime restart dropping into the fresh counter) and
    # must stay quiet
    assert refsan.fold([
        _ev(0, oid, "t", refsan.KIND_REF_DROP_MISSING,
            {"count": -1, "role": "owner"})]) == []


def test_fold_double_release_and_balanced_quiet(fresh_refsan):
    oid = "bb" * 8
    # balanced pin/release: quiet
    assert refsan.fold([
        _ev(0, oid, "t", refsan.KIND_SLOT_PIN, {"store": "s"}),
        _ev(1, oid, "t", refsan.KIND_SLOT_RELEASE, {"store": "s"}),
    ]) == []
    # an extra release with nothing outstanding: double_release
    [f] = refsan.fold([
        _ev(0, oid, "t", refsan.KIND_SLOT_PIN, {"store": "s"}),
        _ev(1, oid, "t", refsan.KIND_SLOT_RELEASE, {"store": "s"}),
        _ev(2, oid, "t", refsan.KIND_SLOT_RELEASE, {"store": "s"}),
    ])
    assert f["kind"] == "double_release"


def test_fold_grace_violation_orders_by_seq(fresh_refsan):
    oid = "cc" * 8
    deleted = _ev(5, oid, "t", refsan.KIND_DELETED)
    borrow = _ev(7, oid, "t", refsan.KIND_REF_ADD,
                 {"count": 1, "role": "owner"})
    # borrow lands AFTER the reclaim → violation (fed out of order to
    # prove the fold re-sorts per holder on seq)
    [f] = refsan.fold([borrow, deleted])
    assert f["kind"] == "grace_violation"
    # borrow BEFORE the reclaim is the legal order → quiet
    early = _ev(3, oid, "t", refsan.KIND_REF_ADD,
                {"count": 1, "role": "owner"})
    assert refsan.fold([deleted, early]) == []
    # non-owner roles never judge grace (workers see local drops only)
    late_borrower = _ev(9, oid, "t", refsan.KIND_REF_ADD,
                        {"count": 1, "role": "borrower"})
    assert refsan.fold([deleted, late_borrower]) == []


def test_fold_leaked_pin_scoped_to_local_holder(fresh_refsan):
    oid = "dd" * 8
    pin = _ev(0, oid, "local", refsan.KIND_SLOT_PIN, {"store": "s"})
    # a live view backs the pin → quiet
    assert refsan.fold([pin], live_views={oid: 1},
                       local_label="local") == []
    # no view backing it → leak
    [f] = refsan.fold([pin], live_views={}, local_label="local")
    assert f["kind"] == "leaked_pin"
    # same stream from a REMOTE holder: never judged (its journal may
    # be truncated by a worker death)
    remote = _ev(0, oid, "worker:x", refsan.KIND_SLOT_PIN, {"store": "s"})
    assert refsan.fold([remote], live_views={},
                       local_label="local") == []


def test_store_push_dedups_on_seq(fresh_refsan):
    refsan.store_push("w:a", [_ev(0, "aa", "w:a", "ref_add"),
                              _ev(1, "aa", "w:a", "ref_drop")])
    refsan.store_push("w:a", [_ev(1, "aa", "w:a", "ref_drop"),
                              _ev(2, "aa", "w:a", "ref_zero")])
    [(label, events)] = refsan.get_store().journals().items()
    assert label == "w:a" and [e[0] for e in events] == [0, 1, 2]


# --- historical regression: PR-11 early-release (eviction canary) ------

@pytest.mark.watchdog(180)
def test_canary_catches_pr11_early_release(ray_start_regular):
    """The pre-PR-11 bug class: ``unpack_pinned`` views whose pins are
    released while the deserialized value is still alive. With the
    fixture flag on, deleting the ref poisons the arena range and the
    live view must read the canary — deterministically, not whenever
    the arena happens to reuse the block."""
    import ray_tpu
    from ray_tpu.core import serialization

    led = refsan.enable(label="driver:test", canary=True)
    serialization._FIXTURE_EARLY_RELEASE = True
    try:
        ref = ray_tpu.put(np.arange(300_000, dtype=np.int64))
        value = ray_tpu.get(ref)
        assert value[0] == 0
        del ref            # driver drop → store delete → canary poison
        time.sleep(0.1)
        # the delete path verifies views at poison time — the hit is
        # already in the ledger, stamped with the culprit view's stack
        kinds = [e[3] for e in led.snapshot()]
        assert refsan.KIND_CANARY_HIT in kinds, kinds
        findings = refsan.report()
        kinds = {f["kind"] for f in findings}
        assert "use_after_release" in kinds, findings
        # the poison is really under the live value: 8 canary bytes
        # reinterpreted as int64
        poisoned = int(np.int64(
            int.from_bytes(bytes([refsan.POISON_BYTE]) * 8,
                           "little", signed=True)))
        assert int(value[0]) == poisoned
    finally:
        serialization._FIXTURE_EARLY_RELEASE = False
        refsan.disable()
        refsan._final_findings = None


@pytest.mark.watchdog(180)
def test_canary_quiet_on_fixed_release_path(ray_start_regular):
    """Same sequence on the FIXED code path (finalizers tie the pin to
    the value): the view holds the slot, the delete defers, no canary."""
    import ray_tpu

    led = refsan.enable(label="driver:test", canary=True)
    try:
        ref = ray_tpu.put(np.arange(300_000, dtype=np.int64))
        value = ray_tpu.get(ref)
        del ref
        time.sleep(0.1)
        assert led.verify_views() == 0
        assert value[0] == 0 and value[-1] == 299_999
        assert [f for f in refsan.report()
                if f["kind"] == "use_after_release"] == []
    finally:
        refsan.disable()
        refsan._final_findings = None


# --- historical regression: PR-13 release-before-grace -----------------

@pytest.fixture
def hostile_runtime():
    import ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=2, system_config={
        "task_max_retries": 0,
        "refsan_hostile_eviction": True,
    })
    yield rt
    ray_tpu.shutdown()


@pytest.mark.watchdog(180)
def test_ledger_catches_pr13_grace_violation(hostile_runtime):
    """The PR-13 Sebulba class: the owner reclaims a deferred-dropped
    object while a borrow is still in flight. Under the hostile grace
    window (~0) the reclaim races ahead; the late borrow registration
    must fold into a grace_violation."""
    import ray_tpu

    rt = hostile_runtime
    refsan.enable(label="driver:test")
    try:
        ref = ray_tpu.put(b"y" * 4096)
        oid = ref.id
        ref._registered = False   # hand-manage the count from here
        del ref
        rt.deferred_remove_reference(oid)   # drop with the grace defer
        time.sleep(1.2)                     # expiry thread reclaims
        # the "in-flight borrow" lands after the reclaim
        rt.reference_counter.add_local_reference(oid)
        findings = refsan.report()
        assert "grace_violation" in {f["kind"] for f in findings}, findings
        rt.reference_counter.remove_local_reference(oid)
    finally:
        refsan.disable()
        refsan._final_findings = None


@pytest.mark.watchdog(180)
def test_ledger_quiet_when_borrow_lands_within_grace(hostile_runtime):
    """The fixed ordering: the borrow registers before the deferred
    reclaim fires, so the re-check at expiry skips the delete
    (reclaim_skip) and no violation is reported."""
    import ray_tpu

    rt = hostile_runtime
    refsan.enable(label="driver:test")
    try:
        ref = ray_tpu.put(b"z" * 4096)
        oid = ref.id
        ref._registered = False
        del ref
        rt.deferred_remove_reference(oid)
        rt.reference_counter.add_local_reference(oid)   # within grace
        time.sleep(1.2)
        assert [f for f in refsan.report()
                if f["kind"] == "grace_violation"] == [], refsan.report()
        # the value must still be there: the re-borrow kept it alive
        assert ray_tpu.get(
            __import__("ray_tpu.core.object_ref", fromlist=["ObjectRef"])
            .ObjectRef(oid)) == b"z" * 4096
    finally:
        refsan.disable()
        refsan._final_findings = None


# --- hostile-eviction stress on fixed code -----------------------------

@pytest.mark.watchdog(300)
def test_hostile_eviction_stress_stays_clean(hostile_runtime):
    """Fixed code under the nastiest store: grace ~0, canaries on, a
    churn of puts/gets/tasks re-borrowing each other's results. Zero
    ledger findings."""
    import ray_tpu

    refsan.enable(label="driver:test", canary=True)
    try:
        @ray_tpu.remote(num_cpus=0)
        def double(x):
            return x * 2

        keepalive = []
        for round_idx in range(6):
            blob = ray_tpu.put(
                np.full(4096, round_idx, dtype=np.float64))
            out = ray_tpu.get(double.remote(blob))
            assert float(out[0]) == 2.0 * round_idx
            keepalive.append(out)          # views stay live across churn
            del blob                        # store churn under the views
        assert refsan.LEDGER.verify_views() == 0
        for i, arr in enumerate(keepalive):  # nothing corrupted
            assert float(arr[0]) == 2.0 * i
        assert refsan.report() == []
    finally:
        refsan.disable()
        refsan._final_findings = None


# --- overhead guard (disabled hot path is two loads + a compare) -------

@pytest.mark.watchdog(300)
def test_refsan_overhead_ratio_guard(ray_start_regular):
    """Ledger-enabled vs disabled wall time on a tight task loop must
    stay under a generous ratio bound: the real cost is one tuple
    append per lifetime transition."""
    saved = refsan.LEDGER
    try:
        hostratio.judge_switched(
            "refsan on / off", 2.0, refsan.disable,
            lambda: refsan.enable("driver:overhead", canary=False))
    finally:
        refsan.LEDGER = saved
