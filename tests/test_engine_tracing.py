"""The served path's own clock: request stages and step host time as
engine counters, step phases as spans on the profiler's clock and in
the flight recorder, and no control-plane call on the stepper thread.
"""

import collections
import gc
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import hostratio
import ray_tpu
from ray_tpu import serve
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import (
    ContinuousBatchingEngine, EngineConfig, GenerationRequest)
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.proxy import RECEIVED_KEY
from ray_tpu.util import flight_recorder
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util.metrics import prometheus_text, remove_series

STAGES = "ray_tpu_engine_request_stage_seconds"
TTFT = "ray_tpu_engine_ttft_seconds"
STEP = "ray_tpu_engine_step_seconds"
STEP_HOST = "ray_tpu_engine_step_host_seconds"
STEP_UPLOAD = "ray_tpu_engine_step_upload_seconds"
STATE_UPLOADS = "ray_tpu_engine_state_uploads_total"


def _engine_config(max_batch=2, **kw):
    return EngineConfig(
        model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                               attention="reference", remat=False),
        max_batch=max_batch, max_seq=64, **kw)


def _hist(name, **tags):
    """(sum, count) of one histogram series in this process's registry."""
    snap = metrics_mod.histogram_snapshot(name, tags)
    return (0.0, 0) if snap is None else (snap[2], snap[3])


def _own_series(engine):
    """{(series name, phase): [sum, count]} of what THIS engine's buffer
    is given from now on. The process's registry is shared: an engine
    of an earlier test (of any file this worker ran) may still flush
    into the same series, so an equality is read here and the registry
    is held to ``>=``."""
    own = collections.defaultdict(lambda: [0.0, 0])
    buffer = engine._mbuf

    def spy(record):
        def recorded(metric, value=1.0, tags=None):
            entry = own[metric._name, (tags or {}).get("phase")]
            entry[0] += value
            entry[1] += 1
            return record(metric, value, tags)
        return recorded

    buffer.inc, buffer.observe = spy(buffer.inc), spy(buffer.observe)
    return own


def _hist_lines(name, label, renamed):
    """The exposition lines of the series carrying ``label``, with the
    label rewritten so that two series can be compared line by line."""
    return [line.replace(label, renamed)
            for line in prometheus_text().splitlines()
            if line.startswith(name) and label in line]


# -- (a) nothing on the stepper thread reaches the control plane ---------

def test_stepper_thread_makes_no_control_plane_call(monkeypatch):
    from ray_tpu.core import worker as worker_mod
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    callers = []   # (what, thread ident, thread name)

    def noting(what, real):
        def wrapper(*args, **kwargs):
            thread = threading.current_thread()
            callers.append((what, thread.ident, thread.name))
            return real(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(metrics_mod, "record_batch",
                        noting("record_batch", metrics_mod.record_batch))
    monkeypatch.setattr(metrics_mod, "_record",
                        noting("_record", metrics_mod._record))
    for method in ("request", "gcs_call"):
        monkeypatch.setattr(
            worker_mod.WorkerRuntime, method,
            noting(method, getattr(worker_mod.WorkerRuntime, method)))

    server = LLMServer(LLMConfig(model_id="tiny", engine=_engine_config()))
    server.engine._mbuf.flush_interval_s = 0.05
    try:
        stepper = server._stepper.ident
        steps_before = server.engine._steps
        # six requests through two slots: some tens of steps, with
        # admissions (first tokens) in between
        outs = []
        pool = [threading.Thread(target=lambda i=i: outs.append(server({
            "__path__": "/v1/completions", "prompt": "hello %d" % i,
            "max_tokens": 12, RECEIVED_KEY: time.time()})))
            for i in range(6)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(120)
        assert len(outs) == 6 and all("choices" in o for o in outs)
        assert server.engine._steps - steps_before >= 30
        deadline = time.monotonic() + 10
        while not any(name == "engine-metrics-flush"
                      for _, _, name in callers):
            assert time.monotonic() < deadline, callers
            time.sleep(0.02)
        server({"__path__": "/v1/stats"})   # flushes on this thread
    finally:
        server.stop()
    assert callers
    assert stepper not in {ident for _, ident, _ in callers}, callers
    assert ("record_batch", threading.get_ident(),
            threading.current_thread().name) in callers
    # the flush thread ended with stop()
    assert not server.engine._mbuf._thread.is_alive()
    # all four stages of every request were recorded
    text = prometheus_text()
    for stage in ("dispatch", "prepare", "queue", "prefill"):
        assert f'{STAGES}_count{{stage="{stage}"}}' in text


def test_flush_thread_ends_with_its_engine():
    engine = ContinuousBatchingEngine(_engine_config())
    engine._mbuf.flush_interval_s = 0.05
    engine.generate([[1, 2, 3]], max_tokens=2)
    thread = engine._mbuf._thread
    assert thread is not None and thread.is_alive()
    del engine
    gc.collect()
    thread.join(5)
    assert not thread.is_alive()


# -- (b) a pre-bucketed histogram merges to what observe() gives ---------

SAMPLES = [0.0, 0.125, 0.25, 0.25, 0.5, 0.75, 1.0, 1.0, 3.5, 64.0]
BOUNDS = [0.25, 1.0, 4.0]


def _observe_both_ways(series):
    from ray_tpu.util import metrics
    hist = metrics.Histogram(series, "merge test", boundaries=BOUNDS,
                             tag_keys=("how",))
    buf = metrics.LocalBuffer()
    for v in SAMPLES[:6]:
        hist.observe(v, {"how": "each"})
        buf.observe(hist, v, {"how": "merged"})
    buf.flush()
    for v in SAMPLES[6:]:        # a second flush merges into the first
        hist.observe(v, {"how": "each"})
        buf.observe(hist, v, {"how": "merged"})
    assert len(buf.flush()) == 1
    assert buf.flush() == []
    return True


@pytest.mark.parametrize("where", ["driver", "worker"])
def test_merged_histogram_equals_observed(ray_start_regular, where):
    series = f"ray_tpu_test_merge_{where}_seconds"
    if where == "driver":
        _observe_both_ways(series)
    else:
        assert ray_tpu.get(
            ray_tpu.remote(_observe_both_ways).remote(series))
    try:
        each = _hist_lines(series, 'how="each"', "how=X")
        merged = _hist_lines(series, 'how="merged"', "how=X")
        assert len(each) == len(BOUNDS) + 3
        assert each == merged
        assert f"{series}_count{{how=X}} {len(SAMPLES)}" in each
        assert f"{series}_sum{{how=X}} {sum(SAMPLES)}" in each
    finally:
        for how in ("each", "merged"):
            remove_series(series, {"how": how})


def test_merged_histogram_needs_the_same_boundaries():
    series = "ray_tpu_test_merge_bounds_seconds"
    metrics_mod.record_batch([
        ("histogram", series, {}, 0.5, [1.0, 2.0])])
    try:
        with pytest.raises(ValueError):
            metrics_mod.record_batch([
                ("histogram_counts", series, {}, ([1, 0], 0.5, 1),
                 [1.0])])
    finally:
        remove_series(series, {})


# -- (c) queue + prefill = engine TTFT; host time inside step time -------

def test_request_stages_add_up_and_host_time_is_inside_step_time():
    engine = ContinuousBatchingEngine(_engine_config())
    engine.flush_metrics()
    before = {key: _hist(*key[:1], **dict(key[1])) for key in (
        (TTFT, ()), (STAGES, (("stage", "queue"),)),
        (STAGES, (("stage", "prefill"),)),
        (STEP, (("phase", "decode"),)), (STEP, (("phase", "prefill"),)),
        (STEP_HOST, (("phase", "decode"),)),
        (STEP_HOST, (("phase", "prefill"),)))}

    def window(name, **tags):
        total, count = _hist(name, **tags)
        was = before[(name, tuple(tags.items()))]
        return total - was[0], count - was[1]

    # five requests through two slots: three of them wait in the queue
    requests = [engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3, i], max_tokens=4)) for i in range(5)]
    while any(not r.done for r in requests):
        engine.step()
    engine.flush_metrics()
    for r in requests:
        assert r.t_submit <= r.t_admit <= r.t_first_token
        ttft = r.t_first_token - r.t_submit
        assert abs((r.t_admit - r.t_submit)
                   + (r.t_first_token - r.t_admit) - ttft) < 1e-3
    assert max(r.t_admit - r.t_submit for r in requests) > \
        min(r.t_first_token - r.t_admit for r in requests)
    ttft_sum, ttft_n = window(TTFT)
    queue_sum, queue_n = window(STAGES, stage="queue")
    prefill_sum, prefill_n = window(STAGES, stage="prefill")
    assert ttft_n == queue_n == prefill_n == len(requests)
    assert abs(queue_sum + prefill_sum - ttft_sum) < 1e-3 * len(requests)
    assert abs(ttft_sum - sum(r.t_first_token - r.t_submit
                              for r in requests)) < 1e-6
    steps = 0
    for phase in ("decode", "prefill"):
        step_sum, step_n = window(STEP, phase=phase)
        host_sum, host_n = window(STEP_HOST, phase=phase)
        assert host_n == step_n
        assert 0.0 <= host_sum <= step_sum
        steps += step_n
    assert steps == engine._steps


# -- (c2) how often the dense step's state stays on the device ------------

def _counter(name, **tags):
    """One counter series of this process's registry, 0.0 if absent."""
    labels = ",".join(f'{k}="{v}"' for k, v in sorted(tags.items()))
    series = f"{name}{{{labels}}} " if labels else name + " "
    for line in prometheus_text().splitlines():
        if line.startswith(series):
            return float(line.split()[-1])
    return 0.0


def _in_order(engine):
    """The dense step as it was until PR 67 (read back before the next
    is launched), through the stepper's own predicate."""
    engine._may_launch_ahead = lambda requests: False
    return engine


@pytest.mark.parametrize("ahead", [False, True])
def test_state_is_sent_once_for_each_change_of_the_slots(ahead):
    """Decode steps with no admission and no ending send nothing. In
    order, an admission, an ending, a cancel and a fail_all each cost
    exactly one state at the next dense step (a cancel is seen by the
    stepper one step after it is made: that step still takes the
    device's state). Launched ahead, the slots change hands on the
    device and a state is sent only where nothing is in flight: the
    first step, and the first after fail_all. ``stats()`` and the two
    series say the same."""
    engine = ContinuousBatchingEngine(_engine_config(max_batch=4))
    if not ahead:
        _in_order(engine)
    engine.flush_metrics()
    uploads0 = _counter(STATE_UPLOADS)
    upload_hist0 = {phase: _hist(STEP_UPLOAD, phase=phase)
                    for phase in ("decode", "prefill")}
    sent = iter([1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 1, 0] if ahead
                else [1, 0, 1, 0, 0, 1, 0, 0, 1, 0, 0, 1, 0])

    def steps(n):
        """n steps; whether they sent the states expected of them."""
        was = engine.state_uploads
        for _ in range(n):
            engine.step()
        return engine.state_uploads - was == next(sent)

    def add(max_tokens):
        return engine.add_request(GenerationRequest(
            prompt_ids=[1, 2, 3, max_tokens], max_tokens=max_tokens))

    long_a, long_b = add(60), add(60)
    assert steps(1)               # two admissions, one state
    assert steps(6)               # k steps, nothing changed hands
    short = add(4)
    assert steps(1)               # an admission
    assert steps(1)
    # its fourth token, an ending; admitted behind a step in flight it
    # joined the step after
    assert steps(1) and short.done != ahead
    assert steps(1) and short.done
    assert steps(3)
    canceller = threading.Thread(target=engine.cancel, args=(long_a,))
    canceller.start()
    canceller.join()
    assert steps(1)               # the stepper finds it cancelled
    assert steps(1)
    assert steps(3) and not long_b.done
    engine.fail_all("test")
    assert long_b.done and steps(1) and engine._state is None
    again = add(8)
    assert steps(1)               # fail_all and the admission: one state
    assert steps(5) and not again.done
    stats = engine.stats()
    n_sent = 2 if ahead else 5
    assert stats["state_uploads"] == engine.state_uploads == n_sent
    # launched ahead, a call leaves one more step on the device: one
    # when fail_all dropped what was in flight, one at the end
    assert stats["decode_steps"] == engine.decode_steps == 25 + 2 * ahead
    # in order also where the host had read everything: the first step,
    # the step behind ``short``'s admission, the first after fail_all
    assert stats["decode_launches"] == (
        {"ahead": 24, "in_order": 3} if ahead
        else {"ahead": 0, "in_order": 25})
    assert _counter(STATE_UPLOADS) - uploads0 == n_sent
    # the upload time is observed once a step, 0.0 where nothing was
    # sent, so its count is the step count and its sum is the time of
    # the few steps that sent
    observed = 0
    for phase in ("decode", "prefill"):
        total, count = _hist(STEP_UPLOAD, phase=phase)
        # prefill: three admitting steps sent prompts; decode, in
        # order: the two steps after an ending sent a state
        assert total - upload_hist0[phase][0] > 0.0 or (
            ahead and phase == "decode")
        observed += count - upload_hist0[phase][1]
    assert observed == engine._steps == 26


# -- (d) one step's spans in the flight recorder -------------------------

@pytest.fixture
def recorder():
    was = flight_recorder.RECORDER
    rec = flight_recorder.enable(label="test", capacity=4096)
    yield rec
    flight_recorder.RECORDER = was


def test_step_spans_lie_inside_their_step_and_carry_its_number(recorder):
    # the spans of a step in order; those of one launched ahead: (g)
    engine = _in_order(ContinuousBatchingEngine(_engine_config()))
    requests = [engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3, i], max_tokens=3,
        logit_bias={7: -100.0} if i == 0 else None)) for i in range(3)]
    while any(not r.done for r in requests):
        engine.step()
    events = [ev for ev in recorder.snapshot() if ev[3] == "serve"]
    steps = {ev[5]["step"]: ev for ev in events if ev[4] == "engine.step"}
    assert sorted(steps) == list(range(1, engine._steps + 1))
    assert sum(ev[5]["tokens"] for ev in steps.values()) == 9
    assert all(ev[5]["slots"] >= 1 for ev in steps.values())
    # a loop's wait between steps is a span beside them, in no step
    with engine.idling():
        pass
    assert [ev[4] for ev in recorder.snapshot()][-1] == "engine.wait"
    children = [ev for ev in events if ev[4].startswith("engine.")
                and ev[4] != "engine.step"]
    names = {ev[4] for ev in children}
    assert names == {"engine.prefill", "engine.bias", "engine.gather",
                     "engine.upload", "engine.launch", "engine.insert",
                     "engine.readback", "engine.emit"}
    for _seq, t0, dur, _cat, name, args in children:
        _, p0, pdur, _, _, _ = steps[args["step"]]
        assert p0 <= t0 and t0 + dur <= p0 + pdur, (name, args)
    # a step that sends a state gathers and uploads it; one that takes
    # the state the step before it left on the device opens neither
    # span (here: every step that admitted nothing, as each follows an
    # admission and nothing ends before the last)
    sent = {n: {ev[4] for ev in children if ev[5]["step"] == n}
            for n in steps}
    assert engine.state_uploads == 2 and engine.decode_steps == 4
    quiet = [n for n, ev in steps.items() if ev[5]["phase"] == "decode"]
    assert len(quiet) == 2
    for n, opened in sent.items():
        if n in quiet:
            assert opened == {"engine.launch", "engine.readback",
                              "engine.emit"}, (n, opened)
        else:
            assert {"engine.gather", "engine.upload"} <= opened
    # a prefill span names the request it served, and the phases of
    # that prefill lie inside it
    prefills = [ev for ev in children if ev[4] == "engine.prefill"]
    assert sorted(ev[5]["req"] for ev in prefills) == \
        sorted(r.request_id for r in requests)
    for ev in prefills:
        assert ev[5]["prompt_len"] == 4 and ev[5]["bucket"] == 4
    first = next(ev for ev in prefills
                 if ev[5]["req"] == requests[0].request_id)
    inside = [ev[4] for ev in children
              if ev is not first and first[1] <= ev[1]
              and ev[1] + ev[2] <= first[1] + first[2]]
    # the only biased request: its row is built and sent once, behind
    # the launch of its prefill (the first launch of its span)
    assert inside.count("engine.bias") == 1
    assert {"engine.upload", "engine.launch", "engine.readback",
            "engine.emit"} <= set(inside)
    assert sum(ev[4] == "engine.bias" for ev in children) == 1
    bias = next(ev for ev in children if ev[4] == "engine.bias")
    launch = min(ev[1] for ev in children if ev[4] == "engine.launch"
                 and ev[1] >= first[1])
    assert launch < bias[1]
    # the wait in the queue, per request, ends where its prefill starts
    queued = {ev[5]["req"]: ev for ev in events
              if ev[4] == "request_queue"}
    assert sorted(queued) == sorted(r.request_id for r in requests)
    for ev in prefills:
        q = queued[ev[5]["req"]]
        assert q[5]["step"] == ev[5]["step"]
        assert abs(q[1] + q[2] - ev[1]) < 5e6   # ns


# -- (g) a step launched ahead: its spans, its hold, its counters ---------

LAUNCHES = "ray_tpu_engine_decode_launches_total"
DISCARDED = "ray_tpu_engine_discarded_tokens_total"


def _held(engine, seconds):
    """Steps that the stepper believes to take ``seconds`` on the device
    and no time to launch: its hold lasts about that long. (The belief
    is the LONGEST of its last launches' leads, so those it has seen go:
    one that met a pause of the box, or an admission's, would leave no
    hold at all.)"""
    engine._step_device_s.extend([seconds] * 8)
    engine._launch_lead_s.clear()
    engine._launch_lead_s.append(0.0)


def _engine_spans(recorder, step=None):
    spans = sorted((ev for ev in recorder.snapshot() if ev[3] == "serve"
                    and ev[4].startswith("engine.")), key=lambda ev: ev[1])
    return [ev for ev in spans if ev[4] != "engine.step"
            and (step is None or ev[5]["step"] == step)]


def test_an_arrival_during_the_hold_goes_behind_the_one_step_that_runs(
        recorder):
    """With decode N+1 launched, the stepper holds. A request that
    arrives then is admitted at once: its prefill, its first token's
    sampling, its insert and its seat are queued behind N+1, where
    today's order would have put them, and decode N+2 is launched behind
    them with the request live in it. Nobody arriving, the hold ends at
    its deadline and the next plain step is launched."""
    engine = ContinuousBatchingEngine(_engine_config(max_batch=3))
    first = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3, 4], max_tokens=60))
    for _ in range(5):
        engine.step()
    _held(engine, 0.05)
    late = GenerationRequest(prompt_ids=[4, 3, 2, 1], max_tokens=8)
    wait = engine._arrived.wait

    def someone_arrives(timeout):
        if late.t_submit is None:
            engine.add_request(late)
        return wait(timeout)

    engine._arrived.wait = someone_arrives
    in_flight = engine.decode_steps            # N+1, launched by step 5
    engine.step()
    spans = _engine_spans(recorder)
    decodes = {ev[5]["decode"]: ev for ev in spans
               if ev[4] == "engine.launch" and "decode" in ev[5]}
    (hold,) = [ev for ev in spans if ev[4] == "engine.hold"
               and ev[5]["step"] == engine._steps]
    (prefill,) = [ev for ev in spans if ev[4] == "engine.prefill"
                  and ev[5]["req"] == late.request_id]
    assert engine.decode_steps == in_flight + 1
    assert (decodes[in_flight][1] < hold[1] < prefill[1]
            < decodes[in_flight + 1][1])
    # the hold ended on the arrival, well before its deadline
    assert hold[2] < 0.04e9
    # it fits before N+1 ends, so everything of the admission, the seat
    # last, is queued before the host waits at all; then N+1 is read
    # when it comes, the first token after it (and a look for who else
    # arrived, as in order), and N+2 is launched last, from the state
    # on the device
    mine = _engine_spans(recorder, step=engine._steps)
    names = [ev[4] for ev in mine]
    read = names.index("engine.readback")
    assert names[1:read].count("engine.launch") == 4   # prefill, sampling,
    assert names[read - 1] == "engine.launch"          # hand-over, seat
    assert mine[-1] is decodes[in_flight + 1]
    assert names[read:] == ["engine.readback", "engine.emit",
                            "engine.readback", "engine.emit",
                            "engine.launch"]
    assert engine.state_uploads == 1
    assert len(late.output_ids) == 1 and late.t_first_token is not None
    rows = {slot.index for slot, _ in engine._flight.rows}
    assert len(rows) == 2                      # it is live in N+2
    engine.step()
    assert len(late.output_ids) == 2
    # nobody arrives: the hold runs to its deadline, then a plain step
    # (believed anew each time: the admission's launches were the
    # host's longest, and the hold reckons with the longest)
    engine._arrived.wait = wait
    for _ in range(2):
        _held(engine, 0.05)
        engine.step()
        mine = [ev[4] for ev in _engine_spans(recorder, step=engine._steps)]
        assert mine == ["engine.hold", "engine.launch", "engine.readback",
                        "engine.emit"]
    hold = next(ev for ev in _engine_spans(recorder, step=engine._steps)
                if ev[4] == "engine.hold")
    assert 0.02e9 < hold[2] < 0.2e9
    assert first.output_ids and engine.state_uploads == 1


def test_the_hold_is_blocked_time_and_the_launches_are_counted():
    """The account still closes with ``engine.hold`` in it: the nine
    wall series sum to the stepper's life, the hold under ``blocked``,
    so a step's host time (its wall less ``blocked``) leaves the hold
    out. The two counters of the order reach ``stats()`` and their
    series. How long the holds last is this box's to say as much as
    the engine's (a stepper kept off its CPU for 30 ms reckons with a
    launch that slow and holds for nothing until eight quicker ones
    have passed), so the three bounds on seconds are judged as
    ``hostratio`` judges every guard of a host timing: over its limit
    in each of three runs."""
    hostratio.judge(_a_run_with_holds)


def _a_run_with_holds():
    """One run, every exact statement asserted; -> the bounds on wall
    time as (what, ratio, limit)."""
    from ray_tpu.llm.engine import STEPPER_PHASES
    engine = ContinuousBatchingEngine(_engine_config(max_batch=3))
    own = _own_series(engine)
    engine.flush_metrics()
    ahead0, in_order0 = (_counter(LAUNCHES, order=o)
                         for o in ("ahead", "in_order"))
    discarded0 = _counter(DISCARDED)
    host0 = _hist(STEP_HOST, phase="decode")
    step0 = _hist(STEP, phase="decode")
    account = engine._account
    account.bind()
    born = account.t
    learn = engine.add_request(GenerationRequest(
        prompt_ids=[5, 6, 7, 8, 9], max_tokens=12))
    while engine.has_work():
        engine.step()
    assert learn.output_ids.index(learn.output_ids[4]) == 4
    stops = engine.add_request(GenerationRequest(
        prompt_ids=[5, 6, 7, 8, 9], max_tokens=12,
        stop_ids=(learn.output_ids[4],)))
    plain = engine.add_request(GenerationRequest(
        prompt_ids=[4, 3, 2, 1], max_tokens=30))
    engine.step()
    _held(engine, 0.03)
    blocked0 = account.wall[STEPPER_PHASES.index("blocked")]
    while engine.has_work():
        engine.step()
    assert stops.finish_reason == "stop" and plain.finish_reason == "length"
    wall = dict(zip(STEPPER_PHASES, account.wall))
    assert abs(sum(account.wall) - (account.t - born)) < 1e-9
    stats = engine.stats()
    launches = stats["decode_launches"]
    assert launches["in_order"] == 2 and launches["ahead"] >= 35
    assert sum(launches.values()) == stats["decode_steps"]
    assert stats["discarded_tokens"] == 1
    # (another test's engine may still flush into the same series)
    assert _counter(LAUNCHES, order="ahead") - ahead0 >= launches["ahead"]
    assert _counter(LAUNCHES, order="in_order") - in_order0 >= 2
    assert _counter(DISCARDED) - discarded0 >= 1
    assert abs(sum(stats["stepper_seconds"]["wall"].values())
               - (stats["stepper_read_at"] - born)) < 0.05
    # every step fed both histograms, this engine's own and, with
    # whatever else flushed meanwhile, the process's
    steps, hosts = own[STEP, "decode"], own[STEP_HOST, "decode"]
    assert steps[1] == hosts[1] >= 35
    assert _hist(STEP, phase="decode")[1] - step0[1] >= steps[1]
    assert _hist(STEP_HOST, phase="decode")[1] - host0[1] >= hosts[1]
    assert _hist(STEP, phase="decode")[0] - step0[0] >= steps[0] - 1e-9
    engine.close()
    tiny = 1e-9
    return [
        # some twenty-five holds, the first of 30 ms (a tiny step has
        # ended long before such a hold does, and the stepper reckons
        # with a shorter one each time): over 0.15 s of them
        ("0.15 s / the holds' blocked seconds",
         0.15 / max(wall["blocked"] - blocked0, tiny), 1.0),
        # the steps' wall time holds the holds, their host time does not
        ("0.15 s / the steps' seconds", 0.15 / max(steps[0], tiny), 1.0),
        ("the steps' host seconds / half their seconds",
         hosts[0] / max(0.5 * steps[0], tiny), 1.0)]


# -- (d2) the stepper's account of its own time ---------------------------

STEPPER = "ray_tpu_engine_stepper_seconds_total"
STEPPER_CPU = "ray_tpu_engine_stepper_cpu_seconds_total"
STEPPER_CPU_WALL = "ray_tpu_engine_stepper_cpu_wall_seconds_total"
# the phases of plain Python, which share one account of CPU seconds
PYTHON = ("admit", "bias", "gather", "emit", "other")


def _phase_series(name):
    """{phase: value} of one of the account's counter families."""
    out = {}
    for line in prometheus_text().splitlines():
        if line.startswith(name + "{phase="):
            out[line.split('"')[1]] = float(line.split()[-1])
    return out


def _scripted(engine, cpu_every):
    """Clocks that a script advances: the k-th read of the wall clock
    comes k ms after the one before, and the thread has been on a CPU
    for a quarter of the wall time whenever anyone asks. Returns the
    count of reads of each."""
    account = engine._account
    reads = {"wall": 0, "cpu": 0}

    def wall():
        reads["wall"] += 1
        return 100.0 + sum(range(reads["wall"])) * 1e-3

    def cpu():
        reads["cpu"] += 1
        return 7.0 + 0.25 * sum(range(reads["wall"])) * 1e-3

    account.clock, account.cpu_clock = wall, cpu
    account.cpu_every = cpu_every
    return reads


def test_scripted_spans_charge_each_interval_to_one_phase():
    """The spans of a step as the engine opens them: nested (bias over
    its upload, launch over insert), two engine.prefill spans that
    overlap, a wait. Every interval between two switches lands in
    exactly one phase and the wall seconds sum to the last switch less
    the first. The thread's CPU clock is read from the first wait for
    the device on, and only where the stepper enters or leaves a phase
    that is not plain Python; the CPU seconds and the wall seconds of
    what it read fall to the same phase."""
    from ray_tpu.llm.engine import STEPPER_CPU_PHASES, STEPPER_PHASES
    engine = ContinuousBatchingEngine(_engine_config())
    account = engine._account
    reads = _scripted(engine, cpu_every=1)
    script = []          # the phase each interval belongs to, in order

    def enter(name, phase):
        span = engine._span(name)
        span.__enter__()
        script.append(phase)
        return span

    def leave(span, back_in):
        span.__exit__(None, None, None)
        script.append(back_in)

    account.bind()                        # the loop's first turn
    script.append("other")
    step = enter("engine.step", "other")
    first = enter("engine.prefill", "admit")
    leave(enter("engine.upload", "upload"), "admit")
    launch = enter("engine.launch", "launch")
    leave(enter("engine.insert", "launch"), "launch")
    leave(launch, "admit")
    second = enter("engine.prefill", "admit")     # under the first
    bias = enter("engine.bias", "bias")
    leave(enter("engine.upload", "upload"), "bias")
    leave(bias, "admit")
    cpu_from = len(script)        # the first wait for the device
    leave(enter("engine.readback", "blocked"), "admit")
    leave(enter("engine.emit", "emit"), "admit")
    leave(first, "admit")                 # the second is still open
    leave(enter("engine.readback", "blocked"), "admit")
    leave(second, "other")
    leave(enter("engine.gather", "gather"), "other")
    leave(enter("engine.upload", "upload"), "other")
    leave(enter("engine.launch", "launch"), "other")
    leave(step, "other")
    leave(enter("engine.wait", "wait"), "other")
    # a request thread's span (prefill_only) is no phase of the stepper
    before = dict(reads)
    other = threading.Thread(
        target=lambda: leave(enter("engine.upload", None), None))
    other.start()
    other.join()
    assert reads == before and script[-2:] == [None, None]
    del script[-2:]
    script.pop()          # the phase after the last switch is open
    assert reads["wall"] == len(script) + 1
    expected = dict.fromkeys(STEPPER_PHASES, 0.0)
    read = dict.fromkeys(STEPPER_CPU_PHASES, 0.0)
    for k, phase in enumerate(script, start=1):
        expected[phase] += k * 1e-3
        if k > cpu_from:
            read["python" if phase in PYTHON else phase] += k * 1e-3
    wall = dict(zip(STEPPER_PHASES, account.wall))
    for phase in STEPPER_PHASES:
        assert abs(wall[phase] - expected[phase]) < 1e-12, phase
        assert wall[phase] > 0.0, phase      # the script visits all nine
    assert abs(sum(account.wall) - (account.t - 100.0)) < 1e-9
    assert abs(sum(account.wall) - sum(range(len(script) + 1)) * 1e-3) < 1e-9
    cpu = dict(zip(STEPPER_CPU_PHASES, account.cpu))
    cpu_wall = dict(zip(STEPPER_CPU_PHASES, account.cpu_wall))
    for phase in STEPPER_CPU_PHASES:
        assert abs(cpu_wall[phase] - read[phase]) < 1e-12, phase
        assert abs(cpu[phase] - 0.25 * read[phase]) < 1e-12, phase
        assert 0.0 < cpu[phase] <= cpu_wall[phase]
    # between two phases of plain Python the CPU clock is left alone.
    # The first wait for the device starts the readings (one on its way
    # in, one out), the second closes a stretch and starts the next
    # (two in, one out); the upload, the launch and the wait after them
    # read on their way in and out
    assert reads["cpu"] == 2 + 3 + 3 * 2 < reads["wall"]
    assert account.prefills == 0
    assert STEPPER_PHASES[account.phase] == "other"


def test_cpu_clock_is_read_in_one_stretch_in_four():
    """Eight plain steps under scripted clocks: the CPU clock is read
    between the fourth wait for the device and the fifth and between
    the eighth and the ninth, at the four places a step leaves plain
    Python or comes back to it, and nowhere else."""
    from ray_tpu.llm.engine import STEPPER_CPU_PHASES
    engine = ContinuousBatchingEngine(_engine_config())
    account = engine._account
    reads = _scripted(engine, cpu_every=4)
    account.bind()
    seen = []
    for _ in range(9):
        with engine._span("engine.step"):
            with engine._span("engine.launch"):
                pass
            with engine._span("engine.readback"):
                seen.append(reads["cpu"])
            with engine._span("engine.emit"):
                pass
    # reads so far when each wait for the device began: the one that
    # opens a stretch, four in it (the last closes it), none outside
    assert seen == [0, 0, 0, 1, 5, 5, 5, 6, 10]
    cpu_wall = dict(zip(STEPPER_CPU_PHASES, account.cpu_wall))
    assert cpu_wall["wait"] == cpu_wall["upload"] == 0.0
    # a step is 8 switches: the stretches are the intervals 29-36 and
    # 61-68 of the script (ms each)
    assert abs(sum(account.cpu_wall)
               - (sum(range(29, 37)) + sum(range(61, 69))) * 1e-3) < 1e-9
    assert abs(sum(account.cpu) - 0.25 * sum(account.cpu_wall)) < 1e-12


def test_account_reaches_its_counter_families_and_stats():
    """Admissions, decode steps and an idle wait on a tiny engine: all
    nine phases appear in the wall family, the five CPU phases in the
    other two and all of them in stats(); the three agree, and the step
    histograms were fed from the account."""
    from ray_tpu.llm.engine import STEPPER_CPU_PHASES, STEPPER_PHASES
    engine = ContinuousBatchingEngine(_engine_config())
    own = _own_series(engine)
    switches = []        # the wall clock, as the account read it

    def clock():
        switches.append(time.perf_counter())
        return switches[-1]

    engine._account.clock = clock
    engine._account.cpu_every = 1
    engine.flush_metrics()
    families = {"wall": (STEPPER, STEPPER_PHASES),
                "cpu": (STEPPER_CPU, STEPPER_CPU_PHASES),
                "cpu_wall": (STEPPER_CPU_WALL, STEPPER_CPU_PHASES)}
    before = {name: _phase_series(name) for name, _ in families.values()}
    hist0 = {(name, phase): _hist(name, phase=phase)[0]
             for name in (STEP, STEP_HOST, STEP_UPLOAD)
             for phase in ("decode", "prefill")}
    with engine.idling():
        pass
    early, early_switch = engine.stats(), switches[-1]
    requests = [engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3, i], max_tokens=5,
        logit_bias={9: -50.0} if i == 1 else None)) for i in range(4)]
    while any(not r.done for r in requests):
        engine.step()
    with engine.idling():
        pass
    late = engine.stats()
    stats = late["stepper_seconds"]
    # a read holds the loop's life up to the last switch before it, so
    # between two reads the account grows by the time that passed, give
    # or take the phase that was open at each
    for read, last_switch in ((early, early_switch), (late, switches[-1])):
        life = sum(read["stepper_seconds"]["wall"].values())
        assert abs(life - (last_switch - switches[0])) < 1e-9
        assert read["stepper_read_at"] >= last_switch
    assert sorted(stats) == sorted(families)
    for kind, (name, phases) in families.items():
        after = _phase_series(name)
        assert list(stats[kind]) == list(phases)
        assert sorted(after) == sorted(phases)
        for phase in phases:
            # stats() tells what this engine's buffer sent; the
            # process's series grew by that and by what any other
            # engine of this process flushed meanwhile
            assert abs(own[name, phase][0] - stats[kind][phase]) < 1e-9, (
                name, phase)
            grew = after[phase] - before[name].get(phase, 0.0)
            assert grew >= stats[kind][phase] - 1e-9, (name, phase)
            assert stats[kind][phase] >= 0.0
    assert len(stats["wall"]) == 9 and len(stats["cpu"]) == 5
    account = engine._account
    # stats() gave what the flush read, and nothing ran since
    assert list(stats["wall"].values()) == account.wall
    assert all(seconds > 0.0 for seconds in stats["wall"].values()), stats
    assert all(seconds > 0.0 for seconds in stats["cpu_wall"].values())
    # the CPU clock was read from the first wait for the device on
    assert 0.0 < sum(stats["cpu_wall"].values()) < sum(account.wall)
    assert stats["cpu_wall"]["blocked"] == stats["wall"]["blocked"]

    def fed(name):
        mine = sum(own[name, phase][0] for phase in ("decode", "prefill"))
        assert sum(_hist(name, phase=phase)[0] - hist0[(name, phase)]
                   for phase in ("decode", "prefill")) >= mine - 1e-9
        return mine

    # host time is step time less the account's blocked seconds, and
    # the upload histogram took the account's upload seconds
    assert abs(fed(STEP) - fed(STEP_HOST) - stats["wall"]["blocked"]) < 1e-9
    assert abs(fed(STEP_UPLOAD) - stats["wall"]["upload"]) < 1e-9
    # the steps are the account less the waits and the loop around them
    in_steps = sum(account.wall) - stats["wall"]["wait"]
    assert fed(STEP) <= in_steps + 1e-9
    # a second flush with nothing new adds nothing
    account_series = (STEPPER, STEPPER_CPU, STEPPER_CPU_WALL)
    sent = {key: total for key, (total, _) in own.items()
            if key[0] in account_series}
    engine.flush_metrics()
    assert {key: total for key, (total, _) in own.items()
            if key[0] in account_series} == sent
    again = _phase_series(STEPPER_CPU_WALL)
    assert all(again[phase] >= after[phase] for phase in after)


# -- (d2) the stall watch reads the account and writes nothing ------------

HELD_SECONDS = "ray_tpu_thread_held_seconds_total"
HELD = "ray_tpu_thread_held_total"


def test_a_slow_readback_is_a_held_episode_in_stats_and_the_counters(
        monkeypatch):
    """A tiny engine whose first read-back takes 0.4 s: the process's
    watch (real clocks) reports the stepper held in ``blocked`` in
    stats()["stalls"], with the stepper's stack, and flush_metrics()
    carries the episode's counts."""
    watch = flight_recorder.start_stall_watch()
    engine = ContinuousBatchingEngine(_engine_config())
    assert engine._mbuf in flight_recorder.stall_carriers
    slept = []

    def slow_asarray(a, real=engine_mod.np.asarray):
        if not slept:
            slept.append(True)
            time.sleep(0.4)
        return real(a)

    # engine.np is numpy itself: patch the name the read-back calls
    # through a stand-in module that has only ``asarray`` slowed
    class _SlowNumpy:
        def __getattr__(self, name):
            return getattr(np, name)
    slow_np = _SlowNumpy()
    slow_np.asarray = slow_asarray
    role = watch.role
    seconds0 = _counter(HELD_SECONDS, process=role, thread="stepper")
    count0 = _counter(HELD, process=role, thread="stepper",
                         phase="blocked")
    request = engine.add_request(GenerationRequest(
        prompt_ids=[1, 2, 3, 4], max_tokens=3))
    engine.step()           # compiles, so that the slow step is plain
    monkeypatch.setattr(engine_mod, "np", slow_np)
    while not request.done:
        engine.step()
    assert slept
    monkeypatch.undo()
    with engine.idling():   # ``since`` moves: the episode closes
        deadline = time.monotonic() + 10.0
        mine = []
        while time.monotonic() < deadline and not mine:
            time.sleep(0.05)
            mine = [e for e in engine.stats()["stalls"]
                    if e["kind"] == "held" and e["thread"] == "stepper"
                    and e["phase"] == "blocked" and "open" not in e
                    and "slow_asarray" in "".join(e["stack"])]
    assert mine, engine.stats()["stalls"]
    episode = mine[-1]
    assert 0.3 <= episode["seconds"] <= 5.0 and episode["process"] == role
    assert len(episode["stack"]) <= flight_recorder.STALL_STACK_FRAMES
    assert "time.sleep(0.4)" in "".join(episode["stack"])
    engine.flush_metrics()
    assert _counter(HELD_SECONDS, process=role, thread="stepper") \
        - seconds0 >= episode["seconds"] - 1e-6
    assert _counter(HELD, process=role, thread="stepper",
                       phase="blocked") - count0 >= 1.0
    engine.close()
    assert engine._mbuf not in flight_recorder.stall_carriers


def test_the_watch_writes_nothing_into_the_stepper_account():
    """The same scripted steps with a watch that looks at the account
    at every turn and with none: the account's lists, its phase and the
    count of clock reads are equal, so the probe neither writes nor
    reads a clock; and a probe of a collected engine goes."""

    def run(watched):
        engine = ContinuousBatchingEngine(_engine_config())
        account = engine._account
        reads = _scripted(engine, cpu_every=1)
        watch = flight_recorder.StallWatch(sleep=lambda seconds: None)
        probe = watch.add_probe("stepper", account.probe,
                                lambda: account.thread)
        seen = []

        def look():
            if watched:
                watch.tick()
                seen.append(probe.since)

        account.bind()
        for _ in range(6):
            with engine._span("engine.step"):
                look()
                with engine._span("engine.launch"):
                    look()
                with engine._span("engine.readback"):
                    look()
                with engine._span("engine.emit"):
                    look()
            with engine.idling():
                look()
                assert account.probe() is None      # idle in ``wait``
        assert account.probe() == ("other", account.t)
        return (list(account.wall), list(account.cpu),
                list(account.cpu_wall), account.phase, account.t,
                dict(reads)), seen

    alone, _ = run(watched=False)
    beside, seen = run(watched=True)
    assert beside == alone
    # the watch did look: it saw ``since`` move with every switch, and
    # None in every wait
    assert len(seen) == 30 and seen.count(None) == 6
    assert len(set(seen)) == 25
    # the engine's own probe lives as long as the engine
    watch = flight_recorder.stall_watch()
    before = len(watch._probes)
    engine = ContinuousBatchingEngine(_engine_config())
    assert len(watch._probes) == before + 1
    assert watch._probes[-1].thread == "stepper"
    del engine
    gc.collect()
    assert len(watch._probes) == before


def test_span_without_recorder_or_profiler_is_inert():
    was = flight_recorder.RECORDER
    flight_recorder.disable()
    try:
        with flight_recorder.span("serve", "engine.nothing", step=1):
            pass
    finally:
        flight_recorder.RECORDER = was


# -- (e) the proxy's stamp cannot come from the client -------------------

def test_proxy_strips_a_client_supplied_stamp(ray_start_shared):
    @serve.deployment
    def echo(req):
        return {"got": req}

    try:
        serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
        port = serve._proxy.port
        serve.run(echo.bind(), name="stamp_app", route_prefix="/stamp")

        def post(path, payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())["got"]

        t0 = time.time()
        got = post("/stamp/v1/x", {"a": 1, RECEIVED_KEY: "1.0",
                                   "__path__": "/evil"})
        assert got["__path__"] == "/v1/x" and got["a"] == 1
        assert isinstance(got[RECEIVED_KEY], float)
        assert t0 <= got[RECEIVED_KEY] <= time.time()
        # a root request keeps a pristine payload: no stamp, and none
        # of the client's either
        assert post("/stamp", {"a": 1, RECEIVED_KEY: 5.0}) == {"a": 1}
    finally:
        serve.shutdown()
