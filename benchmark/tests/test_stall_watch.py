"""The four metrics that read the stall watch's counters
(`ray_tpu_process_stall_seconds_total{process}`: the replica did not
run; `ray_tpu_thread_held_seconds_total{process, thread}`: its stepper
sat in one phase while the process ran) as a share of the stepper's
life: the data files through the harness on hand-made series, what a
program without the series gives, and which cells report them. On the
CPU; nothing here gives a device number."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

PHASES = ("wait", "admit", "bias", "gather", "upload", "launch",
          "blocked", "emit", "other")
WALL = 'ray_tpu_engine_stepper_seconds_total{phase="%s"}'
STALL = 'ray_tpu_process_stall_seconds_total{process="%s"}'
HELD = 'ray_tpu_thread_held_seconds_total{process="%s",thread="%s"}'
PARTS = {"replica_stall_share": STALL % "replica",
         "stepper_held_share": HELD % ("replica", "stepper")}
CELLS = {"mistral7b-chat-steady": (".lat", "itl_p90_ms"),
         "mistral7b-chat-saturated": (".thr", "serve_tok_s")}
LAYERS = {"replica_stall_share": "Serving control (proxy, router, replica)",
          "stepper_held_share": "Engine"}
OTHER_CELLS = ("mistral7b-train-2k", "mistral7b-train-fsdp4",
               "jamba2-3b-chat-steady", "granite4h-agent-steady")

# the stepper's life between the two scrapes: 50 s, most of it blocked
LIFE_S = {"wait": 4.0, "admit": 0.5, "bias": 0.1, "gather": 0.1,
          "upload": 0.5, "launch": 2.5, "blocked": 41.0, "emit": 0.5,
          "other": 0.8}


def _observed(grew, before=900.0):
    """A window in which the nine phases grew by ``LIFE_S`` and each
    series of ``grew`` by its seconds, all from ``before``."""
    start = {WALL % phase: before for phase in PHASES}
    after = {WALL % phase: before + LIFE_S[phase] for phase in PHASES}
    for series, seconds in grew.items():
        start[series] = before
        after[series] = before + seconds
    return {"series_before": start, "series_after": after}


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_four_second_stop_in_fifty_reads_eight(cell_name):
    suffix, moves = CELLS[cell_name]
    cell = harness.load_cell(cell_name)
    mine = {m["name"]: m for m in cell["per_layer"]
            if m["name"].startswith(tuple(PARTS))}
    assert list(mine) == [stem + suffix for stem in PARTS]
    for stem in PARTS:
        m = mine[stem + suffix]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "lower", "program_counter", moves)
        assert m["layer"] == LAYERS[stem] and m["workloads"] == [cell_name]
    assert sum(LIFE_S.values()) == pytest.approx(50.0)
    # the replica stopped for 4 s; the driver's stop and another
    # thread's stay are no part of either metric
    observed = {"cell": cell, **_observed({
        STALL % "replica": 4.0, STALL % "driver": 7.0,
        HELD % ("replica", "stepper"): 1.0,
        HELD % ("replica", "io_loop"): 3.0})}
    values = harness.per_layer_values(cell, observed)
    assert values["replica_stall_share" + suffix] == {
        "value": pytest.approx(8.0, abs=1e-9), "unit": "%"}
    assert values["stepper_held_share" + suffix]["value"] == \
        pytest.approx(2.0, abs=1e-9)
    # what was counted before the window is no part of it
    later = {"cell": cell, **_observed({STALL % "replica": 4.0},
                                       before=5.0)}
    assert harness.per_layer_values(cell, later)[
        "replica_stall_share" + suffix]["value"] == \
        pytest.approx(8.0, abs=1e-9)


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_a_run_that_met_nothing_reads_zero_and_no_series_reads_none(
        cell_name):
    suffix, _ = CELLS[cell_name]
    cell = harness.load_cell(cell_name)
    names = [stem + suffix for stem in PARTS]
    # an undisturbed run, and the parent commit (its stepper series
    # exist, the part is absent): 0.0, on the line
    values = harness.per_layer_values(cell, {"cell": cell, **_observed({})})
    assert [values[name]["value"] for name in names] == [0.0, 0.0]
    # a run that scraped nothing, and a program with no stepper series
    for observed in ({"series_after": None},
                     {"series_before": {}, "series_after": {}}):
        values = harness.per_layer_values(cell, {"cell": cell, **observed})
        assert not [name for name in names if name in values]


@pytest.mark.parametrize("cell_name", OTHER_CELLS)
def test_no_other_cell_reports_them(cell_name):
    names = [m["name"] for m in harness.load_cell(cell_name)["per_layer"]]
    assert not [name for name in names if name.startswith(tuple(PARTS))]


def test_the_files_name_the_series_the_program_exports():
    from ray_tpu.llm.engine import STEPPER_PHASES
    from ray_tpu.util import flight_recorder
    assert tuple(STEPPER_PHASES) == PHASES
    assert (flight_recorder.PROCESS_STALL_SECONDS._name,
            flight_recorder.PROCESS_STALL_SECONDS._tag_keys) == (
        "ray_tpu_process_stall_seconds_total", ("process",))
    assert (flight_recorder.THREAD_HELD_SECONDS._name,
            flight_recorder.THREAD_HELD_SECONDS._tag_keys) == (
        "ray_tpu_thread_held_seconds_total", ("process", "thread"))
    for suffix in (".lat", ".thr"):
        for stem, part in PARTS.items():
            spec = harness.load_json("layer_metrics",
                                     stem + suffix + ".json")
            assert spec == {"reader": "counter_share", "args": {
                "part": part, "whole": [WALL % p for p in PHASES]}}
