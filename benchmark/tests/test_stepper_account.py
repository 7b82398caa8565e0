"""The six metrics that read the stepper's account of its own time
(`ray_tpu_engine_stepper_seconds_total` by nine phases; the thread's
`_cpu_seconds_total` and the `_cpu_wall_seconds_total` of the stretches
in which that clock was read, by five): the new reader's arithmetic,
what a program without the series gives, and the data files through
the harness. On the CPU; nothing here gives a device number."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.readers import stepper_stall_share  # noqa: E402

PHASES = ("wait", "admit", "bias", "gather", "upload", "launch",
          "blocked", "emit", "other")
CPU_PHASES = ("wait", "python", "upload", "launch", "blocked")
WALL = 'ray_tpu_engine_stepper_seconds_total{phase="%s"}'
CPU = 'ray_tpu_engine_stepper_cpu_seconds_total{phase="%s"}'
CPU_WALL = 'ray_tpu_engine_stepper_cpu_wall_seconds_total{phase="%s"}'
SERVING = ("mistral7b-chat-steady", "mistral7b-chat-saturated",
           "jamba2-3b-chat-steady")
NEW = ("stepper_blocked_share", "stepper_emit_share", "stepper_stall_share")


def _observed(wall, cpu, cpu_wall, before=900.0):
    """A window in which each series grew by what its dict says
    (seconds by phase) from ``before``."""
    start = {family % phase: before
             for family, phases in ((WALL, PHASES), (CPU, CPU_PHASES),
                                    (CPU_WALL, CPU_PHASES))
             for phase in phases}
    after = dict(start)
    for family, grew in ((WALL, wall), (CPU, cpu), (CPU_WALL, cpu_wall)):
        for phase, seconds in grew.items():
            after[family % phase] += seconds
    return {"series_before": start, "series_after": after}


# a 40 s window: the device had the stepper waiting for 32 s of it; the
# thread's CPU clock was read in a quarter of it
WALL_S = {"wait": 1.0, "admit": 0.8, "bias": 0.2, "gather": 0.1,
          "upload": 0.5, "launch": 1.4, "blocked": 32.0, "emit": 3.0,
          "other": 1.0}
CPU_WALL_S = {"wait": 0.3, "python": 1.25, "upload": 0.1, "launch": 0.35,
              "blocked": 8.0}
CPU_S = {"wait": 0.01, "python": 1.0, "upload": 0.04, "launch": 0.2,
         "blocked": 0.1}


def test_stall_share_by_hand():
    # 1.25 s of plain Python with the thread on a CPU for 1.0 of them,
    # of the 10 s in which the clock was read; what the thread waited
    # in upload, launch, blocked and wait it waited by intent
    got = stepper_stall_share.read(_observed(WALL_S, CPU_S, CPU_WALL_S),
                                   phase="python")
    assert got == pytest.approx(100.0 * 0.25 / 10.0, abs=1e-9)
    # what was there before the window is no part of it
    later = _observed(WALL_S, CPU_S, CPU_WALL_S, before=5.0)
    assert stepper_stall_share.read(later, phase="python") == \
        pytest.approx(got, abs=1e-9)
    # CPU seconds over the wall seconds (the clocks' grain) read as none
    ahead = dict(CPU_S, python=1.3)
    assert stepper_stall_share.read(
        _observed(WALL_S, ahead, CPU_WALL_S), phase="python") == 0.0


def test_nothing_to_read_reads_as_nothing():
    # a run that scraped no series; a program that lacks the account
    # (the parent commit: other series, none of these); no growth
    assert stepper_stall_share.read({}, phase="python") is None
    parent = {"series_before": {"ray_tpu_engine_step_seconds_count": 3.0},
              "series_after": {"ray_tpu_engine_step_seconds_count": 9.0}}
    assert stepper_stall_share.read(parent, phase="python") is None
    assert stepper_stall_share.read(_observed({}, {}, {}),
                                    phase="python") is None
    for cell_name in SERVING:
        cell = harness.load_cell(cell_name)
        values = harness.per_layer_values(cell, {**parent, "cell": cell})
        assert not [name for name in values if name.startswith(NEW)]


@pytest.mark.parametrize("cell_name", SERVING)
def test_data_files_load_through_the_harness(cell_name):
    cell = harness.load_cell(cell_name)
    suffix = ".thr" if cell_name.endswith("saturated") else ".lat"
    mine = [m for m in cell["per_layer"] if m["name"].startswith(NEW)]
    assert [m["name"] for m in mine] == [stem + suffix for stem in NEW]
    assert {m["moves"] for m in mine} == {
        "serve_tok_s" if suffix == ".thr" else "itl_p90_ms"}
    observed = {**_observed(WALL_S, CPU_S, CPU_WALL_S), "cell": cell}
    values = harness.per_layer_values(cell, observed)
    assert values["stepper_blocked_share" + suffix]["value"] == \
        pytest.approx(80.0, abs=1e-9)
    assert values["stepper_emit_share" + suffix]["value"] == \
        pytest.approx(7.5, abs=1e-9)
    assert values["stepper_stall_share" + suffix]["value"] == \
        pytest.approx(2.5, abs=1e-9)
    assert all(values[stem + suffix]["unit"] == "%" for stem in NEW)


@pytest.mark.parametrize("cell_name", ("mistral7b-train-2k",
                                       "mistral7b-train-fsdp4"))
def test_no_train_cell_reports_them(cell_name):
    names = [m["name"] for m in harness.load_cell(cell_name)["per_layer"]]
    assert not [name for name in names if name.startswith(NEW)]


def test_the_files_name_the_phases_the_engine_exports():
    from ray_tpu.llm.engine import STEPPER_CPU_PHASES, STEPPER_PHASES
    assert tuple(STEPPER_PHASES) == PHASES
    assert tuple(STEPPER_CPU_PHASES) == CPU_PHASES
    for suffix in (".lat", ".thr"):
        for stem, part in (("stepper_blocked_share", "blocked"),
                           ("stepper_emit_share", "emit")):
            spec = harness.load_json("layer_metrics",
                                     stem + suffix + ".json")
            assert spec["reader"] == "counter_share"
            assert spec["args"]["part"] == WALL % part
            assert spec["args"]["whole"] == [WALL % p for p in PHASES]
        spec = harness.load_json("layer_metrics",
                                 "stepper_stall_share" + suffix + ".json")
        assert spec == {"reader": "stepper_stall_share",
                        "args": {"phase": "python"}}
