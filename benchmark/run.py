"""One cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic and metrics are data (BENCHMARK.json
and the files under benchmark/); the traffic file's ``kind`` names the
runner, the configuration file's ``program`` the module of its model
family (benchmark/programs/). The last line of stdout is the result.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.

``--rehearse`` runs the same paths on the CPU to check them, at the
tiny sizes the program module keeps for its family: it says
``"correct": false`` and a device that is ``cpu``, and writes no
metric. Without it a run that finds no chip fails. ``--sweep 2,3,4``
(serving cells) offers each rate in turn to one replica and prints a
row for each: how a traffic file's rate was found. ``--control
none,<name>`` (serving cells) runs the cell's reference check alone, at
the cell's sizes, once under each named control of the program module
(benchmark/programs/__init__.py; ``none`` is the sound program), and
prints one line a control, ``{"control", "workload", "seed",
"report"}``: no cell result, so it can never be read as a run of the
cell. How a configuration file's ``check.limits`` were found.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in
                                               s.split(",")], default=None)
    ap.add_argument("--control", default=None,
                    help="names of the program module's controls, with "
                         "commas: the reference check alone under each")
    ap.add_argument("--dump", default=None,
                    help="directory for what helps to look at a run by "
                         "hand: raw records, the trace's planes")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        platforms = os.environ.get("JAX_PLATFORMS", "").lower()
        if platforms and platforms.split(",")[0].strip() != "tpu":
            sys.stderr.write(
                f"benchmark: JAX_PLATFORMS={platforms!r} does not put the "
                "TPU first; a cell runs on a TPU only (--rehearse checks "
                "paths on the CPU)\n")
            return 1
    from benchmark import harness
    try:
        from ray_tpu.accelerators import TpuAcceleratorManager
        cell = harness.load_cell(args.workload, args.rehearse)
        if not args.rehearse:
            chips = TpuAcceleratorManager.num_chips_on_node()
            if chips < cell["chips"]:
                raise harness.BenchError(
                    f"cell {args.workload} asks for {cell['chips']} "
                    f"chips, this machine has {chips}")
        runner = harness.runner_for(cell["traffic_file"]["kind"])
        if args.control:
            if not hasattr(runner, "control_reports"):
                raise harness.BenchError(
                    f"runner {cell['traffic_file']['kind']} has no "
                    "reference check to run under a control")
            reports = runner.control_reports(cell, args)
            sys.stdout.flush()
            for report in reports:
                print(json.dumps(report))
            return 0
        out = runner.run(cell, args, T_START)
        if args.rehearse:
            metrics = {}
        elif args.trace:
            metrics = harness.per_layer_values(cell, out["observed"])
        else:
            metrics = harness.end_to_end_values(cell, out["measured"])
    except harness.BenchError as exc:
        sys.stderr.write(f"benchmark: FAILED: {exc}\n")
        return 1
    # after the runtime has shut down: the log monitor echoes worker
    # output to stdout, and the result must be the last line
    sys.stdout.flush()
    sys.stderr.write(harness.compared_lines(out.get("compared") or {}))
    sys.stderr.flush()
    print(harness.result_line(out["correct"], out["attempted"],
                              out["failed"], metrics, out["device"],
                              out.get("breakdown"), out.get("compared")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
