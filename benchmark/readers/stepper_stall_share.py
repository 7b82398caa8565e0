"""The share (%) of the stepper thread's life in which it had work of
its own to do and did not run. The program reads its thread's CPU clock
in some stretches of its life only (the clock is a system call), and
exports, by phase, the CPU seconds and the wall seconds of those
stretches; ``phase`` is the one that stands for its plain Python, where
nothing but the interpreter and the scheduler can hold the thread. So:
the window's growth of that phase's wall seconds less its CPU seconds,
not under 0, over the wall growth of every phase of those stretches."""

from benchmark.readers.series import delta

CPU = "ray_tpu_engine_stepper_cpu_seconds_total"
WALL = "ray_tpu_engine_stepper_cpu_wall_seconds_total"


def read(observed, phase: str):
    if observed.get("series_after") is None:
        return None
    life = sum(delta(observed, key) for key in observed["series_after"]
               if key.startswith(WALL + "{"))
    if life <= 0:
        return None
    labels = '{phase="%s"}' % phase
    stalled = delta(observed, WALL + labels) - delta(observed, CPU + labels)
    return 100.0 * max(0.0, stalled) / life
