"""How tier-1 judges a ratio of two host timings: the one place.

A guard compares what something costs on this host with a baseline taken
in the same run (an observer on against off, a task against the box's
Python speed, one wire codec against the other). The box is shared and
the tests run six workers wide, so one reading says as much about the
neighbours as about the tree. Decided here, once: what is timed
(`task_loop`, `calibration_op_seconds`), how two timings are made
comparable (`interleaved_best`), and when a ratio over its limit fails
the test (`judge`).
"""

import gc
import time

import ray_tpu

ATTEMPTS = 3


def task_loop():
    """Prime the pool with 500 no-op tasks and return `run(n=1500)`:
    seconds to submit `n` of them, and seconds until one `get` has them
    all (completions overlap submission, so the second is end to end)."""
    @ray_tpu.remote(num_cpus=0)
    def nop():
        return None

    ray_tpu.get([nop.remote() for _ in range(500)])

    def run(n=1500):
        t0 = time.perf_counter()
        refs = [nop.remote() for _ in range(n)]
        t1 = time.perf_counter()
        ray_tpu.get(refs)
        return t1 - t0, time.perf_counter() - t0

    return run


def calibration_op_seconds(n=300_000):
    """Fixed pure-Python workload (dict stores + tuple allocs + list
    append/clear — the flavor of per-task bookkeeping) measuring the
    box's current effective Python speed, as seconds an operation."""
    t0 = time.perf_counter()
    d = {}
    out = []
    for i in range(n):
        d[i & 1023] = i
        out.append((i, i + 1))
        if len(out) > 1024:
            out.clear()
    return (time.perf_counter() - t0) / n


def interleaved_best(arms, rounds):
    """Run every arm of `arms` (name -> callable giving the seconds of its
    timed segment) in turn, `rounds` times, so that drift hits all of
    them; each keeps its least."""
    best = {}
    for _ in range(rounds):
        for name, arm in arms.items():
            # under pytest the heap carries every earlier test's objects:
            # a collection landing in one arm's segment and not the
            # other's would swamp a cost of microseconds
            gc.collect()
            best[name] = min(arm(), best.get(name, float("inf")))
    return best


def judge(measure):
    """`measure()` gives `[(what, ratio, limit), ...]`, each ratio a cost
    over its baseline. It is asked again while a ratio is at or over its
    limit, `ATTEMPTS` times in all, and the guard fails only if every
    attempt was over. Where both sides of a ratio are timed the same way
    (`interleaved_best`: each arm keeps its least), load can only raise a
    reading, so a regression is over in all attempts and a busy box is
    not. That does NOT hold for a ratio over `calibration_op_seconds`,
    which is one reading of one thread: a slow reading of it lowers the
    ratio, so there three attempts are also three chances to pass on the
    baseline's noise (ROADMAP D9a)."""
    attempts, under = [], False
    while not under and len(attempts) < ATTEMPTS:
        attempts.append(measure())
        under = all(ratio < limit for _, ratio, limit in attempts[-1])
    read = "; ".join(
        ", ".join(f"{what} {ratio:.4g} (limit {limit:.4g})"
                  for what, ratio, limit in readings)
        for readings in attempts)
    assert under, f"over its limit in each of {ATTEMPTS} attempts: {read}"


def judge_switched(what, limit, off, on, timed=None, rounds=2):
    """The guard most tests want: the least seconds of `timed()` after
    the switch `on()` over the least after `off()`, judged against
    `limit`. `timed` is the task loop's end-to-end seconds unless given."""
    if timed is None:
        loop = task_loop()

        def timed():
            return loop()[1]

    def measure():
        best = interleaved_best({"off": lambda: (off(), timed())[1],
                                 "on": lambda: (on(), timed())[1]}, rounds)
        return [(what, best["on"] / best["off"], limit)]

    judge(measure)
