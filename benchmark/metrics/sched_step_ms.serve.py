"""Median wall time of one ``sched.step()``, by the benchmark's own clock
around the call, host and device together."""

from benchmark.harness import median


def read(run):
    walls = [w for _, w in run["counts"]["step_walls"]]
    return 1e3 * median(walls) if walls else None
