"""Median device time of one execution of the decode program
(``jit_decode`` in the trace's ``XLA Modules`` line)."""

from benchmark.harness import median


def read(run):
    times = run["trace"].program_times("jit_decode")
    return 1e3 * median(times) if times else None
