"""Milliseconds per scheduler tick during which the device idled while the
host was in ``draft``, ``prepare_decode`` and ``exec``: everything up to and
including the dispatch of the decode or verify program. The idle gaps of the
device trace, split by exact overlap over the program's ``apex:sched/*`` spans
(``benchmark/spans.py``); the six ``tick_idle_ms.*`` add up to the gaps'
summed length per tick."""

from benchmark import spans


def read(run):
    return spans.tick_idle_ms(run, "dispatch")
