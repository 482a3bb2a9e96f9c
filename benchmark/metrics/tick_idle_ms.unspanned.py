"""Milliseconds per scheduler tick during which the device idled while the
host was in no phase: inside ``step`` under none of its spans, or outside any
``step`` (the caller's loop between ticks). The idle gaps of the device trace,
split by exact overlap over the program's ``apex:sched/*`` spans
(``benchmark/spans.py``); the six ``tick_idle_ms.*`` add up to the gaps'
summed length per tick."""

from benchmark import spans


def read(run):
    return spans.tick_idle_ms(run, "unspanned")
