"""Milliseconds per scheduler tick during which the device idled while the
host was in ``accept``: the finiteness check, the sampling program and their
read-back, where the host waits for the device. The idle gaps of the device
trace, split by exact overlap over the program's ``apex:sched/*`` spans
(``benchmark/spans.py``); the six ``tick_idle_ms.*`` add up to the gaps'
summed length per tick."""

from benchmark import spans


def read(run):
    return spans.tick_idle_ms(run, "accept")
