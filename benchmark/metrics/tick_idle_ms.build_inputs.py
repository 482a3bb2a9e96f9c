"""Milliseconds per scheduler tick during which the device idled while the
host was in ``build_inputs``: the tokens, the active mask, the temperatures
and the per-slot sampling keys of a decode or verify step. The idle gaps of
the device trace, split by exact overlap over the program's ``apex:sched/*``
spans (``benchmark/spans.py``); the six ``tick_idle_ms.*`` add up to the gaps'
summed length per tick."""

from benchmark import spans


def read(run):
    return spans.tick_idle_ms(run, "build_inputs")
