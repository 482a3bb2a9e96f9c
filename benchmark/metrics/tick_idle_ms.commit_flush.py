"""Milliseconds per scheduler tick during which the device idled while the
host was in ``commit`` and ``flush``: the per-slot commit walk and the
end-of-tick stream delivery. The idle gaps of the device trace, split by exact
overlap over the program's ``apex:sched/*`` spans (``benchmark/spans.py``);
the six ``tick_idle_ms.*`` add up to the gaps' summed length per tick."""

from benchmark import spans


def read(run):
    return spans.tick_idle_ms(run, "commit_flush")
