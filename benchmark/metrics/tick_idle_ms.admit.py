"""Milliseconds per scheduler tick during which the device idled while the
host was in admission: the deadline sweep and ``_admit`` with everything
inside it (padding, the prefill dispatch, the wait for its logits, the first
sample, the page copy). The idle gaps of the device trace, split by exact
overlap over the program's ``apex:sched/*`` spans (``benchmark/spans.py``);
the six ``tick_idle_ms.*`` add up to the gaps' summed length per tick."""

from benchmark import spans


def read(run):
    return spans.tick_idle_ms(run, "admit")
