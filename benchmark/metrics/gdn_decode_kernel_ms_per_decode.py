"""Device time of the Gated DeltaNet decode kernel per execution of the
decode program: the Mosaic ``custom-call``s named ``apex_gdn_decode_fwd`` in
the trace, ``linear_layers`` of them per ``jit_decode``. Nothing is reported
when there is none (a program with no recurrent layer), or when the calls are
no multiple of ``linear_layers`` (an execution cut by the session)."""

import re

from benchmark import spans
from benchmark.harness import median

GDN_DECODE_FWD = re.compile(r"^%apex_gdn_decode_fwd(\.\d+)? = ")


def per_decode(run):
    """(seconds, calls) per decode execution, or None."""
    layers = int(run["counts"].get("sizes", {}).get("linear_layers", 0))
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(GDN_DECODE_FWD.match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return seconds / (calls // layers), layers


def state_slots(run):
    """Slots whose recurrent state a decode step of the traced span updates:
    the median ``state_slots`` stat of the ``apex:sched/exec`` spans that
    begin there (the engine's own count), or None when no span says."""
    said = [int(s.stats["state_slots"]) for s in spans.in_window(run, "exec")
            if "state_slots" in s.stats]
    return int(median(said)) if said else None


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
