"""Device time of the grouped expert product per execution of the decode
program: the Mosaic ``custom-call``s named ``apex_moe_gmm_fwd`` that ran
INSIDE a ``jit_decode``, two per expert layer. The prefill program holds the
same kernel (at its smallest bucket with the very shapes of a decode step:
128 prompt positions are as many rows as 128 slots), so the operations'
summed times by name cannot tell the two apart: this reader goes back to the
trace file and puts each call down to the program execution it lies in
(``XLA Modules``, on the same plane and clock). Nothing is reported when
there is no such call (a program with no expert layer; ``jax.lax.ragged_dot``
in the kernel's place), when the calls are no multiple of two per expert
layer, or when the trace file is not at hand."""

import bisect
import os
import re

from benchmark import trace

MOE_GMM_FWD = re.compile(r"^%apex_moe_gmm_fwd(\.\d+)? = ")
_HASH = re.compile(r"\(\d+\)$")


def load(path: str) -> dict:
    """``{program: (seconds, calls)}`` of the ``apex_moe_gmm_fwd`` calls of
    one ``.xplane.pb`` (or ``.gz``), by the program execution each lies in,
    on the first chip that ran any."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        modules, calls = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  _HASH.sub("", e.name)) for e in line.events)
            elif line.name == "XLA Ops":
                calls = [(e.start_ns, e.duration_ns) for e in line.events
                         if MOE_GMM_FWD.match(e.name)]
        if not calls:
            continue
        starts = [m[0] for m in modules]
        out = {}
        for start, dur in calls:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < modules[i][1]:
                seconds, n = out.get(modules[i][2], (0.0, 0))
                out[modules[i][2]] = (seconds + dur * 1e-9, n + 1)
        return out
    return {}


def of(run) -> dict:
    """The calls of this run's trace, parsed once and kept on ``run``."""
    if "moe_gmm_calls" not in run:
        try:
            cell = run["cell"]
            run["moe_gmm_calls"] = load(trace.find(os.path.join(
                cell.root, ".bench_trace", cell.name)))
        except (AttributeError, OSError):
            run["moe_gmm_calls"] = {}
    return run["moe_gmm_calls"]


def per_decode(run):
    """(seconds per decode execution, expert layers), or None."""
    layers = int(run["counts"].get("sizes", {}).get("expert_layers", 0))
    if not layers or not run["trace"].program_times("jit_decode"):
        return None
    seconds, calls = of(run).get("jit_decode", (0.0, 0))
    if not calls or calls % (2 * layers) or seconds <= 0:
        return None
    return seconds / (calls // (2 * layers)), layers


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
