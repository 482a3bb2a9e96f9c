"""The program's own host spans, out of the same ``.xplane.pb`` the device
planes are in.

The serving scheduler opens a ``jax.profiler.TraceAnnotation`` named
``apex:sched/<phase>`` around every phase of its tick
(``apex_tpu/serving/observe.py::PHASES``), with what it knows at that moment
as the event's stats (``tick``, ``bucket``, ``prompt_tokens``, ...). They lie
on the host plane, on the clock of the device planes, so an idle gap of the
device can be put down to the phase the host was in. The runner builds the
engine and is not touched: the trace file is the only channel, and a program
that opens no such span (the parent of PR 24) gives an empty list, on which
every reader returns ``None``.

A span's parent is the span it lies inside on its thread. Only ``step`` spans
and what lies inside them are kept: a phase whose ``step`` the session cut
off at its start or stop is left out, not half counted (the profiler itself
drops a span that was open when the session started or stopped).
"""

import collections
import os

import numpy as np

from benchmark import trace

PREFIX = "apex:sched/"

#: phase -> the group its self time is counted under. A phase that is not
#: here (``prefill`` and the transfers inside it) counts under the nearest
#: span around it that is; ``step``'s own self time is ``unspanned``.
GROUP_OF = {
    "expire": "admit", "admit": "admit", "chunk_prefill": "admit",
    "build_inputs": "build_inputs",
    "draft": "dispatch", "prepare_decode": "dispatch", "exec": "dispatch",
    "accept": "accept",
    "commit": "commit_flush", "flush": "commit_flush",
}
GROUPS = ("admit", "build_inputs", "dispatch", "accept", "commit_flush",
          "unspanned")

Span = collections.namedtuple("Span", "phase start end stats parent")


def nest(rows, base: int = 0):
    """``rows`` of (phase, start, end, stats) from ONE thread -> ``Span``s in
    start order, each with the index (from ``base``) of the span it lies
    inside (-1: none). Kept: ``step`` and its descendants."""
    rows = sorted(rows, key=lambda r: (r[1], -r[2]))
    out, stack, index = [], [], {}
    for i, (phase, s, e, stats) in enumerate(rows):
        while stack and rows[stack[-1]][2] < e:
            stack.pop()
        parent = stack[-1] if stack else -1
        stack.append(i)
        if phase == "step" and parent < 0 or parent in index:
            index[i] = base + len(out)
            out.append(Span(phase, s, e, stats, index.get(parent, -1)))
    return out


def load(path: str):
    """The ``apex:sched/*`` spans of one ``.xplane.pb`` (or ``.gz``), in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = []
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    rows.append((e.name[len(PREFIX):], s,
                                 s + e.duration_ns * 1e-9, dict(e.stats)))
            out += nest(rows, base=len(out))
    return out


def of(run):
    """The spans of this run's trace, parsed once and kept on ``run``."""
    if "apex_spans" not in run:
        cell = run["cell"]
        run["apex_spans"] = load(trace.find(os.path.join(
            cell.root, ".bench_trace", cell.name)))
    return run["apex_spans"]


def in_window(run, phase: str):
    """The ``phase`` spans that begin inside the traced window."""
    lo, hi = run["trace"].window
    return [s for s in of(run) if s.phase == phase and lo <= s.start <= hi]


def group_of(spans, i: int) -> str:
    while i >= 0:
        if spans[i].phase in GROUP_OF:
            return GROUP_OF[spans[i].phase]
        i = spans[i].parent
    return "unspanned"


def self_intervals(spans):
    """group -> (n, 2) array: for every span the parts of it that no child
    covers, under the span's group. Disjoint by construction."""
    children = collections.defaultdict(list)
    for i, s in enumerate(spans):
        if s.parent >= 0:
            children[s.parent].append(i)
    out = collections.defaultdict(list)
    for i, s in enumerate(spans):
        cur, group = s.start, group_of(spans, i)
        for c in children[i]:               # in start order
            if spans[c].start > cur:
                out[group].append((cur, spans[c].start))
            cur = max(cur, spans[c].end)
        if s.end > cur:
            out[group].append((cur, s.end))
    return {g: np.asarray(v, float).reshape(-1, 2) for g, v in out.items()}


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Summed length of the intersections of intervals ``a`` with ``b``."""
    if not len(a) or not len(b):
        return 0.0
    lo = np.maximum(a[:, None, 0], b[None, :, 0])
    hi = np.minimum(a[:, None, 1], b[None, :, 1])
    return float(np.clip(hi - lo, 0.0, None).sum())


def split_idle(gaps: np.ndarray, spans) -> dict:
    """Seconds of ``gaps`` by group, by exact overlap with the innermost
    span covering each part; what no span covers, and ``step``'s own self
    time, is ``unspanned``. The groups add up to the gaps' summed length."""
    gaps = np.asarray(gaps, float).reshape(-1, 2)
    by = self_intervals(spans)
    out = {g: overlap(gaps, by[g]) if g in by else 0.0 for g in GROUPS}
    total = float((gaps[:, 1] - gaps[:, 0]).sum())
    out["unspanned"] += total - sum(out.values())
    return out


def idle_by_phase(run):
    """Milliseconds per tick the device idled, by group of phases: the idle
    gaps of ``run["trace"]`` split over the spans, over the ``step`` spans
    that begin in the traced window. ``None`` without any such span."""
    ticks = len(in_window(run, "step"))
    if not ticks:
        return None
    return {g: 1e3 * s / ticks for g, s in split_idle(
        run["trace"].idle_gaps(), of(run)).items()}


def tick_idle_ms(run, group: str):
    by = idle_by_phase(run)
    return None if by is None else by[group]
