"""From a profiler trace (``.xplane.pb``) to what the metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced span;
``jax.profiler.ProfileData`` reads it with nothing but JAX. What a TPU v5e
trace holds (looked at by hand, PR 23):

- one plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules`` (one
  event per execution of a compiled program, named ``jit_<fn>(<hash>)``),
  ``XLA Ops`` (every operation the TensorCore ran, named by its HLO text,
  ``%name = shape op(operands)``; a Pallas kernel is a ``custom-call``) and
  ``Async XLA Ops`` (copies and collectives in flight, start to done, beside
  the TensorCore's own work);
- a plane ``/host:CPU`` whose line ``python`` holds the main thread: the
  ``TraceAnnotation`` spans the benchmark writes (``bench:<name>``) and the
  Python functions entered (``$file.py:line function``), on the same clock.

Busy is the union of the ``XLA Ops`` intervals; the window runs from the
first to the last thing the benchmark or the device did in the trace; an idle
gap is labelled by the benchmark span it lies in and the innermost Python
function that covers it.
"""

import collections
import glob
import os
import re

import numpy as np

_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
_HASH = re.compile(r"\(\d+\)$")
MIN_GAP_S = 20e-6        # shorter gaps are the device's own between ops


_LAYOUT = re.compile(r"\{[^}]*\}")
_OP = re.compile(r"^(%\S+) = (.*?) ([a-z][\w\-]*)\(")


def short(name: str, limit: int = 96) -> str:
    """An HLO instruction's text as ``%name op shape``: layouts and operands
    dropped."""
    m = _OP.match(_LAYOUT.sub("", name))
    if not m:
        return name[:limit]
    return f"{m.group(1)} {m.group(3)} {m.group(2)}"[:limit]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge (n, 2) start/end rows into disjoint sorted intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.nonzero(new)[0][1:] - 1, [len(iv) - 1]])
    return np.stack([starts, ends[last]], 1)


def _measure(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def _subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j, 1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k, 0] < e:
            if b[k, 0] > cur:
                out.append((cur, b[k, 0]))
            cur = max(cur, b[k, 1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return np.asarray(out, float).reshape(-1, 2)


class Chip:
    """One device plane, reduced."""

    def __init__(self, plane):
        self.name = plane.name
        self.modules = []            # (program, start_s, dur_s)
        self.op_time = collections.Counter()    # HLO text -> seconds
        self.op_count = collections.Counter()
        ops, compute, coll = [], [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    self.modules.append((_HASH.sub("", e.name), e.name,
                                         e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9))
            elif line.name == "XLA Ops":
                for e in line.events:
                    s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                    self.op_time[e.name] += d
                    self.op_count[e.name] += 1
                    ops.append((s, s + d))
                    (coll if _COLLECTIVE.search(e.name)
                     else compute).append((s, s + d))
            elif line.name == "Async XLA Ops":
                for e in line.events:
                    if _COLLECTIVE.search(e.name):
                        s = e.start_ns * 1e-9
                        coll.append((s, s + e.duration_ns * 1e-9))
        self.busy = _union(np.asarray(ops, float).reshape(-1, 2))
        self.compute = _union(np.asarray(compute, float).reshape(-1, 2))
        self.collective = _union(np.asarray(coll, float).reshape(-1, 2))

    @property
    def span(self):
        if not len(self.busy):
            return None
        return float(self.busy[0, 0]), float(self.busy[-1, 1])

    def program_times(self, program: str):
        """Device seconds of each execution of ``jit_<program>``."""
        return [d for p, _, _, d in self.modules if p == program]

    def kernel_time(self, match) -> tuple:
        """(seconds, calls) of the operations whose HLO text ``match``
        accepts."""
        names = [n for n in self.op_time if match(n)]
        return (sum(self.op_time[n] for n in names),
                sum(self.op_count[n] for n in names))


class Host:
    """The main thread of the host plane: benchmark spans and Python
    functions, as (name, start, end)."""

    def __init__(self, planes):
        self.bench, self.python = [], []
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    n = e.name
                    if n.startswith("bench:"):
                        s = e.start_ns * 1e-9
                        self.bench.append((n[6:], s,
                                           s + e.duration_ns * 1e-9))
                    elif n.startswith("$") and line.name == "python":
                        s = e.start_ns * 1e-9
                        self.python.append((n, s, s + e.duration_ns * 1e-9))
        self.python.sort(key=lambda r: r[1])
        self._py_starts = np.asarray([r[1] for r in self.python])

    def label(self, s: float, e: float) -> str:
        """What the host was doing in [s, e): the benchmark span that
        overlaps it most, and the innermost Python function covering nine
        tenths of it."""
        best, name = 0.0, "outside_any_span"
        for n, bs, be in self.bench:
            ov = min(e, be) - max(s, bs)
            if ov > best:
                best, name = ov, n
        need = 0.9 * (e - s)
        inner, inner_dur = None, float("inf")
        hi = int(np.searchsorted(self._py_starts, e))
        for n, ps, pe in self.python[max(0, hi - 4000):hi]:
            if min(e, pe) - max(s, ps) >= need and pe - ps < inner_dur:
                inner, inner_dur = n, pe - ps
        return f"{name}|{inner[1:]}" if inner else name


class Reduced:
    """``chips``: the device planes on which something ran. With
    ``may_be_empty`` (a cell that serves requests) there may be none: the
    window is then what the benchmark's own host spans cover, the device
    idled all through it, and whatever asks for device work finds none."""

    def __init__(self, chips, host, may_be_empty: bool = False):
        self.chips = chips
        self.host = host
        spans = [c.span for c in chips if c.span]
        if not spans and not (may_be_empty and host.bench):
            raise ValueError("no operation ran on a device in this trace")
        lo = min([s for s, _ in spans] + [b[1] for b in host.bench])
        hi = max([e for _, e in spans] + [b[2] for b in host.bench])
        self.window = (lo, hi)
        self.window_s = hi - lo
        self.busy_s = float(np.mean([_measure(c.busy) for c in chips])
                            ) if chips else 0.0

    # -- what the metric readers ask ------------------------------------
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_times(self, program: str):
        return [d for c in self.chips for d in c.program_times(program)]

    def programs_summary(self) -> dict:
        out = collections.defaultdict(lambda: [0, 0.0])
        for c in self.chips:
            for p, _, _, d in c.modules:
                out[p][0] += 1
                out[p][1] += d
        n = len(self.chips)
        return {p: {"calls": k // n, "seconds": s / n}
                for p, (k, s) in sorted(out.items(),
                                        key=lambda kv: -kv[1][1])[:12]}

    def kernel_time(self, match) -> tuple:
        """(seconds, calls), averaged over the chips."""
        rows = [c.kernel_time(match) for c in self.chips] or [(0.0, 0)]
        return (float(np.mean([r[0] for r in rows])),
                int(round(np.mean([r[1] for r in rows]))))

    def executions(self, program: str) -> int:
        """How often each chip ran ``jit_<program>``."""
        return len(self.program_times(program)) // len(self.chips)

    def mosaic_kernels(self, keep) -> tuple:
        """(seconds, calls) of the Pallas kernels whose HLO text ``keep``
        accepts: the ``custom-call``s that take time (50 ns a call or more;
        the smallest kernel seen took 250). XLA also writes custom calls of
        no length at all (layout and buffer markers, thousands a step)."""
        chip = self.chips[0]
        names = {n for n in chip.op_time
                 if " custom-call(" in _LAYOUT.sub("", n) and keep(n)
                 and chip.op_time[n] > 50e-9 * chip.op_count[n]}
        return self.kernel_time(lambda n: n in names)

    def collective_exposed_s(self) -> float:
        """Seconds in collectives during which no other operation ran on
        that chip, averaged over the chips."""
        return float(np.mean([_measure(_subtract(c.collective, c.compute))
                              for c in self.chips]))

    def custom_calls(self) -> dict:
        """Every ``custom-call`` (a Pallas kernel, on this chip) with its
        calls and seconds on the first chip: for looking at by hand."""
        if not self.chips:
            return {}
        c = self.chips[0]
        return {short(n, 160): [c.op_count[n], c.op_time[n]]
                for n in c.op_time if " custom-call(" in _LAYOUT.sub("", n)}

    def collective_s(self) -> float:
        return float(np.mean([_measure(c.collective) for c in self.chips]))

    def idle_gaps(self):
        """(start, end) of the first chip's idle gaps inside the window."""
        whole = np.asarray([self.window], float)
        gaps = _subtract(whole, self.chips[0].busy) if self.chips else whole
        return gaps[(gaps[:, 1] - gaps[:, 0]) >= MIN_GAP_S]

    def breakdown(self) -> dict:
        ops = collections.Counter()
        for c in self.chips:
            for n, s in c.op_time.items():
                label = short(n)
                # a loop or a branch holds the operations listed beside it
                if label.split(" ")[1:2] not in (["while"], ["conditional"],
                                                 ["call"]):
                    ops[label] += s / len(self.chips)
        gaps = collections.Counter()
        for s, e in self.idle_gaps():
            gaps[self.host.label(s, e)] += e - s
        return {"device_ops": ops.most_common(10),
                "idle_gaps": gaps.most_common(10)}


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_file(path: str, may_be_empty: bool = False) -> Reduced:
    """Reduce one ``.xplane.pb`` (or ``.xplane.pb.gz``: the recorded trace
    the tests read is kept compressed)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = list(data.planes)
    chips = [Chip(p) for p in planes if p.name.startswith("/device:TPU:")]
    chips = [c for c in chips if c.span]
    return Reduced(chips, Host(planes), may_be_empty)


def reduce_dir(trace_dir: str, may_be_empty: bool = False) -> Reduced:
    return reduce_file(find(trace_dir), may_be_empty)
