"""Device time by region of the program, out of the same ``.xplane.pb`` the
device planes are in.

The program opens a ``jax.named_scope`` per REGION of its device work
(``apex_tpu/utils/profiler.py::REGIONS``: ``embed``, ``attention``, ``mlp``,
...) where that work is written. A scope is a component of an operation's
name in the lowered module, and the device trace keeps it: every ``XLA Ops``
event's METADATA carries the stat ``tf_op``, the operation's path
(``jit(train_step)/jvp(layer0)/attention/bhqd,bhkd->bhqk/dot_general:``)
beside ``program_id``. ``jax.profiler.ProfileData`` gives an event's own
stats only, so the metadata is read here from the file's wire format: a
plane's ``lines`` are skipped by their length, ``event_metadata`` and
``stat_metadata`` are decoded. The events' times still come from
``ProfileData``; an event finds its metadata by the program it ran in (the
``XLA Modules`` interval that holds it, whose name ends in the program's id)
and its name (the HLO text). The runner builds the program and is not
touched: the trace file is the only channel, and a program that opens no
region (the parent of PR 38, or an executable the compile cache kept from
it: scope names are no part of the cache's key) reads ``None`` in every
reader, with a line on standard error, never "all of it unscoped".

An operation counts under the FIRST component of its path that is a region,
``jvp(..)`` / ``transpose(..)`` and the like unwrapped; ``jit(..)``,
``while/body``, ``layer{i}`` and a kernel's own ``apex_<kernel>`` scope are
no regions and are passed over, so a kernel's time counts under the region
around its call. ``while`` / ``conditional`` / ``call`` events hold the
operations listed beside them and are left out (``trace.Reduced.breakdown``
does the same). Within a family the groups add up exactly to the summed
``XLA Ops`` time inside the program's executions.
"""

import collections
import os
import re
import sys
import time

import numpy as np

from benchmark import trace

#: this file's own copy of the program's vocabulary (``spans.GROUP_OF`` is
#: the host side's): a region the program adds and this list lacks counts
#: as ``unscoped`` until a benchmark PR adds it here
REGIONS = ("embed", "attention", "mixer", "mlp", "router", "experts", "head",
           "loss", "amp", "optimizer", "grad_sync", "cache_write")

#: family -> region -> the group a metric reports. A region a family's
#: programs do not hold today is still mapped, so the groups always add up.
GROUP_OF = {
    "train": {"embed": "embed", "attention": "attention", "mixer": "attention",
              "cache_write": "attention", "mlp": "mlp", "router": "mlp",
              "experts": "mlp", "head": "head_loss", "loss": "head_loss",
              "amp": "amp", "optimizer": "optimizer",
              "grad_sync": "grad_sync"},
    "serve": {"attention": "attention", "cache_write": "attention",
              "mixer": "mixer", "mlp": "mlp", "router": "experts",
              "experts": "experts", "head": "head", "embed": "head",
              "loss": "unscoped", "amp": "unscoped", "optimizer": "unscoped",
              "grad_sync": "unscoped"},
}
GROUPS = {
    "train": ("embed", "attention", "mlp", "head_loss", "amp", "optimizer",
              "grad_sync", "unscoped"),
    "serve": ("attention", "mixer", "mlp", "experts", "head", "unscoped"),
}

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")     # jit_decode(1234567)
_HELD = (["while"], ["conditional"], ["call"])


def region_of(tf_op: str):
    """The first component of an operation's path that is a region, or
    ``None``. ``jvp(embed)`` and ``transpose(jvp(embed))`` are ``embed``;
    ``jit(mlp)`` is a function's name and no region."""
    for part in tf_op.split("/"):
        if part.startswith(("jit(", "pjit(")):
            continue
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part in REGIONS:
            return part
    return None


def backward(tf_op: str) -> bool:
    """Does the path lie in the transposed (backward) half of a gradient?"""
    return "transpose(" in tf_op


# ---------------------------------------------------------------------------
# the wire format: just enough of protobuf to reach a plane's metadata
# ---------------------------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, wire type, value) of one message: a varint's value,
    or the (start, end) of a length-delimited field, which is not read."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of a map entry."""
    for field, wire, value in _fields(buf, *span):
        if field == 2 and wire == 2:
            return value
    return None


def _stat_names(buf, spans) -> dict:
    """``stat_metadata``: id -> name."""
    out = {}
    for span in spans:
        value = _map_value(buf, span)
        if value is None:
            continue
        sid, name = 0, ""
        for field, wire, v in _fields(buf, *value):
            if field == 1 and wire == 0:
                sid = v
            elif field == 2 and wire == 2:
                name = _text(buf, v)
        out[sid] = name
    return out


def _event_metadata(buf, spans, stat_names) -> dict:
    """``event_metadata``: (program id or None, name) -> ``tf_op`` (""
    where the operation has none: a copy the compiler added)."""
    ids = {name: sid for sid, name in stat_names.items()}
    tf_op_id, program_id = ids.get("tf_op"), ids.get("program_id")
    out = {}
    for span in spans:
        value = _map_value(buf, span)
        if value is None:
            continue
        name, tf_op, program = "", "", None
        for field, wire, v in _fields(buf, *value):
            if field == 2 and wire == 2:
                name = _text(buf, v)
            elif field == 5 and wire == 2:          # an XStat
                sid, text, number = None, None, None
                for f, w, sv in _fields(buf, *v):
                    if f == 1 and w == 0:
                        sid = sv
                    elif f == 5 and w == 2:         # str_value
                        text = _text(buf, sv)
                    elif f == 7 and w == 0:         # ref_value
                        text = stat_names.get(sv, "")
                    elif f in (3, 4) and w == 0:    # uint64 / int64
                        number = sv
                if sid is not None and sid == tf_op_id:
                    tf_op = text or ""
                elif sid is not None and sid == program_id:
                    program = number
        out[(program, name)] = tf_op
    return out


def metadata(data) -> dict:
    """plane name -> {(program id, HLO text) -> ``tf_op``} for the device
    planes of a serialized ``XSpace``."""
    buf = memoryview(data)
    out = {}
    for field, wire, plane in _fields(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        name, events, stats = "", [], []
        for f, w, v in _fields(buf, *plane):
            if f == 2 and w == 2:
                name = _text(buf, v)
            elif f == 4 and w == 2:
                events.append(v)
            elif f == 5 and w == 2:
                stats.append(v)
        if name.startswith("/device:TPU:"):
            out[name] = _event_metadata(buf, events, _stat_names(buf, stats))
    return out


# ---------------------------------------------------------------------------
# one trace, by program and operation
# ---------------------------------------------------------------------------

Op = collections.namedtuple("Op", "seconds count tf_op region")


class Table:
    """``ops``: (program, HLO text) -> ``Op``, seconds and count summed over
    the program's executions in the trace and averaged over the chips, the
    held operations (``while`` ...) left out; ``executions``: program -> how
    often each chip ran it; ``module_s``: program -> its ``XLA Modules``
    seconds, averaged over the chips; ``left_out_s``: the seconds of the
    held operations and of operations between two programs, which no
    program's groups count; ``read_s``: what reading took."""

    def __init__(self, ops, executions, module_s, left_out_s, read_s):
        self.ops, self.executions = ops, executions
        self.module_s, self.left_out_s = module_s, left_out_s
        self.read_s = read_s
        self._said = set()

    def regions(self, program: str):
        """region (``None``: under no region) -> seconds, or ``None`` for a
        program that ran nothing or carries no region at all."""
        out = collections.Counter()
        for (prog, _), op in self.ops.items():
            if prog == program:
                out[op.region] += op.seconds
        if set(out) - {None}:
            return dict(out)
        if program not in self._said:
            self._said.add(program)
            print(f"benchmark/regions.py: {program} "
                  + ("did not run in the traced span" if not out else
                     "carries no region scope in this trace's operation "
                     "metadata (a program from before the regions, or an "
                     "executable a shared compile cache kept from one): its "
                     "region metrics are left out"), file=sys.stderr)
        return None

    def groups(self, program: str, family: str):
        """group -> seconds over the trace, every group of the family
        present; they add up to the program's summed operation time."""
        by_region = self.regions(program)
        if by_region is None:
            return None
        out = dict.fromkeys(GROUPS[family], 0.0)
        for region, seconds in by_region.items():
            out[GROUP_OF[family].get(region, "unscoped")] += seconds
        return out

    def directions(self, program: str) -> dict:
        """(region, "fwd" | "bwd") -> seconds: for ``PERF.md``, no metric."""
        out = collections.Counter()
        for (prog, _), op in self.ops.items():
            if prog == program:
                out[(op.region, "bwd" if backward(op.tf_op) else "fwd")] \
                    += op.seconds
        return dict(out)


def _held(name: str) -> bool:
    return trace.short(name).split(" ")[1:2] in _HELD


def load(path: str) -> Table:
    """Read one ``.xplane.pb`` (or ``.gz``)."""
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            raw = f.read()
    else:
        with open(path, "rb") as f:
            raw = f.read()
    meta = metadata(raw)
    data = ProfileData.from_serialized_xspace(raw)
    seconds, counts = collections.Counter(), collections.Counter()
    tf_ops, runs, module_s = {}, collections.Counter(), collections.Counter()
    chips, left_out = 0, 0.0
    for plane in data.planes:
        if plane.name not in meta:
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(e.start_ns, e.duration_ns, e.name)
                       for e in line.events]
        if not ops:
            continue
        chips += 1
        modules.sort()
        starts = np.asarray([m[0] for m in modules], float)
        ends = np.asarray([m[1] for m in modules], float)
        named = []              # (program, its id) of each execution
        for s, e, full in modules:
            m = _PROGRAM.match(full)
            named.append((m.group(1), int(m.group(2))) if m else (full, None))
            runs[named[-1][0]] += 1
            module_s[named[-1][0]] += (e - s) * 1e-9
        at = np.searchsorted(starts, np.asarray([o[0] for o in ops], float),
                             side="right") - 1
        by_plane = meta[plane.name]
        held = {}
        for (s, d, name), i in zip(ops, at):
            if name not in held:
                held[name] = _held(name)
            if held[name] or i < 0 or s >= ends[i]:
                left_out += d * 1e-9    # holds others, or no program's
                continue
            program, pid = named[i]
            key = (program, name)
            seconds[key] += d * 1e-9
            counts[key] += 1
            if key not in tf_ops:
                tf_ops[key] = by_plane.get(
                    (pid, name), by_plane.get((None, name), ""))
    n = max(chips, 1)
    ops = {key: Op(seconds[key] / n, counts[key] / n, tf_ops[key],
                   region_of(tf_ops[key])) for key in seconds}
    return Table(ops, {p: k // n for p, k in runs.items()},
                 {p: s / n for p, s in module_s.items()}, left_out / n,
                 time.perf_counter() - t0)


def of(run):
    """The table of this run's trace, read once and kept on ``run``;
    ``None`` for a run that names no cell, and so no trace file."""
    if "apex_regions" not in run:
        cell = run.get("cell")
        if cell is None:
            return None
        run["apex_regions"] = load(trace.find(os.path.join(
            cell.root, ".bench_trace", cell.name)))
        print(f"benchmark/regions.py: read the trace's operation metadata "
              f"in {run['apex_regions'].read_s:.2f} s", file=sys.stderr)
    return run["apex_regions"]


# ---------------------------------------------------------------------------
# what the metric readers ask
# ---------------------------------------------------------------------------

def _per_execution(run, program: str, family: str, group: str):
    table = of(run)
    groups = table and table.groups(program, family)
    runs = table and table.executions.get(program, 0)
    if not groups or not runs:
        return None
    return 1e3 * groups[group] / runs


def train_step_ms(run, group: str):
    """Device milliseconds of ``group`` per execution of
    ``jit_train_step``."""
    return _per_execution(run, "jit_train_step", "train", group)


def decode_ms(run, group: str):
    """Device milliseconds of ``group`` per execution of ``jit_decode``
    (the MEAN, so that the family adds up)."""
    return _per_execution(run, "jit_decode", "serve", group)


def bucket_for(n: int, buckets) -> int:
    return min(b for b in buckets if b >= n)


def prefill_ms_per_ktok(run, group: str):
    """Device milliseconds of ``group`` in ``jit_prefill`` (every bucket)
    per thousand bucket tokens, the tokens counted as
    ``metrics/prefill_device_ms_per_ktok.py`` counts them: the buckets of the
    prompts whose first token was delivered inside the traced span."""
    counts = run["counts"]
    span = counts.get("traced")
    if not span:
        return None
    table = of(run)
    groups = table and table.groups("jit_prefill", "serve")
    if not groups:
        return None
    tokens = sum(bucket_for(counts["prompt_tokens"][i], counts["buckets"])
                 for i, t in counts["first_delivery"].items()
                 if span[0] <= t <= span[1])
    return 1e3 * groups[group] / (tokens / 1e3) if tokens else None
