"""APX6xx cost tier — abstract HBM-traffic / communication / FLOP
interpreter over registered trace entries.

The repo's byte claims are roofline arguments: the optimizer ladder is
priced in GB/step, the decode tokens/s ceiling follows from a ~2.3
GB/step HBM read. A jaxpr is a complete statement of what a step reads,
writes, and communicates, so this module *computes* those bytes per
registered entrypoint and ``budgets.py`` gates them against a committed
manifest (APX601-604).

The cost model, per entry (all numbers static, from abstract shapes):

- **read bytes** — the sum over the traced program's top-level inputs
  (invars + closed-over consts). This is the roofline convention: each
  operand is charged ONCE per step, regardless of how many equations
  touch it (XLA re-reads inside a step are a fusion question, not a
  footprint question).
- **write bytes** — the sum over top-level outputs, EXCEPT outputs
  absorbed by a ``pjit`` donation (``donate_argnums``): donation is
  what lets XLA lower a cache update in place, so a donated output is
  charged only its *delta* — the bytes of ``dynamic_update_slice``/
  ``scatter`` update operands inside donated bodies, times loop trip
  counts. A donated KV cache therefore counts once (its read), not
  twice. Pallas ``input_output_aliases`` outputs deliberately still
  charge the full write: the kernel physically rewrites every byte of
  the aliased buffer (the flat optimizer reads g+p+m+v and
  writes p+m+v — aliasing saves the *allocation*, not the traffic).
- **peak live bytes** — a liveness walk over equation order: inputs
  start resident, each equation's outputs join the live set (donation-
  absorbed outputs are free — they land in the donated input's buffer,
  which is kept resident instead), operands are released after their
  last use. Sub-jaxprs (scan/cond/pjit bodies) contribute their inner
  peak minus their inputs as a transient. An upper-ish bound under the
  no-rematerialization schedule XLA actually emits for these programs.
- **collective bytes** — per collective primitive, reusing APX511's
  per-rank schedule simulator: the rank-0 footprint of each
  ``shard_map`` body (which already resolves loop structure and
  per-rank conds) now carries each collective's operand bytes, and the
  fold prices ``bytes x mesh-axis size`` for psum/all_gather/
  reduce_scatter-style rendezvous and ``bytes x hop count`` (the
  permutation's pair count) for ``ppermute``, times loop trip counts.
- **flops** — ``dot_general`` (2·batch·M·N·K from the dimension
  numbers) and ``conv_general_dilated`` (2·out_elems·kernel_window),
  times loop trip counts and pallas grid sizes; everything else is
  free. Arithmetic intensity = flops / total HBM bytes.

Loop conventions: ``scan`` multiplies by its static length; ``while``
counts one iteration (trip counts are dynamic — the manifest pins the
per-iteration cost); ``cond`` takes the most expensive branch.
"""

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from apex_tpu.lint.traced import jaxprlib as jl
from apex_tpu.lint.traced.aliases import _LAYOUT_PRESERVING

# update-primitive -> index of the update operand whose bytes are the
# in-place write delta (operand layouts: dus(operand, update, *starts),
# scatter(operand, indices, updates))
_UPDATE_OPERAND = {
    "dynamic_update_slice": 1,
    "scatter": 2,
    "scatter-add": 2,
    "scatter-mul": 2,
    "scatter-min": 2,
    "scatter-max": 2,
}


@dataclass
class CostReport:
    """Static per-entry cost summary; all byte counts are per step."""
    entry: str
    module: str  # file path of the module the entry exercises
    read_bytes: int = 0
    write_bytes: int = 0        # full-charged (non-donated) outputs
    delta_write_bytes: int = 0  # in-place update traffic under donation
    peak_live_bytes: int = 0
    flops: int = 0
    per_collective: Dict[str, int] = field(default_factory=dict)

    @property
    def collective_bytes(self) -> int:
        return sum(self.per_collective.values())

    @property
    def hbm_total_bytes(self) -> int:
        return self.read_bytes + self.write_bytes + self.delta_write_bytes

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_total_bytes, 1)

    def as_dict(self) -> dict:
        return {
            "entry": self.entry,
            "module": self.module,
            "read_bytes": int(self.read_bytes),
            "write_bytes": int(self.write_bytes),
            "delta_write_bytes": int(self.delta_write_bytes),
            "hbm_total_bytes": int(self.hbm_total_bytes),
            "peak_live_bytes": int(self.peak_live_bytes),
            "collective_bytes": int(self.collective_bytes),
            "per_collective": {k: int(v)
                               for k, v in sorted(self.per_collective.items())},
            "flops": int(self.flops),
            "arithmetic_intensity": round(self.arithmetic_intensity, 3),
        }


def _donation_pairs(eqn) -> List[tuple]:
    """(in_idx, out_idx) pairs a pjit donation actually lands in — the
    same greedy shape/dtype matching XLA (and APX512) applies: each
    output absorbs at most one donated input."""
    donated = eqn.params.get("donated_invars") or ()
    pairs: List[tuple] = []
    if not any(donated):
        return pairs
    taken = [False] * len(eqn.outvars)
    for in_idx, is_donated in enumerate(donated):
        if not is_donated:
            continue
        op_aval = eqn.invars[in_idx].aval
        for out_idx, out in enumerate(eqn.outvars):
            if taken[out_idx]:
                continue
            if (getattr(out.aval, "shape", None) == getattr(
                    op_aval, "shape", None)
                    and getattr(out.aval, "dtype", None) == getattr(
                        op_aval, "dtype", None)):
                taken[out_idx] = True
                pairs.append((in_idx, out_idx))
                break
    return pairs


def _scan_length(eqn) -> int:
    try:
        return max(1, int(eqn.params.get("length")))
    except (TypeError, ValueError):
        return 1


def _pallas_grid(eqn) -> int:
    """Total grid size of a pallas_call (the kernel body runs once per
    grid point); 1 when the traced params don't expose it."""
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None) if gm is not None else None
    if grid is None:
        grid = eqn.params.get("grid")
    n = 1
    try:
        for d in tuple(grid):
            n *= int(d)
    except (TypeError, ValueError):
        return 1
    return max(1, n)


def _dot_flops(eqn) -> int:
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lshape = tuple(eqn.invars[0].aval.shape)
    rshape = tuple(eqn.invars[1].aval.shape)
    batch = 1
    for d in lb:
        batch *= int(lshape[d])
    k = 1
    for d in lc:
        k *= int(lshape[d])
    m = 1
    for i, d in enumerate(lshape):
        if i not in lc and i not in lb:
            m *= int(d)
    n = 1
    for i, d in enumerate(rshape):
        if i not in rc and i not in rb:
            n *= int(d)
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    out_elems = 1
    for d in eqn.outvars[0].aval.shape:
        out_elems *= int(d)
    rhs_elems = 1
    for d in eqn.invars[1].aval.shape:
        rhs_elems *= int(d)
    dn = eqn.params.get("dimension_numbers")
    out_feature_dim = getattr(dn, "rhs_spec", (0,))[0] if dn else 0
    try:
        out_ch = int(eqn.invars[1].aval.shape[out_feature_dim])
    except (IndexError, TypeError):
        out_ch = 1
    # window per output element = kernel elems per output channel
    window = rhs_elems // max(out_ch, 1)
    return 2 * out_elems * window


def _fold_footprint(fp, mult: int, axis_sizes: Dict[str, int],
                    coll: Dict[str, int]) -> None:
    """Price an APX511 footprint: each collective carries its operand
    bytes (item[4]); rendezvous collectives scale by the product of
    their mesh-axis sizes, ppermute by its hop count."""
    for item in fp:
        if item[0] == "coll":
            name, axes, extra = item[1], item[2], item[3]
            nbytes = item[4] if len(item) > 4 else 0
            if name == "ppermute" and extra:
                vol = nbytes * len(extra[0])
            else:
                size = 1
                for ax in axes:
                    size *= int(axis_sizes.get(ax, 1))
                vol = nbytes * size
            coll[name] = coll.get(name, 0) + mult * vol
        elif item[0] == "scan":
            length = item[1]
            try:
                length = max(1, int(length))
            except (TypeError, ValueError):
                length = 1
            _fold_footprint(item[2], mult * length, axis_sizes, coll)
        elif item[0] == "while":
            _fold_footprint(item[1], mult, axis_sizes, coll)
            _fold_footprint(item[2], mult, axis_sizes, coll)


def _collective_volume(eqn, mult: int, acc: dict) -> None:
    from apex_tpu.lint.traced import schedule

    mesh = eqn.params.get("mesh")
    try:
        axis_sizes = dict(mesh.shape)
    except Exception:  # noqa: BLE001 - abstract mesh; price axes at 1
        axis_sizes = {}
    rank0 = {ax: 0 for ax in axis_sizes}
    try:
        fp = schedule._footprint(eqn.params["jaxpr"], {}, rank0)
    except Exception:  # noqa: BLE001 - unverifiable body prices at 0
        return
    _fold_footprint(fp, mult, axis_sizes, acc["coll"])


def _walk(jaxpr_like, mult: int, in_donated: bool, in_shard_map: bool,
          acc: dict) -> None:
    """Accumulate flops, in-place update deltas, and collective volume
    over one jaxpr, scaled by the enclosing loop multiplier."""
    jaxpr = jl.open_jaxpr(jaxpr_like)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            acc["flops"] += mult * _dot_flops(eqn)
            continue
        if name == "conv_general_dilated":
            acc["flops"] += mult * _conv_flops(eqn)
            continue
        if name in _UPDATE_OPERAND:
            if in_donated:
                idx = _UPDATE_OPERAND[name]
                if idx < len(eqn.invars):
                    acc["delta"] += mult * jl.aval_bytes(
                        eqn.invars[idx].aval)
            continue
        if name == "shard_map":
            if not in_shard_map:
                _collective_volume(eqn, mult, acc)
            _walk(eqn.params["jaxpr"], mult, in_donated, True, acc)
            continue
        if name == "scan":
            _walk(eqn.params["jaxpr"], mult * _scan_length(eqn),
                  in_donated, in_shard_map, acc)
            continue
        if name == "cond":
            best: Optional[dict] = None
            for _, sub in jl.sub_jaxprs(eqn):
                branch = {"flops": 0, "delta": 0, "coll": {}}
                _walk(sub, mult, in_donated, in_shard_map, branch)
                if best is None or (branch["flops"] + branch["delta"]
                                    > best["flops"] + best["delta"]):
                    best = branch
            if best is not None:
                acc["flops"] += best["flops"]
                acc["delta"] += best["delta"]
                for k, v in best["coll"].items():
                    acc["coll"][k] = acc["coll"].get(k, 0) + v
            continue
        if name == "jit":
            donated = in_donated or any(
                eqn.params.get("donated_invars") or ())
            for _, sub in jl.sub_jaxprs(eqn):
                _walk(sub, mult, donated, in_shard_map, acc)
            continue
        if name == "pallas_call":
            grid = _pallas_grid(eqn)
            for _, sub in jl.sub_jaxprs(eqn):
                _walk(sub, mult * grid, in_donated, in_shard_map, acc)
            continue
        for _, sub in jl.sub_jaxprs(eqn):
            _walk(sub, mult, in_donated, in_shard_map, acc)


def _peak_live(jaxpr_like, inplace_out=frozenset(), depth: int = 0) -> int:
    """Liveness walk over equation order; see module doc."""
    if depth > 16:
        return 0
    jaxpr = jl.open_jaxpr(jaxpr_like)
    producers = {ov: e for e in jaxpr.eqns for ov in e.outvars}

    # outputs backed by a donated input's buffer are free: chase each
    # back through layout-preserving views to the var that fills it
    credit = set()
    for ov in inplace_out:
        v, hops = ov, 0
        while True:
            credit.add(v)
            e = producers.get(v)
            if (e is None or e.primitive.name not in _LAYOUT_PRESERVING
                    or not e.invars or jl.is_literal(e.invars[0])):
                break
            v = e.invars[0]
            hops += 1
            if hops > 32:
                break

    immortal = {v for v in jaxpr.outvars if not jl.is_literal(v)}
    for e in jaxpr.eqns:
        if e.primitive.name == "jit":
            for in_idx, _ in _donation_pairs(e):
                if not jl.is_literal(e.invars[in_idx]):
                    # the donated buffer IS the output: never released
                    immortal.add(e.invars[in_idx])

    last_use: Dict[object, int] = {}
    for i, e in enumerate(jaxpr.eqns):
        for v in e.invars:
            if not jl.is_literal(v):
                last_use[v] = i

    start = {v for v in list(jaxpr.invars) + list(jaxpr.constvars)}
    cur = sum(jl.aval_bytes(v.aval) for v in start)
    peak = cur
    released = set()
    for i, e in enumerate(jaxpr.eqns):
        inplace_idx = set()
        extra = 0
        if e.primitive.name == "jit":
            pairs = _donation_pairs(e)
            inplace_idx = {oi for _, oi in pairs}
            body = e.params.get("jaxpr")
            if body is not None:
                bj = jl.open_jaxpr(body)
                inner_inplace = frozenset(
                    bj.outvars[oi] for _, oi in pairs
                    if oi < len(bj.outvars)
                    and not jl.is_literal(bj.outvars[oi]))
                inner = _peak_live(body, inner_inplace, depth + 1)
                inputs = sum(jl.aval_bytes(v.aval) for v in e.invars
                             if not jl.is_literal(v))
                extra = max(0, inner - inputs)
        else:
            inputs = sum(jl.aval_bytes(v.aval) for v in e.invars
                         if not jl.is_literal(v))
            for _, sub in jl.sub_jaxprs(e):
                extra = max(extra,
                            _peak_live(sub, frozenset(), depth + 1)
                            - inputs)
            extra = max(0, extra)
        produced = 0
        for oi, ov in enumerate(e.outvars):
            if ov in credit or oi in inplace_idx:
                continue
            produced += jl.aval_bytes(ov.aval)
        cur += produced
        peak = max(peak, cur + extra)
        for v in {v for v in e.invars if not jl.is_literal(v)}:
            if v in immortal or v in released or v in credit:
                continue
            if last_use.get(v) == i:
                released.add(v)
                cur -= jl.aval_bytes(v.aval)
    return peak


def compute(closed, path: str, entry: str) -> CostReport:
    """Cost report for one traced entry (output of jax.make_jaxpr)."""
    jaxpr = jl.open_jaxpr(closed)

    seen = set()
    read = 0
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if v in seen:
            continue
        seen.add(v)
        read += jl.aval_bytes(v.aval)

    # top-level outputs absorbed by a donation, propagated forward
    # through layout-preserving views to the jaxpr outvars
    inplace = set()
    for e in jaxpr.eqns:
        if e.primitive.name == "jit":
            for _, out_idx in _donation_pairs(e):
                inplace.add(e.outvars[out_idx])
    changed = True
    while changed:
        changed = False
        for e in jaxpr.eqns:
            if (e.primitive.name in _LAYOUT_PRESERVING and e.invars
                    and not jl.is_literal(e.invars[0])
                    and e.invars[0] in inplace):
                for ov in e.outvars:
                    if ov not in inplace:
                        inplace.add(ov)
                        changed = True

    write = 0
    for v in jaxpr.outvars:
        if jl.is_literal(v) or v in inplace:
            continue
        write += jl.aval_bytes(v.aval)

    acc = {"flops": 0, "delta": 0, "coll": {}}
    _walk(jaxpr, 1, False, False, acc)
    peak = _peak_live(jaxpr)

    return CostReport(
        entry=entry, module=path, read_bytes=read, write_bytes=write,
        delta_write_bytes=acc["delta"], peak_live_bytes=peak,
        flops=acc["flops"], per_collective=acc["coll"])


def render_table(reports: List[CostReport]) -> str:
    """The ``--cost --report`` JSON payload."""
    return json.dumps(
        {"entries": [r.as_dict() for r in
                     sorted(reports, key=lambda r: r.entry)]},
        indent=2, sort_keys=True)
