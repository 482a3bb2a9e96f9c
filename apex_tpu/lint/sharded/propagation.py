"""APX703 — rule-derived specs must survive into the staged program.

A rule table can be internally consistent (APX701/702 clean) and still
never reach the compiler: a train step whose ``shard_map`` was wired
with stale hand-written ``in_specs`` shards nothing the table says it
should. This check stages the entry's builder under its mesh with
``jax.make_jaxpr`` (abstract — no compile, no devices touched beyond
the CPU world) and verifies, per flattened operand, that the traced
``shard_map`` equation's ``in_specs`` give the dim->axes mapping of
the expected ``PartitionSpec`` the builder derived from the table.

It also walks the shard_map body for the classic silent failure GSPMD
makes easy: an operand that arrives FULLY REPLICATED (an empty
spec), is at least ``replication_floor`` bytes, and flows into
a ``dot_general`` — i.e. a weight matrix every rank stores and
multiplies whole. Taint propagates only through layout-preserving ops
(convert/transpose/reshape/...) and inlined calls, so the finding
names an actual matmul operand, not everything downstream of it.
"""

from typing import Any, List

from jax.sharding import PartitionSpec

from apex_tpu.lint import Finding
from apex_tpu.lint.traced import jaxprlib as jl

# ops a replicated operand passes through without changing what it is
_TAINT_THROUGH = {
    "convert_element_type", "transpose", "reshape", "squeeze",
    "broadcast_in_dim", "copy", "stop_gradient", "expand_dims",
}


def spec_to_names(spec: PartitionSpec) -> dict:
    """One spec as ``{dim: (axis, ...)}`` with replicated dims absent
    (so ``P("x")`` and ``P("x", None)`` compare equal)."""
    names = {}
    for dim, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        names[dim] = tuple(entry) if isinstance(entry, tuple) else (entry,)
    return names


def _flat_expected(in_specs: Any) -> List[PartitionSpec]:
    import jax

    return jax.tree_util.tree_leaves(
        in_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))


def _replicated_dot_operands(body, seeds) -> List[tuple]:
    """(label, nbytes) per tainted var consumed by a dot_general,
    recursing through inlined calls."""
    hits: List[tuple] = []
    jaxpr = jl.open_jaxpr(body)
    tainted = dict(seeds)  # var -> (label, nbytes)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            for v in eqn.invars:
                if not jl.is_literal(v) and v in tainted:
                    hits.append(tainted[v])
            continue
        if name in _TAINT_THROUGH and eqn.invars and not jl.is_literal(
                eqn.invars[0]) and eqn.invars[0] in tainted:
            tainted[eqn.outvars[0]] = tainted[eqn.invars[0]]
            continue
        for _, sub in jl.sub_jaxprs(eqn):
            sj = jl.open_jaxpr(sub)
            if len(sj.invars) != len(eqn.invars):
                continue
            sub_seeds = {sv: tainted[v]
                         for sv, v in zip(sj.invars, eqn.invars)
                         if not jl.is_literal(v) and v in tainted}
            if sub_seeds:
                hits.extend(_replicated_dot_operands(sub, sub_seeds))
    return hits


def check(closed, in_specs: Any, path: str, entry) -> List[Finding]:
    findings: List[Finding] = []
    expected = [spec_to_names(s) for s in _flat_expected(in_specs)]
    matched = False
    for eqn in jl.all_eqns(closed, into_pallas=False):
        if eqn.primitive.name != "shard_map":
            continue
        specs = eqn.params.get("in_specs")
        if specs is None or len(specs) != len(expected):
            continue  # an inner shard_map with a different signature
        matched = True
        actual = [spec_to_names(s) for s in specs]
        for i, (want, got) in enumerate(zip(expected, actual)):
            if got != want:
                aval = eqn.invars[i].aval
                findings.append(Finding(
                    "APX703", path, 1,
                    f"entry '{entry.name}': shard_map operand {i} "
                    f"(shape {tuple(getattr(aval, 'shape', ()))}) "
                    f"traced with in_specs {got} but the rule "
                    f"table derives {want} — the staged program does "
                    f"not shard what the table says"))

        body = eqn.params["jaxpr"]
        bj = jl.open_jaxpr(body)
        floor = entry.replication_floor
        seeds = {}
        for i, (names, bv) in enumerate(zip(actual, bj.invars)):
            if names:
                continue
            nbytes = jl.aval_bytes(bv.aval)
            if nbytes >= floor:
                shape = tuple(getattr(bv.aval, "shape", ()))
                seeds[bv] = (f"operand {i} (shape {shape})", nbytes)
        for label, nbytes in _replicated_dot_operands(body, seeds):
            findings.append(Finding(
                "APX703", path, 1,
                f"entry '{entry.name}': {label}, {nbytes} bytes, "
                f"enters the shard_map body fully replicated and is "
                f"consumed by a dot_general — every rank stores and "
                f"multiplies the whole matrix (silent replication "
                f"above the {floor}-byte floor)"))
    if not matched:
        findings.append(Finding(
            "APX703", path, 1,
            f"entry '{entry.name}': no shard_map equation with "
            f"{len(expected)} operands found in the staged program — "
            f"the rule-derived in_specs were never applied"))
    return findings
