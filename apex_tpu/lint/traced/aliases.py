"""APX512 — declared kernel aliasing must survive into the traced
program.

The flat optimizer kernels declare ``input_output_aliases`` so a step
is one read-modify-write pass over HBM. That declaration is only worth
anything if the aliased *operand* of the lowered ``pallas_call`` is
still the caller's buffer: an intervening copy-producing equation — a
dtype cast, a pad to the block multiple, an arithmetic touch-up —
silently inserts a second buffer, the alias binds to the *copy*, and
HBM traffic doubles with bit-identical numerics. No runtime test can
see it; the traced jaxpr can.

For every ``pallas_call`` equation in the entry's jaxpr, each declared
``(operand, output)`` alias pair is verified:

- the operand and output abstract values agree in shape and dtype
  (an alias between mismatched buffers is rejected by XLA at compile
  time on hardware — on the interpret-mode CPU rig it is ignored);
- the operand's provenance chain, followed through layout-preserving
  equations (``reshape``/``squeeze``/``expand_dims``) and through an
  earlier ``pallas_call``'s own aliased output (kernels chained in place
  on one buffer), terminates at an *invar* of the jaxpr the call sits
  in — i.e. the caller's buffer, not a fresh intermediate.

The same contract covers jit DONATIONS (``donate_argnums``): a traced
``pjit`` equation carries ``donated_invars``, and the serving KV cache
depends on its donation surviving — a dropped donation turns every
decode step's cache update into a fresh ``O(L·B·H·S·d)`` allocation.
Each donated invar must have a shape/dtype-matching output to land in
(XLA only reuses buffers between compatible avals; a donation with no
matching output is silently discarded and the HBM win evaporates).
Donated invars count toward ``min_alias_pairs`` alongside pallas pairs.

Each entry declares ``min_alias_pairs``: if fewer pairs survive into
the trace than the kernel registry promises (e.g. a refactor dropped
the parameter), that is a finding too.
"""

from typing import List

from apex_tpu.lint import Finding
from apex_tpu.lint.traced import jaxprlib as jl

# Producers an alias legitimately traces through: pure layout views.
_LAYOUT_PRESERVING = {"reshape", "squeeze", "expand_dims"}


def _normalize_pairs(raw):
    """``input_output_aliases`` appears as a dict at the pallas API and
    as a tuple of (in_idx, out_idx) pairs in the traced params."""
    if raw is None:
        return []
    if isinstance(raw, dict):
        return sorted(raw.items())
    return sorted((int(i), int(o)) for i, o in raw)


def _trace_to_invar(var, producers, invars) -> str:
    """'' when ``var`` reaches an invar through layout-preserving eqns,
    else the name of the first severing primitive."""
    seen = 0
    while True:
        if jl.is_literal(var):
            return "literal"
        if var in invars:
            return ""
        eqn = producers.get(var)
        if eqn is None:
            return "constvar"  # a closed-over constant, not a live buffer
        if eqn.primitive.name == "pallas_call":
            # a chain of in-place kernels (the recurrent state stepped by
            # one layer's call after another): an output that aliases an
            # operand IS that operand's buffer, so follow it
            at = next(i for i, ov in enumerate(eqn.outvars) if ov is var)
            src = [i for i, o in _normalize_pairs(
                eqn.params.get("input_output_aliases")) if o == at]
            if not src:
                return "pallas_call"
            var = eqn.invars[src[0]]
        elif eqn.primitive.name not in _LAYOUT_PRESERVING:
            return eqn.primitive.name
        else:
            var = eqn.invars[0]
        seen += 1
        if seen > 32:
            return "cycle"


def _check_jaxpr(jaxpr_like, path, entry, counts, findings):
    jaxpr = jl.open_jaxpr(jaxpr_like)
    producers = {ov: e for e in jaxpr.eqns for ov in e.outvars}
    invars = set(jaxpr.invars)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            if eqn.primitive.name == "jit":
                _check_donations(eqn, path, entry, counts, findings)
            for _, sub in jl.sub_jaxprs(eqn):
                _check_jaxpr(sub, path, entry, counts, findings)
            continue
        pairs = _normalize_pairs(eqn.params.get("input_output_aliases"))
        counts[0] += len(pairs)
        for in_idx, out_idx in pairs:
            if in_idx >= len(eqn.invars) or out_idx >= len(eqn.outvars):
                findings.append(Finding(
                    "APX512", path, 1,
                    f"entry '{entry}': alias pair ({in_idx}, {out_idx}) "
                    f"is out of range for a pallas_call with "
                    f"{len(eqn.invars)} operands / "
                    f"{len(eqn.outvars)} outputs"))
                continue
            op, out = eqn.invars[in_idx], eqn.outvars[out_idx]
            op_aval, out_aval = op.aval, out.aval
            if (getattr(op_aval, "shape", None) != getattr(
                    out_aval, "shape", None)
                    or getattr(op_aval, "dtype", None) != getattr(
                        out_aval, "dtype", None)):
                findings.append(Finding(
                    "APX512", path, 1,
                    f"entry '{entry}': alias pair ({in_idx}, {out_idx}) "
                    f"binds mismatched buffers {op_aval} -> {out_aval} "
                    f"— XLA rejects the donation and doubles HBM"))
                continue
            sever = _trace_to_invar(op, producers, invars)
            if sever:
                findings.append(Finding(
                    "APX512", path, 1,
                    f"entry '{entry}': aliased operand {in_idx} of "
                    f"'{_kernel_of(eqn)}' is produced by '{sever}', not "
                    f"the caller's buffer — the declared in-place "
                    f"update writes to a copy and HBM traffic doubles"))


def _check_donations(eqn, path, entry, counts, findings):
    """``pjit`` donations (``donate_argnums``): each donated invar needs
    a shape/dtype-matching output for XLA to land the reuse in — each
    output can absorb at most one donation."""
    donated = eqn.params.get("donated_invars") or ()
    if not any(donated):
        return
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
                counts[0] += 1
                break
        else:
            findings.append(Finding(
                "APX512", path, 1,
                f"entry '{entry}': donated operand {in_idx} of "
                f"'{_kernel_of(eqn)}' ({op_aval}) has no shape/dtype-"
                f"matching output to reuse — XLA discards the donation "
                f"and the update allocates a fresh buffer"))


def _kernel_of(eqn) -> str:
    name = eqn.params.get("name")
    if name:
        return str(name)
    j = eqn.params.get("jaxpr")
    return getattr(j, "name", None) or "pallas_call"


def check(closed, path: str, entry: str, *,
          min_alias_pairs: int = 0) -> List[Finding]:
    findings: List[Finding] = []
    counts = [0]
    _check_jaxpr(closed, path, entry, counts, findings)
    if counts[0] < min_alias_pairs:
        findings.append(Finding(
            "APX512", path, 1,
            f"entry '{entry}': expected at least {min_alias_pairs} "
            f"input_output_aliases pair(s) in the traced program, found "
            f"{counts[0]} — the declared in-place aliasing was dropped "
            f"before lowering"))
    return findings
