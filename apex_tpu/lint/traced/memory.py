"""APX503 — broadcast/materialization blowup.

The classic mixed-precision OOM is not a big *input*, it is a big
*intermediate*: an attention backward that re-materializes the S x S
fp32 score matrix, a one-hot expansion of a label vector against the
vocabulary, a broadcast that XLA cannot fuse because its consumer is a
contraction. None of these are visible in source — the shapes only
exist in the traced program.

The check walks every equation (including scan/cond/pjit sub-jaxprs
and Pallas kernel bodies, where block shapes keep tile-local dot
products under the floor) and flags producers whose output abstract
value is more than ``factor`` times the sum of all operand sizes AND at
least ``floor`` bytes. Two classes of producers are charged:

- contraction/layout primitives that always materialize their output
  (``dot_general``, ``conv_general_dilated``, ``gather``,
  ``concatenate``, ``pad``);
- pure-expansion primitives (``broadcast_in_dim``, ``iota``) only when
  some consumer in the same jaxpr *materializes* them (a contraction, a
  stacked loop, a Pallas call, a jaxpr output). A broadcast feeding
  only elementwise math fuses into its consumer and costs nothing, so
  charging it would flag every ``(h,) -> (b, s, h)`` affine weight.

The ``floor`` (default 1 MiB) keeps tile-sized intermediates, ring
buffers and tiny-model test entries out of scope: a 16x blowup to
200 KiB is not an OOM.
"""

from typing import List

from apex_tpu.lint import Finding
from apex_tpu.lint.traced import jaxprlib as jl

DEFAULT_FACTOR = 8.0
DEFAULT_FLOOR = 1 << 20  # 1 MiB

# Producers whose output always occupies real memory.
_MATERIALIZING_PRODUCERS = {
    "dot_general", "conv_general_dilated", "gather", "concatenate", "pad",
}

# Expansion producers charged only when materialized by a consumer.
_EXPANSION_PRODUCERS = {"broadcast_in_dim", "iota"}


def _mib(n: int) -> str:
    return f"{n / (1 << 20):.2f} MiB"


def _check_one(jaxpr_like, path: str, entry: str, factor: float,
               floor: int, findings: List[Finding]) -> None:
    jaxpr = jl.open_jaxpr(jaxpr_like)
    consumers = {}
    out_set = {v for v in jaxpr.outvars if not jl.is_literal(v)}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not jl.is_literal(v):
                consumers.setdefault(v, set()).add(eqn.primitive.name)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for _, sub in jl.sub_jaxprs(eqn):
            _check_one(sub, path, entry, factor, floor, findings)
        if name in _EXPANSION_PRODUCERS:
            # materialized = escapes the jaxpr, or has any consumer
            # that is not a known-fusible elementwise/reduce/shape op
            # (scan, dot_general, pallas_call, scatter, ... all count)
            materialized = any(
                (v in out_set)
                or any(c not in _FUSIBLE for c in consumers.get(v, set()))
                for v in eqn.outvars)
            if not materialized:
                continue
        elif name not in _MATERIALIZING_PRODUCERS:
            continue
        in_bytes = sum(jl.aval_bytes(v.aval) for v in eqn.invars)
        out_bytes = max((jl.aval_bytes(v.aval) for v in eqn.outvars),
                        default=0)
        if out_bytes >= floor and out_bytes > factor * max(in_bytes, 1):
            findings.append(Finding(
                "APX503", path, 1,
                f"entry '{entry}': {name} materializes "
                f"{_mib(out_bytes)} from {_mib(in_bytes)} of operands "
                f"(> {factor:g}x blowup, shape "
                f"{tuple(eqn.outvars[0].aval.shape)} "
                f"{eqn.outvars[0].aval.dtype}) — a fused/blocked "
                f"formulation keeps this intermediate tile-sized"))


# Consumers known to fuse an expansion producer away: elementwise math,
# reductions, and shape-only ops. Anything NOT in this set counts as
# materializing (conservative for new primitives).
_FUSIBLE = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "integer_pow",
    "neg", "abs", "sign", "exp", "exp2", "log", "log1p", "expm1", "tanh",
    "logistic", "erf", "erf_inv", "erfc", "rsqrt", "sqrt", "cbrt", "sin",
    "cos", "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh", "floor",
    "ceil", "round", "clamp", "is_finite", "not", "and", "or", "xor",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "convert_element_type",
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min", "reduce_and",
    "reduce_or", "cumsum", "cumprod", "cumlogsumexp", "argmax", "argmin",
    "reduce_precision", "broadcast_in_dim", "reshape", "squeeze",
    "expand_dims", "transpose", "rev", "slice", "dynamic_slice", "copy",
    "stop_gradient", "jit", "remat", "remat2", "checkpoint", "nextafter",
    "square", "add_any", "mul_add", "real", "imag", "device_put",
}


def check(closed, path: str, entry: str, *,
          factor: float = DEFAULT_FACTOR,
          floor: int = DEFAULT_FLOOR) -> List[Finding]:
    findings: List[Finding] = []
    _check_one(closed, path, entry, factor, floor, findings)
    return findings
