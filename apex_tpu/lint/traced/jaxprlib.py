"""Shared jaxpr-walking tools for the trace tier.

Every APX5xx verifier operates on the output of ``jax.make_jaxpr`` over
a registered entrypoint and has to see through the same set of
higher-order primitives: ``pjit`` (closed sub-jaxpr), ``scan``/``while``
(ClosedJaxpr body + carry structure), ``cond`` (tuple of branch
ClosedJaxprs), ``shard_map`` (open Jaxpr body), ``remat``/``custom_vjp``
wrappers, and ``pallas_call`` (the kernel body itself). This module
centralizes that traversal so each checker only writes its per-equation
logic.

``sub_jaxprs(eqn)`` is deliberately generic — any equation parameter
that *is* a Jaxpr/ClosedJaxpr (or a tuple/list of them) is yielded — so
a new higher-order primitive degrades to "recursed into" rather than
"silently skipped".
"""

from typing import Iterator, List, Tuple


def _jaxpr_types():
    from jax.extend.core import ClosedJaxpr, Jaxpr

    return Jaxpr, ClosedJaxpr


def open_jaxpr(j):
    """Jaxpr from either a Jaxpr or a ClosedJaxpr."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def sub_jaxprs(eqn) -> List[Tuple[str, object]]:
    """``[(param_name, jaxpr-or-closed), ...]`` for one equation."""
    Jaxpr, ClosedJaxpr = _jaxpr_types()
    out = []
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, (Jaxpr, ClosedJaxpr)):
                out.append((name, v))
    return out


def all_eqns(jaxpr, *, into_pallas: bool = True) -> Iterator[object]:
    """Depth-first over every equation, recursing into sub-jaxprs."""
    for eqn in open_jaxpr(jaxpr).eqns:
        yield eqn
        if not into_pallas and eqn.primitive.name == "pallas_call":
            continue
        for _, sub in sub_jaxprs(eqn):
            yield from all_eqns(sub, into_pallas=into_pallas)


def is_literal(v) -> bool:
    from jax.extend.core import Literal

    return isinstance(v, Literal)


def aval_bytes(aval) -> int:
    """Byte size of an abstract value; 0 when it has no shape/dtype
    (tokens, refs without inner avals, effects)."""
    import numpy as np

    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # symbolic dim
            return 0
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        return 0


def is_sub_fp32(aval) -> bool:
    """True for float dtypes narrower than 32 bits (bf16/f16/fp8)."""
    import numpy as np

    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        np_dtype = np.dtype(dtype)
    except TypeError:
        return False
    # bfloat16/fp8 are ml_dtypes extension types: np.issubdtype sees
    # them as void, so classify by jax's own lattice instead.
    import jax.numpy as jnp

    return bool(jnp.issubdtype(dtype, jnp.floating)) and np_dtype.itemsize < 4


def scalar_literal(v):
    """Python value of a scalar Literal, else None."""
    if not is_literal(v):
        return None
    if getattr(v.aval, "shape", None) not in ((), None):
        return None
    try:
        return v.val.item() if hasattr(v.val, "item") else v.val
    except (ValueError, AttributeError):
        return None


def axis_names(params, key: str = "axis_name"):
    """Normalize a collective's axis-name param to a tuple of names.

    jax stores it as a bare name, a tuple, or (psum) under ``axes``.
    """
    ax = params.get(key, params.get("axes", params.get("axis_name")))
    if ax is None:
        return ()
    if isinstance(ax, (tuple, list)):
        return tuple(ax)
    return (ax,)
