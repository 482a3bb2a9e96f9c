"""Kernel-contract checks: APX101 (in-place aliasing) and APX103
(fp32 statistics tiles).

**APX101** — the optimizer kernels update state buffers in place; the
whole one-pass-over-HBM design rests on ``input_output_aliases``. The
repo's kernels follow a strict naming convention: an input ref
``X_ref`` whose updated value is written to an output ``X_out`` (same
stem) IS an in-place update, and the ``pallas_call`` must declare the
matching ``{input_operand_index: output_index}`` alias — otherwise XLA
materializes a second buffer and the "donated" state silently doubles
its HBM footprint. The check maps kernel parameters to operands
positionally (inputs = first ``len(in_specs)`` params, outputs next),
so it only fires when the call site's spec lists are statically
countable; ``*refs``-style kernels are skipped, never guessed at.

**APX103** — flash attention keeps its online-softmax statistics
(running max ``m``, normalizer ``l``, logsumexp ``lse``) and layer norm
its ``mean``/``rstd`` in fp32 even though the probability tiles are
cast to bf16: the normalizer sums the fp32 tile *before*
the cast, and a half-precision ``l`` or ``lse`` corrupts every row that
spans more than one k tile. The check flags (a) stores into a
stats-named ref that round through ``astype(bf16/f16)``, (b) stats
scratch buffers allocated below fp32, (c) stats outputs whose
``ShapeDtypeStruct`` dtype is below fp32.
"""

import ast
from typing import Dict, List, Optional

from apex_tpu.lint import Finding
from apex_tpu.lint.astutil import (
    attr_chain,
    call_name,
    functions_in,
    kwarg,
    static_elements,
    static_len,
)

_STATS_STEMS = {"m", "l", "lse", "mean", "rstd"}
_LOW_PRECISION = {"bfloat16", "float16"}


def _stem(param: str) -> str:
    for suffix in ("_ref", "_out"):
        if param.endswith(suffix):
            return param[: -len(suffix)]
    return param


def _kernel_name(node: ast.AST) -> Optional[str]:
    """First positional arg of pallas_call: a function name, possibly
    wrapped in functools.partial."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call) and call_name(node) == "partial":
        if node.args and isinstance(node.args[0], ast.Name):
            return node.args[0].id
    return None


def _alias_map(node: Optional[ast.AST]) -> Optional[Dict[int, int]]:
    """Literal ``{in_operand: out_index}`` dict; {} if absent; None if
    present but not statically readable."""
    if node is None:
        return {}
    if not isinstance(node, ast.Dict):
        return None
    out: Dict[int, int] = {}
    for k, v in zip(node.keys, node.values):
        if not (isinstance(k, ast.Constant) and isinstance(k.value, int)
                and isinstance(v, ast.Constant)
                and isinstance(v.value, int)):
            return None
        out[k.value] = v.value
    return out


def _is_low_precision(node: Optional[ast.AST]) -> bool:
    if node is None:
        return False
    chain = attr_chain(node)
    return bool(chain) and chain[-1] in _LOW_PRECISION


def _downcasts(expr: ast.AST) -> bool:
    """Does the expression round through astype(bf16/f16) anywhere?"""
    for n in ast.walk(expr):
        if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and n.func.attr == "astype" and n.args
                and _is_low_precision(n.args[0])):
            return True
    return False


def check_module(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []
    defs: Dict[str, ast.FunctionDef] = {}
    for fn in functions_in(tree):
        # first definition wins; ambiguous names are skipped below
        defs.setdefault(fn.name, fn)

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and call_name(node) == "pallas_call" and node.args):
            continue
        kname = _kernel_name(node.args[0])
        kernel = defs.get(kname) if kname else None
        if kernel is None:
            continue

        n_in = static_len(kwarg(node, "in_specs"))
        n_out = static_len(kwarg(node, "out_specs"))
        params = [a.arg for a in kernel.args.posonlyargs + kernel.args.args]
        if n_in is None:
            continue
        if n_out is None:
            if kwarg(node, "scratch_shapes") is not None:
                continue  # can't split outputs from scratch params
            n_out = len(params) - n_in
        if n_out < 0 or len(params) < n_in + n_out:
            continue

        in_params = params[:n_in]
        out_params = params[n_in:n_in + n_out]
        scratch_params = params[n_in + n_out:]

        findings.extend(_check_aliases(node, kernel, path, in_params,
                                       out_params))
        findings.extend(_check_stats_decls(node, path, out_params,
                                           scratch_params))
    findings.extend(_check_stats_stores(tree, path, defs))
    return findings


def _check_aliases(node: ast.Call, kernel: ast.FunctionDef, path: str,
                   in_params: List[str],
                   out_params: List[str]) -> List[Finding]:
    aliases = _alias_map(kwarg(node, "input_output_aliases"))
    if aliases is None:
        return []
    in_stems: Dict[str, int] = {}
    dup = set()
    for i, p in enumerate(in_params):
        s = _stem(p)
        dup.add(s) if s in in_stems else in_stems.setdefault(s, i)
    findings = []
    for o, p in enumerate(out_params):
        s = _stem(p)
        if s in dup or s not in in_stems:
            continue
        i = in_stems[s]
        if aliases.get(i) != o:
            findings.append(Finding(
                "APX101", path, node.lineno,
                f"kernel '{kernel.name}' writes output '{p}' from input "
                f"'{in_params[i]}' (same stem '{s}') but pallas_call "
                f"declares no input_output_aliases entry {{{i}: {o}}} — "
                "the in-place update materializes a second HBM buffer"))
    return findings


def _check_stats_decls(node: ast.Call, path: str, out_params: List[str],
                       scratch_params: List[str]) -> List[Finding]:
    findings = []
    scratch = static_elements(kwarg(node, "scratch_shapes")) or []
    for p, elem in zip(scratch_params, scratch):
        if _stem(p) not in _STATS_STEMS:
            continue
        if (isinstance(elem, ast.Call) and len(elem.args) >= 2
                and _is_low_precision(elem.args[1])):
            findings.append(Finding(
                "APX103", path, elem.lineno,
                f"stats scratch '{p}' allocated in reduced precision — "
                "online-softmax statistics must stay fp32"))
    outs = static_elements(kwarg(node, "out_shape")) or []
    for p, elem in zip(out_params, outs):
        if _stem(p) not in _STATS_STEMS:
            continue
        if (isinstance(elem, ast.Call) and len(elem.args) >= 2
                and _is_low_precision(elem.args[1])):
            findings.append(Finding(
                "APX103", path, elem.lineno,
                f"stats output '{p}' declared in reduced precision — "
                "lse/mean/rstd residuals must stay fp32"))
    return findings


def _check_stats_stores(tree: ast.Module, path: str,
                        defs: Dict[str, ast.FunctionDef]) -> List[Finding]:
    """(a) of APX103: any ``m_ref[...] = (...).astype(bf16)`` store, in
    any function — stats refs are unambiguous by naming convention, so
    this needs no call-site mapping and also covers ``*refs`` kernels
    (where the refs are rebound via ``next(it)``)."""
    findings = []
    seen = set()
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        else:
            continue
        for t in targets:
            if not (isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)):
                continue
            name = t.value.id
            if not name.endswith(("_ref", "_out")):
                continue
            if _stem(name) not in _STATS_STEMS:
                continue
            if _downcasts(node.value) and node.lineno not in seen:
                seen.add(node.lineno)
                findings.append(Finding(
                    "APX103", path, node.lineno,
                    f"store into stats ref '{name}' rounds through a "
                    "reduced-precision astype — m/l/lse/mean/rstd must "
                    "stay fp32 (only the probability tiles go to bf16)"))
    return findings
