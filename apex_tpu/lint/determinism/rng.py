"""APX805 — RNG key discipline on the tick path.

Sampling randomness in the serving engine must be a pure function of
``(request seed, position counter)`` — that is what makes a committed
stream replayable across restarts, failovers, and replica migrations:
the decode slot that picks up a preempted stream re-derives the exact
key the original slot would have used. The repo's idiom is

    key = jax.random.fold_in(jax.random.PRNGKey(req.seed), step)

in two batched spellings: a ``jnp.stack`` of per-slot keys, and — what
the scheduler does since the decode tick builds its keys in one program
— the seed root read back to the host once per request,
``np.asarray(jax.random.PRNGKey(req.seed))``, kept in a ``[slots, 2]``
array and folded with the ``[slots]`` counters inside the sampler's
program (``jax.vmap(jax.random.fold_in)(base, counts)``,
``serving.sampling.stream_keys``). Two statically detectable ways to
break it:

**Raw PRNGKey on the tick path.** A ``PRNGKey(...)`` whose result is
consumed directly (not folded, not an element of a batched key stack)
gives every step of the stream the SAME key — identical draws at
every position, and no counter to re-derive after a migration. A
``PRNGKey`` call is fine when (a) some enclosing call in the same
expression is ``fold_in`` (it is the seed root of a fold chain), or
(b) it is an element of a list/tuple/comprehension that feeds a
``stack`` / ``concatenate`` / ``array`` / ``asarray`` call (the
batched-slot idiom — the fold already happened upstream or the slot
is inert/padding), or (c) it is itself the operand of ``asarray`` /
``array``: the seed root read back as plain words, which no sampler
takes as a key — the batched fold consumes it.

**Key reuse.** A local name bound to a ``fold_in`` / ``PRNGKey``
result and then passed as an argument to two or more distinct calls:
the second consumer sees correlated randomness. Deriving is not
consuming — passing the key to ``fold_in`` / ``split`` again is how
chains are built and does not count as a use.

``split`` is also flagged on the tick path when it is clearly
``jax.random.split`` (attribute chain mentioning ``random``, or a
name imported from ``jax.random``): split trees make the key at a
position depend on how many OTHER streams were scheduled that tick,
which is exactly the cross-request coupling fold_in chains avoid.
(``s.split(",")`` on strings has no ``random`` in its chain and is
never flagged.)
"""

import ast
from typing import Dict, List, Optional, Set

from apex_tpu.lint import Finding
from apex_tpu.lint.astutil import attr_chain, call_name
from apex_tpu.lint.determinism.reach import reachable_functions

_STACKERS = {"stack", "concatenate", "array", "asarray"}


def _parents(fn: ast.FunctionDef) -> Dict[int, ast.AST]:
    out: Dict[int, ast.AST] = {}
    for node in ast.walk(fn):
        for child in ast.iter_child_nodes(node):
            out[id(child)] = node
    return out


def _random_split_names(tree: ast.Module) -> Set[str]:
    """Local names that are ``jax.random.split`` via from-import."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.endswith("random"):
            for a in node.names:
                if a.name == "split":
                    out.add(a.asname or "split")
    return out


def _key_ok(call: ast.Call, parents: Dict[int, ast.AST]) -> bool:
    """Is this PRNGKey(...) call blessed — under a fold_in, an element
    of a batched key stack, or read back as the seed root of a batched
    fold (``np.asarray(PRNGKey(seed))``)?"""
    node: ast.AST = call
    while id(node) in parents:
        parent = parents[id(node)]
        if isinstance(parent, ast.Call):
            pn = call_name(parent)
            if pn == "fold_in":
                return True
            if pn in _STACKERS:
                return True
        if isinstance(parent, (ast.stmt,)) and not isinstance(
                parent, ast.Expr):
            # climbed out of the expression without meeting a blesser
            # — except keep climbing through simple value statements
            # so `key = fold_in(PRNGKey(s), 0)` (Assign) still works:
            # the Call check above already fired before we got here.
            return False
        node = parent
    return False


def check_files(strees: Dict[str, ast.Module]) -> List[Finding]:
    findings: List[Finding] = []
    reach: Dict[str, List[ast.FunctionDef]] = {}
    for path, fn in reachable_functions(strees):
        reach.setdefault(path, []).append(fn)

    for path in sorted(reach):
        split_imports = _random_split_names(strees[path])
        for fn in reach[path]:
            parents = _parents(fn)
            # name -> line where bound to a key-producing call
            key_names: Dict[str, int] = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and isinstance(
                        node.value, ast.Call) and call_name(
                        node.value) in ("fold_in", "PRNGKey"):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            key_names[t.id] = node.lineno

            uses: Dict[str, List[int]] = {}
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                cn = call_name(node)
                if cn == "PRNGKey" and not _key_ok(node, parents):
                    findings.append(Finding(
                        "APX805", path, node.lineno,
                        f"raw PRNGKey on the tick path in '{fn.name}' "
                        "— a fixed key repeats the same draw at every "
                        "position; derive per-step keys as "
                        "fold_in(PRNGKey(request seed), counter)"))
                elif cn == "split":
                    chain = attr_chain(node.func)
                    is_random = (chain is not None and "random" in
                                 chain[:-1]) or (
                        isinstance(node.func, ast.Name)
                        and node.func.id in split_imports)
                    if is_random:
                        findings.append(Finding(
                            "APX805", path, node.lineno,
                            f"jax.random.split in '{fn.name}' on the "
                            "tick path — split trees couple a "
                            "stream's key to what else was scheduled "
                            "that tick; use fold_in(seed, counter) "
                            "chains"))
                # key reuse: a bound key passed as an argument to
                # distinct consumer calls (fold_in/split derive, they
                # don't consume)
                if cn in ("fold_in", "split"):
                    continue
                for arg in list(node.args) + [kw.value for kw in
                                              node.keywords]:
                    if isinstance(arg, ast.Name) and arg.id in key_names:
                        uses.setdefault(arg.id, []).append(node.lineno)
            for name, lines in sorted(uses.items()):
                if len(lines) > 1:
                    findings.append(Finding(
                        "APX805", path, lines[1],
                        f"key '{name}' (bound at line "
                        f"{key_names[name]}) consumed by "
                        f"{len(lines)} calls in '{fn.name}' — reusing "
                        "a key correlates draws; fold_in a fresh "
                        "counter per consumer"))
    return findings
