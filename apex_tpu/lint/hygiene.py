"""Tracer-hygiene checks (APX401, APX402).

A function traced by jax (a ``jit``/``grad``/``scan`` body, a
``custom_vjp`` rule, a Pallas kernel) runs ONCE at trace time; any host
state it reads is baked into the compiled program as a constant. A
``time.time()`` timestamp, an ``np.random`` draw, or a mutated global
inside such a function is a silent staleness bug: the program keeps
replaying the value captured at trace time. Host-side code (metrics,
mesh initialization) is free to do all of these — so the check first
builds the set of functions *reachable from a trace root* and only
flags violations inside that set.

Trace roots in a module: functions decorated with (or passed to)
``jax.custom_vjp``/``custom_jvp``/``jit``/``checkpoint``/``remat``,
arguments of ``.defvjp(...)``, Pallas kernel bodies (first argument of
``pallas_call``, through ``functools.partial``), and named functions
passed to ``grad``/``value_and_grad``/``vjp``/``vmap``/``pmap``/
``shard_map``/``scan``/``cond``/``switch``/``while_loop``/
``fori_loop``. Reachability closes transitively over calls to
module-local function names.

Host-module references (``time``, ``random``, ``numpy``/``np.random``,
``datetime``) are matched against the module's actual imports, so
``from jax import random`` never false-positives.

Roots also propagate *across modules*: ``jax.jit(sample_stream_checked)``
in ``serving/scheduler.py`` makes ``sample_stream_checked`` — defined in
``serving/sampling.py`` — a traced body, and with it what it calls there
(``sample_stream``, ``finite_rows``), even though sampling.py itself
never mentions jit. :func:`check_files` collects such imported-name
roots per file (via the importing module's ``from apex_tpu.x import
name`` statements), maps each dotted module back to its file in the
linted set, and seeds them into that file's reachability frontier.

Beyond the stdlib host modules, apex_tpu's OWN host state is
registered: ``serving.faults`` (fault schedules, call counters),
``serving.health`` (``ServingStats`` degradation counters, replica
health ladders), ``serving.observe`` (tracer flags, metric registries,
flight-recorder rings), ``serving.transfer`` (handoff attempt
counters), and ``serving.router`` (replica roles, admission charges)
exist to be mutated between ticks, so reading them inside a
traced body freezes a counter value into the compiled program — the
canonical staleness bug this tier exists for. Any use of those
modules' stateful classes — or of a module-level instance constructed
from them — inside a reachable function is APX401 (see
``_HOST_STATE_MODULES``/``_HOST_STATE_SYMBOLS`` and the
``apx401_hoststate_*`` / ``apx401_observe_*`` fixtures).
"""

import ast
import os
from typing import Dict, Iterable, List, Set, Tuple

from apex_tpu.lint import Finding
from apex_tpu.lint.astutil import attr_chain, call_name

_TRANSFORMS = {
    "jit", "grad", "value_and_grad", "vjp", "jvp", "vmap", "pmap",
    "shard_map", "scan", "cond", "switch", "while_loop", "fori_loop",
    "checkpoint", "remat", "custom_vjp", "custom_jvp", "pallas_call",
    "named_call",
}
_DECORATOR_ROOTS = {"custom_vjp", "custom_jvp", "jit", "checkpoint",
                    "remat"}

#: apex_tpu modules whose contents are host state by design: their
#: counters/schedules mutate between scheduler ticks, so a traced body
#: reading them bakes one stale value into the compiled program.
_HOST_STATE_MODULES = {"apex_tpu.serving.faults",
                       "apex_tpu.serving.health",
                       "apex_tpu.serving.observe",
                       "apex_tpu.serving.transfer",
                       "apex_tpu.serving.router",
                       "apex_tpu.serving.tenancy",
                       "apex_tpu.serving.streaming"}
#: The stateful classes those modules export (re-exported by
#: ``apex_tpu.serving``); instances are mutated on the host every tick.
_HOST_STATE_SYMBOLS = {"FaultInjector", "ServingStats", "Tracer",
                       "MetricsRegistry", "FlightRecorder",
                       "PageTransfer", "ReplicaHealth",
                       "DisaggregatedRouter", "TenancyPolicy",
                       "StreamMux"}


def _host_modules(tree: ast.Module) -> Dict[str, str]:
    """Local alias -> canonical host-module name, from this module's
    imports only."""
    out: Dict[str, str] = {}
    interesting = {"time", "random", "numpy", "datetime"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                root = a.name.split(".")[0]
                if root in interesting:
                    out[a.asname or root] = root
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] == "numpy":
                for a in node.names:
                    if a.name == "random":
                        out[a.asname or "random"] = "numpy.random"
    return out


def _host_state_names(tree: ast.Module) -> Dict[str, str]:
    """Local alias -> origin for names bound to serving fault/health
    host state: imports of the registered modules or their stateful
    classes (from the defining module or the ``apex_tpu.serving``
    re-export), plus module-level instances constructed from an
    imported stateful class (``STATS = ServingStats()``)."""
    names: Dict[str, str] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ImportFrom) and node.module
                and not node.level):
            continue
        if node.module in _HOST_STATE_MODULES:
            for a in node.names:
                if a.name != "*":
                    names[a.asname or a.name] = \
                        f"{node.module}.{a.name}"
        elif node.module.split(".")[0] == "apex_tpu":
            for a in node.names:
                full = f"{node.module}.{a.name}"
                if full in _HOST_STATE_MODULES \
                        or a.name in _HOST_STATE_SYMBOLS:
                    names[a.asname or a.name] = full
    if not names:
        return names
    for node in tree.body:  # module-level singletons only
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        if isinstance(value, ast.Call) and call_name(value) in names:
            for t in targets:
                if isinstance(t, ast.Name):
                    names[t.id] = f"{names[call_name(value)]} instance"
    return names


def _function_table(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    table: Dict[str, ast.FunctionDef] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.FunctionDef):
            table.setdefault(n.name, n)
    return table


def _decorator_is_root(dec: ast.AST) -> bool:
    chain = attr_chain(dec if not isinstance(dec, ast.Call) else dec.func)
    if chain and chain[-1] in _DECORATOR_ROOTS:
        return True
    # @functools.partial(jax.custom_vjp, ...) / @partial(jit, ...)
    if isinstance(dec, ast.Call) and call_name(dec) == "partial" \
            and dec.args:
        inner = attr_chain(dec.args[0])
        return bool(inner) and inner[-1] in _DECORATOR_ROOTS
    return False


def _roots(tree: ast.Module, table: Dict[str, ast.FunctionDef]
           ) -> Set[str]:
    roots: Set[str] = set()
    for fn in table.values():
        if any(_decorator_is_root(d) for d in fn.decorator_list):
            roots.add(fn.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        is_defvjp = (isinstance(node.func, ast.Attribute)
                     and node.func.attr in ("defvjp", "defjvp"))
        if name not in _TRANSFORMS and not is_defvjp:
            continue
        args = list(node.args)
        # functools.partial(kernel, ...) as a pallas_call argument
        for a in list(args):
            if isinstance(a, ast.Call) and call_name(a) == "partial":
                args.extend(a.args)
        for a in args:
            if isinstance(a, ast.Name) and a.id in table:
                roots.add(a.id)
    return roots


def _calls(fn: ast.FunctionDef) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(fn):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
            out.add(n.func.id)
        elif isinstance(n, ast.Name):
            # a bare reference (closure capture, callback arg) keeps the
            # callee reachable too
            out.add(n.id)
    return out


def _import_map(tree: ast.Module) -> Dict[str, Tuple[str, str]]:
    """Local alias -> (dotted apex_tpu module, original name) for every
    ``from apex_tpu.x import name [as alias]`` in this module."""
    out: Dict[str, Tuple[str, str]] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.ImportFrom) and node.module
                and node.module.split(".")[0] == "apex_tpu"
                and not node.level):
            for a in node.names:
                if a.name != "*":
                    out[a.asname or a.name] = (node.module, a.name)
    return out


def _external_roots(tree: ast.Module) -> Set[Tuple[str, str]]:
    """(dotted module, function name) pairs this module passes into a
    tracing transform — roots it creates in OTHER files."""
    imports = _import_map(tree)
    if not imports:
        return set()
    out: Set[Tuple[str, str]] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        is_defvjp = (isinstance(node.func, ast.Attribute)
                     and node.func.attr in ("defvjp", "defjvp"))
        if name not in _TRANSFORMS and not is_defvjp:
            continue
        args = list(node.args)
        for a in list(args):
            if isinstance(a, ast.Call) and call_name(a) == "partial":
                args.extend(a.args)
        for a in args:
            if isinstance(a, ast.Name) and a.id in imports:
                out.add(imports[a.id])
    return out


def _resolve_module(dotted: str, trees: Dict[str, ast.Module]
                    ) -> str:
    rel = dotted.replace(".", os.sep)
    suffixes = (os.sep + rel + ".py",
                os.sep + rel + os.sep + "__init__.py")
    for path in trees:
        if path.endswith(suffixes):
            return path
    return ""


def check_files(trees: Dict[str, ast.Module]) -> List[Finding]:
    """Project pass: per-module hygiene with cross-module root
    propagation (the only way a ``jax.jit(imported_fn)`` call site can
    taint the defining module)."""
    extra: Dict[str, Set[str]] = {}
    for tree in trees.values():
        for dotted, fname in _external_roots(tree):
            target = _resolve_module(dotted, trees)
            if target:
                extra.setdefault(target, set()).add(fname)
    findings: List[Finding] = []
    for path in sorted(trees):
        findings.extend(check_module(
            trees[path], path, extra_roots=sorted(extra.get(path, ()))))
    return findings


def check_module(tree: ast.Module, path: str,
                 extra_roots: Iterable[str] = ()) -> List[Finding]:
    table = _function_table(tree)
    host = _host_modules(tree)
    host_state = _host_state_names(tree)
    if not table:
        return []
    reachable = set()
    frontier = list(_roots(tree, table))
    frontier.extend(n for n in extra_roots if n in table)
    while frontier:
        name = frontier.pop()
        if name in reachable or name not in table:
            continue
        reachable.add(name)
        frontier.extend(_calls(table[name]) & set(table))

    findings: List[Finding] = []
    seen: Set[int] = set()
    for name in sorted(reachable):
        fn = table[name]
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                if node.lineno not in seen:
                    seen.add(node.lineno)
                    findings.append(Finding(
                        "APX402", path, node.lineno,
                        f"'global {', '.join(node.names)}' inside "
                        f"'{name}', which is reachable from a traced "
                        "body — trace-time global mutation is baked in "
                        "as a constant"))
                continue
            if host_state and isinstance(node, (ast.Attribute,
                                                ast.Name)):
                chain = attr_chain(node)
                if chain and chain[0] in host_state \
                        and node.lineno not in seen:
                    seen.add(node.lineno)
                    findings.append(Finding(
                        "APX401", path, node.lineno,
                        f"serving host state '{'.'.join(chain)}' "
                        f"({host_state[chain[0]]}) inside '{name}', "
                        "which is reachable from a traced body — fault "
                        "schedules and ServingStats counters mutate "
                        "between ticks; a traced read freezes one "
                        "stale value into the compiled program"))
                continue
            if not isinstance(node, ast.Call):
                continue
            chain = attr_chain(node.func)
            if not chain or chain[0] not in host:
                continue
            root = host[chain[0]]
            full = [root] + chain[1:]
            bad = (
                root == "time"
                or root == "random"
                or root == "numpy.random"
                or (root == "numpy" and len(full) > 1
                    and full[1] == "random")
                or (root == "datetime" and full[-1] in ("now", "today",
                                                        "utcnow"))
            )
            if bad and node.lineno not in seen:
                seen.add(node.lineno)
                findings.append(Finding(
                    "APX401", path, node.lineno,
                    f"host-state read '{'.'.join(chain)}' inside "
                    f"'{name}', which is reachable from a traced body — "
                    "the value is frozen at trace time"))
    return findings
