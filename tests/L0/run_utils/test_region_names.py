"""One vocabulary of device scopes (PR 38): every ``jax.named_scope`` /
``annotate`` / ``region`` the package opens is a device region
(``utils.profiler.REGIONS``, through ``region()``), a Pallas kernel's own
``apex_<kernel>`` scope, a ``layer{i}``, an optimizer's ``<Name>.step`` or one
of the sparse attention's four steps inside its region (``OTHER``).
A scope outside these would be a second vocabulary the trace readers
(``benchmark/regions.py``) do not know."""

import ast
import glob
import os

from apex_tpu.utils.pallas import KERNEL_NAMES
from apex_tpu.utils.profiler import REGIONS

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: the scopes that are neither a region nor a kernel's, by file: an
#: optimizer's step, and the four steps of learned-sparse attention INSIDE
#: the ``attention`` region (an operation counts under the first region on its
#: path, so the readers see them as attention; a trace says which step)
_SPARSE = "apex_tpu/models/glm_next.py"
OTHER = {"FusedAdam.step": "apex_tpu/optimizers/fused_adam.py",
         "dsa_index": _SPARSE, "dsa_topk": _SPARSE, "dsa_gather": _SPARSE,
         "dsa_attend": _SPARSE}
OPENERS = ("named_scope", "annotate", "region")


def _sites():
    """(file, line, opener, first argument's AST node) of every call of
    an opener under ``apex_tpu/``."""
    out = []
    for path in sorted(glob.glob(os.path.join(REPO, "apex_tpu", "**", "*.py"),
                                 recursive=True)):
        rel = os.path.relpath(path, REPO)
        for node in ast.walk(ast.parse(open(path).read())):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                f.id if isinstance(f, ast.Name) else None
            if name in OPENERS:
                out.append((rel, node.lineno, name, node.args[0]))
    return out


def test_every_scope_is_a_region_a_kernel_a_layer_or_an_optimizer_step():
    sites = _sites()
    seen = set()
    for rel, line, opener, arg in sites:
        where = f"{rel}:{line}"
        if isinstance(arg, ast.Constant):
            name = arg.value
            seen.add(name)
            if opener == "region":
                assert name in REGIONS, where
            elif name in OTHER:
                assert OTHER[name] == rel, where
            else:
                # a region goes through region(), which checks it
                assert name in KERNEL_NAMES, (where, name)
        elif isinstance(arg, ast.JoinedStr):
            assert opener == "named_scope" and arg.values[0].value \
                == "layer" and len(arg.values) == 2, where
        else:
            # a name handed on: region() itself, and the HOST side's
            # Tracer.annotate (``apex:sched/<phase>``, no device scope)
            assert rel in ("apex_tpu/utils/profiler.py",
                           "apex_tpu/serving/observe.py"), where
    assert set(REGIONS) <= seen, set(REGIONS) - seen
    assert set(KERNEL_NAMES) <= seen
    assert set(OTHER) <= seen


def test_regions_is_the_only_list_in_the_program():
    """No second tuple of region names under ``apex_tpu/``: the readers'
    own copy lives in ``benchmark/regions.py`` and is held to this one."""
    from benchmark import regions

    assert tuple(regions.REGIONS) == tuple(REGIONS)
    for family, groups in regions.GROUPS.items():
        mapped = regions.GROUP_OF[family]
        assert set(mapped) == set(REGIONS), family
        assert set(mapped.values()) <= set(groups), family
    hits = []
    for path in glob.glob(os.path.join(REPO, "apex_tpu", "**", "*.py"),
                          recursive=True):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, (ast.Tuple, ast.List)) and len(node.elts) > 3:
                values = {e.value for e in node.elts
                          if isinstance(e, ast.Constant)}
                if len(values & set(REGIONS)) > 3:
                    hits.append(os.path.relpath(path, REPO))
    assert hits == ["apex_tpu/utils/profiler.py"], hits
