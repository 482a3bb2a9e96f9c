"""Every Pallas kernel carries a stable name (PR 24): ``name="apex_..."`` on
the ``pl.pallas_call`` and a ``jax.named_scope`` of the same name
immediately around it, inside the function that makes the call. On the chip
a Mosaic ``custom-call`` is named in the profiler's trace after the
innermost scope around it (``%apex_ln_fwd.4``), which is what the trace
readers and ``breakdown.device_ops`` find it by."""

import ast
import glob
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _sites():
    """(file, line, name= literal or None, innermost scope literal or
    None) of every ``pl.pallas_call`` under ``apex_tpu/`` (the lint's own
    sources, which only talk about kernels, left out)."""
    out = []
    for path in sorted(glob.glob(os.path.join(REPO, "apex_tpu", "**", "*.py"),
                                 recursive=True)):
        if os.sep + "lint" + os.sep in path:
            continue
        tree = ast.parse(open(path).read())

        def walk(node, scope):
            if isinstance(node, ast.With) and len(node.items) == 1:
                ctx = node.items[0].context_expr
                if isinstance(ctx, ast.Call) and isinstance(
                        ctx.func, ast.Attribute) \
                        and ctx.func.attr == "named_scope" and ctx.args \
                        and isinstance(ctx.args[0], ast.Constant):
                    scope = ctx.args[0].value
            elif isinstance(node, (ast.FunctionDef, ast.Lambda)):
                scope = None        # a scope does not reach into a def
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                name = next((k.value.value for k in node.keywords
                             if k.arg == "name"
                             and isinstance(k.value, ast.Constant)), None)
                out.append((os.path.relpath(path, REPO), node.lineno,
                            name, scope))
            for child in ast.iter_child_nodes(node):
                walk(child, scope)

        walk(tree, None)
    return out


def test_every_pallas_call_has_a_unique_literal_apex_name_and_scope():
    from apex_tpu.utils.pallas import KERNEL_NAMES

    sites = _sites()
    for path, line, name, scope in sites:
        where = f"{path}:{line}"
        assert isinstance(name, str) and name.startswith("apex_"), where
        assert scope == name, (where, name, scope)
    names = [name for _, _, name, _ in sites]
    assert len(set(names)) == len(names), sorted(names)
    # the package's one list says what the sources say
    assert sorted(KERNEL_NAMES) == sorted(names)
    # the names the trace readers and PERF.md lean on
    assert {"apex_ln_fwd", "apex_ln_bwd", "apex_xentropy_fwd",
            "apex_xentropy_bwd", "apex_flash_fwd", "apex_fmha_fwd",
            "apex_fmha_bwd", "apex_paged_decode_fwd"} <= set(names)


def test_chip_smoke_expects_only_kernels_of_the_list():
    """``chip_smoke.py`` kept names of its own once and broke in silence
    when the kernels were renamed (PR 24)."""
    import re

    from apex_tpu.utils.pallas import KERNEL_NAMES

    source = open(os.path.join(REPO, "chip_smoke.py")).read()
    expected = set(re.findall(r'"(apex_\w+)"', source))
    assert {"apex_ln_fwd", "apex_paged_decode_fwd"} <= expected
    assert expected <= set(KERNEL_NAMES), expected - set(KERNEL_NAMES)


def test_kernel_name_reaches_the_lowered_program():
    """The scope and the name are in what the compiler is given: the
    LayerNorm forward and backward kernels differ by name after ``grad``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.normalization import fused_layer_norm_affine

    x = jnp.ones((16, 128), jnp.float32)
    w = jnp.ones((128,), jnp.float32)
    b = jnp.zeros((128,), jnp.float32)

    def loss(x, w, b):
        return fused_layer_norm_affine(x, w, b, (128,)).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, w, b).as_text(
        debug_info=True)
    assert "apex_ln_fwd" in text and "apex_ln_bwd" in text
