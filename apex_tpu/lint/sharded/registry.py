"""Sharding-tier entry registry and driver (APX701-704).

A :class:`ShardedEntry` names one partition-rule table plus everything
the repo derives from it: the abstract trees it must cover (params,
optimizer families, the serving KV cache), the hand-maintained
reference spec trees it must reproduce, and — for train-step entries —
a builder staging the rule-derived ``shard_map`` program whose
``in_specs`` and per-rank collective schedule are verified against the
table. The table is data; these entries are what make a wrong table a
lint finding instead of a silent mis-sharding on a pod slice.

Check dispatch per entry:

- ``rules`` + ``trees``            -> APX701 (coverage / spec sanity /
  dead rules, :mod:`rules_check`)
- ``optimizer_families`` /
  ``reference_specs`` / ``kv_*``   -> APX702 (cross-tree consistency)
- ``build``                        -> APX703 (in_specs vs table,
  replicated-matmul floor, :mod:`propagation`) and APX704 (per-rank
  schedule + collective volume vs budgets.json,
  :mod:`schedule_check`)

The driver mirrors the trace tier's contract: entries trace under
``jax.make_jaxpr`` only (abstract, CPU-safe), the global parallel state
is snapshotted/restored around each entry, and an entry that fails to
evaluate is an APX100 finding, never a silent skip.
"""

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from apex_tpu.lint import Finding
from apex_tpu.lint.traced.registry import (
    _mesh,
    _module_path,
    _restore_parallel_state,
    _sds,
    _snapshot_parallel_state,
    ensure_cpu_devices,
    zero_dp2xtp2_parts,
    zero_parts,
)

_REPLICATION_FLOOR = 1 << 20


@dataclass
class ShardedEntry:
    name: str
    module: str  # dotted module whose sharding contract this verifies
    rules: Callable[[], tuple]
    # name -> abstract tree (ShapeDtypeStructs); every rule must match
    # at least one leaf across the union of these trees
    trees: Optional[Callable[[], Dict[str, Any]]] = None
    # name -> hand-maintained spec tree the derived specs must equal
    reference_specs: Optional[Callable[[], Dict[str, Any]]] = None
    # optimizer-state families re-derived under a path prefix (APX702)
    optimizer_families: Tuple[str, ...] = ()
    # KV-cache consistency: tree name of the cache + regex of the
    # attention qkv kernel leaf whose output-dim axes the cache's head
    # axis must equal
    kv_cache_tree: Optional[str] = None
    qkv_kernel_re: str = r"qkv/kernel"
    # train-step staging: () -> (fn, args, in_specs)
    build: Optional[Callable[[], Tuple[Callable, tuple, Any]]] = None
    mesh: Optional[Callable[[], None]] = None
    min_devices: int = 1
    replication_floor: int = _REPLICATION_FLOOR
    budget_name: Optional[str] = None


def run_entries(entries: List[ShardedEntry], *,
                manifest: Any = "__load__") -> List[Finding]:
    """All sharding-tier findings; APX100 on any entry that fails to
    evaluate. ``manifest`` is the budgets.json dict (or the default
    sentinel to load the committed one) for APX704's volume gate."""
    ensure_cpu_devices()
    import jax

    from apex_tpu.lint.sharded import propagation, rules_check, schedule_check
    from apex_tpu.lint.traced import budgets

    if manifest == "__load__":
        manifest = budgets.load_manifest()

    findings: List[Finding] = []
    for e in entries:
        path = _module_path(e.module)
        try:
            findings.extend(rules_check.check(e, path))
        except Exception as exc:  # noqa: BLE001 - surfaced as a finding
            findings.append(Finding(
                "APX100", path, 1,
                f"sharded entry '{e.name}' rule checks failed to "
                f"evaluate: {type(exc).__name__}: {exc}"))
        if e.build is None:
            continue
        snap = _snapshot_parallel_state()
        try:
            try:
                have = jax.device_count()
                if have < e.min_devices:
                    raise RuntimeError(
                        f"needs {e.min_devices} devices, have {have} "
                        f"(backend initialized before ensure_cpu_devices)")
                if e.mesh is not None:
                    e.mesh()
                fn, args, in_specs = e.build()
                closed = jax.make_jaxpr(fn)(*args)
            finally:
                _restore_parallel_state(snap)
        except Exception as exc:  # noqa: BLE001 - surfaced as a finding
            findings.append(Finding(
                "APX100", path, 1,
                f"sharded entry '{e.name}' failed to trace: "
                f"{type(exc).__name__}: {exc}"))
            continue
        findings.extend(propagation.check(closed, in_specs, path, e))
        findings.extend(schedule_check.check(closed, path, e, manifest))
    return findings


# ---------------------------------------------------------------------------
# registered rule tables / sharded entrypoints
# ---------------------------------------------------------------------------

def _gpt_trees():
    import functools as ft

    import jax

    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    from apex_tpu.serving.cache import init_paged_cache

    cfg = gpt_tiny()
    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(ft.partial(init_paged_cache, cfg, 2, 32, 6, 16))
    return {"params": params, "kv_cache": cache}


def _gpt_reference():
    from apex_tpu.models.gpt import gpt_partition_specs, gpt_tiny
    from apex_tpu.serving.cache import paged_cache_partition_specs

    return {"params": gpt_partition_specs(gpt_tiny()),
            "kv_cache": paged_cache_partition_specs()}


def _gpt_quant_trees():
    """The weight-only int8 tree (same kernel paths, sibling fp32
    scales) + the int8 page pool with its per-page-per-head scales —
    registering both keeps every gpt_quant_rules scale rule live for
    APX701 and the derived specs APX702-checked. gpt_tiny() default
    (learned positions) so the position-embedding rule stays live."""
    import functools as ft

    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    from apex_tpu.quant.params import quantize_params
    from apex_tpu.serving.cache import init_paged_cache

    cfg = gpt_tiny()
    params = quantize_params(jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0)))
    paged = jax.eval_shape(ft.partial(
        init_paged_cache, cfg, 2, 32, 6, 16, jnp.int8))
    return {"params": params, "paged_kv_cache": paged}


def _gpt_quant_reference():
    from apex_tpu.models.gpt import gpt_tiny
    from apex_tpu.partition import kv_cache_quant_rules
    from apex_tpu.quant.params import quant_partition_specs
    from apex_tpu.serving.cache import paged_cache_partition_specs

    return {"params": quant_partition_specs(gpt_tiny()),
            "paged_kv_cache": paged_cache_partition_specs(
                kv_cache_quant_rules(), quantized=True)}


def _draft_trees():
    """The speculative drafter's trees: a RoPE-only param tree (no
    position leaf) and the lockstep cache (engine max_len 32 plus
    DraftModel's catch-up chunk of 5, under its identity table) —
    exactly what draft_gpt_rules must cover with no dead rows."""
    import functools as ft

    import jax

    from apex_tpu.models.gpt import draft_gpt_tiny, init_gpt
    from apex_tpu.serving.draft_model import init_draft_cache

    cfg = draft_gpt_tiny()
    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(ft.partial(init_draft_cache, cfg, 2, 37))
    return {"params": params, "kv_cache": cache}


def _draft_reference():
    from apex_tpu.models.gpt import draft_gpt_tiny, gpt_partition_specs
    from apex_tpu.serving.cache import paged_cache_partition_specs

    return {"params": gpt_partition_specs(draft_gpt_tiny()),
            "kv_cache": paged_cache_partition_specs()}


def repo_entries() -> List[ShardedEntry]:
    from apex_tpu.partition import (
        draft_gpt_rules, gpt_quant_rules, gpt_rules,
    )

    return [
        ShardedEntry(
            "gpt_tiny_rules", "apex_tpu.partition.tables",
            rules=gpt_rules, trees=_gpt_trees,
            reference_specs=_gpt_reference,
            optimizer_families=("m", "v", "master"),
            kv_cache_tree="kv_cache",
            qkv_kernel_re=r"layers/qkv/kernel"),
        # quantized tier: no optimizer families (int8 trees are
        # inference-only); the kv consistency check re-runs against the
        # int8 pool so its head axis stays pinned to the qkv tp axis
        ShardedEntry(
            "gpt_tiny_quant_rules", "apex_tpu.partition.tables",
            rules=gpt_quant_rules, trees=_gpt_quant_trees,
            reference_specs=_gpt_quant_reference,
            kv_cache_tree="paged_kv_cache",
            qkv_kernel_re=r"layers/qkv/kernel"),
        # the speculative drafter: same mesh and layout as the target
        # minus the row its trees can never match (the position
        # table); no optimizer families (inference-only). The kv
        # consistency check pins the lockstep cache's head axis to the
        # draft qkv column shard — the invariant that lets the drafter
        # run TP on the target's mesh without a resharding hop.
        ShardedEntry(
            "gpt_draft_rules", "apex_tpu.partition.tables",
            rules=draft_gpt_rules, trees=_draft_trees,
            reference_specs=_draft_reference,
            kv_cache_tree="kv_cache",
            qkv_kernel_re=r"layers/qkv/kernel"),
        # trace-staged: same builder as the gpt_tiny_dp2xtp2_zero
        # TraceEntry, so APX703/704 see exactly the program the APX5xx
        # and APX6xx tiers gate
        ShardedEntry(
            "gpt_tiny_dp2xtp2_zero",
            "apex_tpu.contrib.optimizers.distributed_fused_adam",
            rules=gpt_rules,
            build=zero_dp2xtp2_parts,
            mesh=_mesh(tp=2, n_devices=4), min_devices=4,
            budget_name="gpt_tiny_dp2xtp2_zero"),
        # the ROADMAP item-5 headline shape: the same rule-derived
        # builder at dp4 x tp2 on the full 8-device world, so APX703/704
        # verify in_specs and the per-rank schedule at the shape the
        # training headline will actually run (the APX9xx scaling tier
        # additionally sweeps the whole grid)
        ShardedEntry(
            "gpt_tiny_dp4xtp2_zero",
            "apex_tpu.contrib.optimizers.distributed_fused_adam",
            rules=gpt_rules,
            build=lambda: zero_parts(dp=4, tp=2),
            mesh=_mesh(tp=2, n_devices=8), min_devices=8,
            budget_name="gpt_tiny_dp4xtp2_zero"),
    ]


def check_repo() -> List[Finding]:
    return run_entries(repo_entries())


__all__ = ["ShardedEntry", "repo_entries", "run_entries", "check_repo",
           "_sds"]
