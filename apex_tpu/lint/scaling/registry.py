"""Scaling-tier entry registry and driver (APX901-904).

A :class:`ScalingEntry` names either a *swept program* — a builder
``build(shape) -> (fn, args, in_specs)`` re-staged under
``jax.make_jaxpr`` at every :class:`~apex_tpu.lint.scaling.grid
.MeshShape` of its grid — or a *rule table* audited for scale safety
across the same grid. Every other tier verifies its contract at exactly
one mesh shape; this tier is the claim that those contracts are
functions of *axis names*, not axis sizes:

- ``schedule``  -> APX901 (:mod:`isomorphism`): the APX511 per-rank
  simulator re-issued at every swept shape, plus cross-shape structural
  equality of the collective schedule;
- ``volume``    -> APX902 (:mod:`volume`): per-collective bytes from
  the APX6xx cost interpreter fitted against the entry's declared
  scaling model, pinned byte-exact per shape in ``budgets.json``
  (``<entry>@<tag>`` rows written by ``--write-budgets``);
- ``memory``    -> APX903 (:mod:`memory`): per-device optimizer-state
  and peak-live bytes non-increasing in dp, and the APX703
  replicated-operand taint walk re-run at every shape;
- ``tables``    -> APX904 (:mod:`tables_check`): the APX701
  coverage/dead-rule analysis re-issued under the sweep plus a
  divisibility audit — any ``dim % axis_size != 0`` a table or a staged
  operand would induce at a swept shape is a finding here, not a crash
  on an 8-chip pod.

The driver mirrors the trace tier's contract: abstract staging only
(``jax.make_jaxpr``, CPU-safe), parallel state snapshotted/restored
around every shape, and a shape that fails to stage is an APX100
finding, never a silent skip.
"""

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from apex_tpu.lint import Finding
from apex_tpu.lint.scaling.grid import (
    FULL_GRID, HALO_GRID, ZERO_GRID, MeshShape,
)
from apex_tpu.lint.traced.registry import (
    _mesh,
    _module_path,
    _restore_parallel_state,
    _snapshot_parallel_state,
    bottleneck_parts,
    ensure_cpu_devices,
    zero_parts,
)

#: APX703 re-run floor, same default as the sharded tier.
_REPLICATION_FLOOR = 1 << 20


@dataclass
class ScalingEntry:
    name: str
    module: str  # dotted module whose scaling contract this verifies
    # swept program: shape -> (fn, args, in_specs); staged per shape
    build: Optional[Callable[[MeshShape], Tuple[Callable, tuple, Any]]] = None
    grid: Tuple[MeshShape, ...] = FULL_GRID
    checks: Tuple[str, ...] = ("schedule", "volume", "memory")
    # APX902: collective primitive -> ((term_name, fn(shape)->float),
    # ...) basis; measured bytes must be a non-negative combination of
    # the terms, exact at every swept shape (see volume.py)
    volume_model: Optional[
        Callable[[], Dict[str, Tuple[Tuple[str, Callable], ...]]]] = None
    # APX903: declared per-device optimizer-state bytes at rest
    state_bytes: Optional[Callable[[MeshShape], int]] = None
    # APX904: rule table + abstract trees audited across the grid
    rules: Optional[Callable[[], tuple]] = None
    trees: Optional[Callable[[], Dict[str, Any]]] = None
    replication_floor: int = _REPLICATION_FLOOR
    budget_name: Optional[str] = None  # base name of the @-rows


@dataclass
class StagedShape:
    """One staged sweep point, shared by every checker."""
    shape: MeshShape
    closed: Any        # jax.make_jaxpr output
    in_specs: Any
    report: Any        # traced.cost.CostReport (entry name '<base>@<tag>')


def stage_entry(entry: ScalingEntry, *,
                findings: Optional[List[Finding]] = None,
                timings_out: Optional[list] = None
                ) -> List[StagedShape]:
    """Stage ``entry.build`` at every grid shape; APX100 per failure.
    ``timings_out`` collects ``('<base>@<tag>', seconds)`` per shape."""
    import time

    import jax

    from apex_tpu.lint.traced import cost

    path = _module_path(entry.module)
    base = entry.budget_name or entry.name
    staged: List[StagedShape] = []
    if entry.build is None:
        return staged
    for shape in entry.grid:
        t0 = time.monotonic()
        snap = _snapshot_parallel_state()
        try:
            try:
                have = jax.device_count()
                if have < shape.devices:
                    raise RuntimeError(
                        f"shape {shape.tag} needs {shape.devices} "
                        f"devices, have {have} (backend initialized "
                        f"before ensure_cpu_devices)")
                _mesh(tp=shape.tp, cp=shape.cp,
                      n_devices=shape.devices)()
                fn, args, in_specs = entry.build(shape)
                closed = jax.make_jaxpr(fn)(*args)
            finally:
                _restore_parallel_state(snap)
            report = cost.compute(closed, path, f"{base}@{shape.tag}")
        except Exception as exc:  # noqa: BLE001 - surfaced as a finding
            if findings is not None:
                findings.append(Finding(
                    "APX100", path, 1,
                    f"scaling entry '{entry.name}' failed to stage at "
                    f"{shape.tag}: {type(exc).__name__}: {exc}"))
            continue
        finally:
            if timings_out is not None:
                timings_out.append(
                    (f"{base}@{shape.tag}", time.monotonic() - t0))
        staged.append(StagedShape(shape, closed, in_specs, report))
    return staged


def run_entries(entries: List[ScalingEntry], *,
                manifest: Any = "__load__",
                cost_out: Optional[list] = None,
                timings_out: Optional[list] = None) -> List[Finding]:
    """All scaling-tier findings. ``manifest`` is the budgets.json dict
    (or the default sentinel to load the committed one) for APX902's
    per-mesh volume gate; ``cost_out`` collects the per-shape
    CostReports (the ``--write-budgets`` path); ``timings_out``
    collects ``(entry@tag, seconds)`` per staged shape so run_tests.sh
    can report where the wall budget goes."""
    ensure_cpu_devices()
    from apex_tpu.lint.scaling import (
        isomorphism, memory, tables_check, volume,
    )
    from apex_tpu.lint.traced import budgets

    if manifest == "__load__":
        manifest = budgets.load_manifest()

    findings: List[Finding] = []
    swept_rows: Dict[str, set] = {}
    for e in entries:
        path = _module_path(e.module)
        staged = stage_entry(e, findings=findings,
                             timings_out=timings_out)
        if cost_out is not None:
            cost_out.extend(s.report for s in staged)
        base = e.budget_name or e.name
        # @-rows exist only for volume-checked entries; schedule- or
        # memory-only sweeps never consult the manifest
        if staged and "volume" in e.checks:
            swept_rows.setdefault(base, set()).update(
                s.shape.tag for s in staged)
        if "schedule" in e.checks and staged:
            findings.extend(isomorphism.check(staged, path, e))
        if "volume" in e.checks and staged:
            findings.extend(volume.check(staged, path, e, manifest))
        if "memory" in e.checks and staged:
            findings.extend(memory.check(staged, path, e))
        if "tables" in e.checks:
            try:
                findings.extend(tables_check.check(e, path))
            except Exception as exc:  # noqa: BLE001 - surfaced
                findings.append(Finding(
                    "APX100", path, 1,
                    f"scaling entry '{e.name}' table audit failed to "
                    f"evaluate: {type(exc).__name__}: {exc}"))
    findings.extend(volume.check_manifest_rows(swept_rows, manifest))
    return findings


# ---------------------------------------------------------------------------
# registered sweeps
# ---------------------------------------------------------------------------

def _zero_flat_local_bytes(tp: int) -> int:
    """Exact fp32 byte size of the ZeRO flat master buffer built from
    the TP-local gpt_tiny param shard — the ``P(tp)`` every declared
    ZeRO volume law below is stated in. Uses the same
    ``flatten.make_spec`` row layout the optimizer uses, so per-leaf
    ALIGN_ROWS padding is part of the law, not noise around it."""
    import jax

    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    from apex_tpu.multi_tensor_apply import flatten as _flatten
    from apex_tpu.partition import gpt_rules, match_partition_rules
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.lint.traced.registry import _local_shapes

    params = jax.eval_shape(
        lambda k: init_gpt(k, gpt_tiny()), jax.random.PRNGKey(0))
    specs = match_partition_rules(gpt_rules(), params)
    local = _local_shapes(params, specs, {ps.TENSOR_AXIS: tp})
    spec = _flatten.make_spec(jax.tree_util.tree_leaves(local))
    return spec.total_rows * _flatten.LANES * 4


def _zero_volume_model():
    """The ZeRO communication law under the APX6xx pricing convention
    (rendezvous volume = operand bytes x axis size; the wire-level
    ``(dp-1)/dp`` ring refinement divides out of every cross-shape
    comparison):

    - ``reduce_scatter`` (grad psum_scatter over ``data``):
      ``P(tp) * dp`` — the whole TP-local flat grad buffer enters the
      rendezvous on each of the dp ranks;
    - ``all_gather`` (master-row regather over ``data``): ``P(tp)`` —
      each rank contributes its 1/dp row shard, dp ranks;
    - ``psum`` (TP activation reductions + the scalar loss pmean):
      ``A * tp + 4 * dp`` with the activation coefficient fitted (the
      local batch is fixed per data rank, so it is dp-independent);
    - ``pmax`` (vocab-parallel CE max over the ``model`` shard):
      ``B * tp``, coefficient fitted.
    """
    P = _zero_flat_local_bytes
    return {
        "reduce_scatter": (
            ("flat_params(tp)*dp", lambda s: float(P(s.tp) * s.dp)),),
        "all_gather": (
            ("flat_params(tp)", lambda s: float(P(s.tp))),),
        "psum": (
            ("act*tp", lambda s: float(s.tp)),
            ("loss_pmean*dp", lambda s: float(4 * s.dp)),),
        "pmax": (
            ("ce_max*tp", lambda s: float(s.tp)),),
    }


def _zero_state_bytes(shape: MeshShape) -> int:
    """Declared per-device ZeRO optimizer-state bytes at rest (the
    ~1/dp claim) — ``DistributedFusedAdam.state_bytes_per_device`` over
    the TP-local gpt_tiny shard at this shape."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )
    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    from apex_tpu.partition import gpt_rules, match_partition_rules
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.lint.traced.registry import _local_shapes

    params = jax.eval_shape(
        lambda k: init_gpt(k, gpt_tiny()), jax.random.PRNGKey(0))
    specs = match_partition_rules(gpt_rules(), params)
    local = _local_shapes(params, specs, {ps.TENSOR_AXIS: shape.tp})
    opt = DistributedFusedAdam(dp_size=shape.dp, m_dtype=jnp.bfloat16)
    return opt.state_bytes_per_device(local)


def _halo_volume_model():
    """The context-ring halo law: each rank ships one fixed-width halo
    strip left and one right per conv, so the priced ppermute volume
    (bytes x hop count) is linear in cp with a fitted per-hop
    coefficient. Anything super-linear means the halo width grew with
    the ring — a hardcoded-size bug."""
    return {"ppermute": (("halo*cp", lambda s: float(s.cp)),)}


def _sharded_table_trees():
    """name -> (rules, trees) for every rule table the sharded tier
    registers, re-used for the APX904 audit so the two tiers can never
    drift apart on what a 'registered table' is."""
    from apex_tpu.lint.sharded import registry as sharded

    out = {}
    for e in sharded.repo_entries():
        if e.trees is not None:
            out[e.name] = (e.rules, e.trees)
    return out


def _draft_medium_trees():
    """The medium-config drafter trees: the serving headline pairs
    ``draft_gpt_medium`` with ``gpt_medium`` on ONE mesh, so its param
    tree and lockstep cache must survive the same swept tp sizes as the
    target's — a head count indivisible at a swept tp fires APX904 here
    before the drafter ever shares a pod slice."""
    import functools as ft

    import jax

    from apex_tpu.models.gpt import draft_gpt_medium, init_gpt
    from apex_tpu.serving.draft_model import init_draft_cache

    cfg = draft_gpt_medium()
    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(ft.partial(init_draft_cache, cfg, 2, 37))
    return {"params": params, "kv_cache": cache}


def repo_entries() -> List[ScalingEntry]:
    from apex_tpu.partition import draft_gpt_rules

    entries = [
        # the ROADMAP item-5 headline program swept across the whole
        # (dp, tp) grid — gpt_tiny_dp4xtp2_zero's shape is one point;
        # every shape's collective volume is pinned byte-exact in
        # budgets.json as gpt_tiny_zero@<tag>
        ScalingEntry(
            "gpt_tiny_zero_sweep",
            "apex_tpu.contrib.optimizers.distributed_fused_adam",
            build=lambda shape: zero_parts(dp=shape.dp, tp=shape.tp),
            grid=ZERO_GRID,
            checks=("schedule", "volume", "memory"),
            volume_model=_zero_volume_model,
            state_bytes=_zero_state_bytes,
            budget_name="gpt_tiny_zero"),
        # the context-parallel halo exchange swept across ring sizes —
        # the cp axis's first scale-invariance coverage (ROADMAP item
        # 5's ring-attention prerequisite)
        ScalingEntry(
            "bottleneck_halo_sweep",
            "apex_tpu.contrib.bottleneck.bottleneck",
            build=lambda shape: bottleneck_parts(),
            grid=HALO_GRID,
            checks=("schedule", "volume", "memory"),
            volume_model=_halo_volume_model,
            budget_name="bottleneck_halo"),
    ]
    # one table-audit entry per sharded-tier rule table, plus the
    # medium drafter trees against the draft table (the tp-envelope the
    # serving headline actually needs)
    for name, (rules, trees) in sorted(_sharded_table_trees().items()):
        entries.append(ScalingEntry(
            f"{name}_scale", "apex_tpu.partition.tables",
            checks=("tables",), rules=rules, trees=trees,
            grid=FULL_GRID))
    entries.append(ScalingEntry(
        "gpt_draft_medium_rules_scale", "apex_tpu.partition.tables",
        checks=("tables",), rules=draft_gpt_rules,
        trees=_draft_medium_trees, grid=FULL_GRID))
    return entries


def sweep_cost_reports() -> Tuple[list, List[Finding]]:
    """Per-shape CostReports for every swept entry — the
    ``--write-budgets`` input that regenerates the @-tagged rows."""
    findings: List[Finding] = []
    reports: list = []
    for e in repo_entries():
        if e.build is None:
            continue
        reports.extend(
            s.report for s in stage_entry(e, findings=findings))
    return reports, findings


def check_repo() -> List[Finding]:
    return run_entries(repo_entries())
