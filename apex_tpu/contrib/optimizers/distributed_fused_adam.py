"""ZeRO-style distributed Adam — optimizer state sharded over data ranks.

Reference: ``apex/contrib/optimizers/distributed_fused_adam.py ::
DistributedFusedAdam`` (kernel ``distributed_adam_cuda``) — the ZeRO
optimizer: gradients are reduce-scattered across the data-parallel group,
each rank owns 1/dp of the fp32 master params and Adam moments, updates
only its shard, and the updated params are all-gathered back. Grad
communication collapses from allreduce+replicated-state to
reduce_scatter+all_gather with 1/dp per-rank state memory.

TPU redesign:

- The shard unit is a ROW of the multi-tensor engine's flat ``(R, 128)``
  buffer (``multi_tensor_apply.flatten``): params/moments flatten once
  into tile-aligned flat buffers, and rank d owns rows
  ``[d·R/dp, (d+1)·R/dp)``. No per-tensor bucketing logic — the CUDA
  implementation's block/bucket bookkeeping is replaced by one reshape.
- ``step`` runs INSIDE ``parallel_state.shard_map`` with the ``data``
  axis bound: ``lax.psum_scatter`` (grads, tiled) → local fused update →
  ``lax.all_gather`` (params, tiled). XLA schedules both collectives to
  overlap with the elementwise update where profitable.
- At rest the state is a GLOBAL ``(R, 128)`` array whose
  ``partition_spec()`` is ``P("data", None)``: under GSPMD/``device_put``
  each device PHYSICALLY stores only its R/dp rows — the ZeRO memory
  saving — while the code addresses it as one logical array.
- The fp32 master weights live in the state (``state.master``) and are
  authoritative; ``step`` returns the full-precision params all-gathered
  and cast back to the model dtype. This subsumes amp-O2 master weights
  for the ZeRO path (the reference likewise absorbs
  ``FP16_Optimizer``-style master storage).
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.multi_tensor_apply import flatten as _flatten
from apex_tpu.optimizers._common import check_m_dtype, f32, select_finite
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.utils.profiler import region


class DistributedAdamState(NamedTuple):
    step: jax.Array
    master: jax.Array   # (R, 128) fp32 — shard over rows at rest
    m: jax.Array        # (R, 128) fp32 or bf16 (``m_dtype``)
    v: jax.Array        # (R, 128) fp32


def _check_shardable(total_rows: int, dp: int) -> None:
    if total_rows % dp:
        raise ValueError(
            f"flat buffer rows {total_rows} not divisible by data-parallel "
            f"size {dp}; ALIGN_ROWS={_flatten.ALIGN_ROWS} guarantees this "
            "for power-of-two dp <= 256")


class DistributedFusedAdam:
    """Construct OUTSIDE shard_map; call ``step`` INSIDE shard_map with
    the ``data`` axis bound (state passed with ``partition_spec()``)."""

    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999),
                 eps: float = 1e-8, adam_w_mode: bool = True,
                 weight_decay: float = 0.0, *,
                 average_grads: bool = True,
                 dp_size: Optional[int] = None,
                 axis_name: str = ps.DATA_AXIS,
                 m_dtype=jnp.float32):
        self.lr = lr
        # reduced-precision first moment: the bf16 shard halves m's share
        # of the at-rest state (see ``state_bytes_per_device``); the
        # update still accumulates in fp32 and stores round-to-nearest.
        self.m_dtype = check_m_dtype(m_dtype)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.average_grads = average_grads
        self.axis_name = axis_name
        self.dp = dp_size if dp_size is not None else \
            ps.get_data_parallel_world_size()
        self._specs = {}

    def _layout(self, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef,
               tuple((l.shape, jnp.dtype(l.dtype)) for l in leaves))
        spec = self._specs.get(key)
        if spec is None:
            spec = self._specs[key] = _flatten.make_spec(leaves)
            _check_shardable(spec.total_rows, self.dp)
        return leaves, treedef, spec

    def init(self, params: Any) -> DistributedAdamState:
        leaves, _, spec = self._layout(params)
        master, _ = _flatten.flatten_tensors(leaves, spec,
                                             dtype=jnp.float32)
        return DistributedAdamState(
            step=jnp.zeros((), jnp.int32), master=master,
            m=jnp.zeros(master.shape, self.m_dtype),
            v=jnp.zeros_like(master))

    def partition_spec(self, *, tensor_axis: Optional[str] = None
                       ) -> DistributedAdamState:
        """PartitionSpecs for the state pytree (shard_map in_specs /
        ``NamedSharding`` at rest): master/m/v row-sharded over data.

        Under dp x tp the flat buffers are built from TP-LOCAL param
        shards, so each tp rank holds different rows: pass
        ``tensor_axis`` to shard the row dim over ``(tensor_axis, data)``
        jointly — tuple order is major-to-minor, so rank ``(t, d)`` owns
        block ``t*dp + d``, matching the per-(t,d) ``psum_scatter`` over
        ``data`` inside :meth:`step`."""
        from jax.sharding import PartitionSpec as P

        if tensor_axis is None:
            row = P(self.axis_name, None)
        else:
            row = P((tensor_axis, self.axis_name), None)
        return DistributedAdamState(step=P(), master=row, m=row, v=row)

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: DistributedAdamState,
             *, lr=None, grad_scale=1.0, weight_decay=None,
             found_inf: Optional[jax.Array] = None
             ) -> Tuple[Any, DistributedAdamState]:
        """One ZeRO step. ``grads`` are the rank-LOCAL (unreduced) grads —
        do NOT pre-average with DDP; the reduce-scatter averages here
        (``average_grads``). ``grad_scale`` MULTIPLIES (inverse loss
        scale), the package-wide convention. ``params`` supplies
        structure/dtypes only — ``state.master`` is authoritative.
        Returns (full params in model dtype, new state)."""
        leaves, treedef, spec = self._layout(params)
        ax = self.axis_name
        lr = f32(self.lr if lr is None else lr)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)
        gs = f32(grad_scale)
        if self.average_grads:
            gs = gs / self.dp

        gbuf, _ = _flatten.flatten_tensors(
            jax.tree_util.tree_leaves(grads), spec)
        # ZeRO collective #1: sum-reduce + scatter rows in rank order
        g_local = lax.psum_scatter(gbuf, ax, scatter_dimension=0,
                                   tiled=True)

        t = state.step + 1
        b1, b2, eps = f32(self.beta1), f32(self.beta2), f32(self.eps)
        tf = t.astype(jnp.float32)
        if self.bias_correction:
            c1, c2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf
        else:
            c1 = c2 = jnp.float32(1.0)

        g = g_local.astype(jnp.float32) * gs
        p32 = state.master
        if not self.adam_w_mode:
            g = g + wd * p32
        m = b1 * state.m.astype(jnp.float32) + (1.0 - b1) * g
        v = b2 * state.v + (1.0 - b2) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if self.adam_w_mode:
            u = u + wd * p32
        master = p32 - lr * u

        new_state = DistributedAdamState(
            step=t, master=master, m=m.astype(self.m_dtype), v=v)
        if found_inf is not None:
            # a rank-local overflow must skip the step EVERYWHERE — the
            # shards are disjoint, so OR across the data group first
            found_inf = lax.pmax(
                jnp.asarray(found_inf).astype(jnp.int32), ax) > 0
        new_state = select_finite(found_inf, new_state, state)

        # ZeRO collective #2: regather the updated master rows
        full = lax.all_gather(new_state.master, ax, axis=0, tiled=True)
        new_params = jax.tree_util.tree_unflatten(
            treedef, _flatten.unflatten_tensors(full, spec))
        return new_params, new_state

    def state_bytes_per_device(self, params: Any) -> int:
        """Per-device optimizer-state bytes at rest (the ~1/dp claim):
        master + v at 4 bytes each, m at ``m_dtype`` width."""
        _, _, spec = self._layout(params)
        per_elem = 4 + 4 + jnp.dtype(self.m_dtype).itemsize
        return per_elem * (spec.total_rows // self.dp) * _flatten.LANES
