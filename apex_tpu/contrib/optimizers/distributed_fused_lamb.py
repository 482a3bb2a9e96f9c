"""ZeRO-style distributed LAMB — sharded state + per-tensor trust ratios.

Reference: ``apex/contrib/optimizers/distributed_fused_lamb.py ::
DistributedFusedLAMB`` (kernel ``distributed_lamb_cuda``) — LAMB with the
optimizer state sharded across the data-parallel group, used for the
large-batch BERT MLPerf runs.

Same flat-row sharding as ``DistributedFusedAdam``; what LAMB adds is
cross-shard reductions (per the two CUDA stages):

- the GLOBAL grad norm for clipping: local sum-of-squares → psum;
- per-TENSOR ``||p||``/``||u||`` for trust ratios, where a tensor's rows
  may span several ranks: the flat layout's per-row tensor-id table makes
  this a ``segment_sum`` over the local rows followed by one psum of the
  (num_tensors,) vectors — the TPU analogue of the reference's
  ``reduce_scatter``-then-allreduce norm plumbing. Tile alignment
  guarantees pad lanes are zero, so segment sums need no masking.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.contrib.optimizers.distributed_fused_adam import (
    _check_shardable,
)
from apex_tpu.multi_tensor_apply import flatten as _flatten
from apex_tpu.optimizers._common import check_m_dtype, f32, select_finite
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.utils.profiler import region


class DistributedLambState(NamedTuple):
    step: jax.Array
    master: jax.Array
    m: jax.Array       # fp32 or bf16 (``m_dtype``)
    v: jax.Array


class DistributedFusedLAMB:
    """Construct OUTSIDE shard_map; ``step`` INSIDE (data axis bound)."""

    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999),
                 eps: float = 1e-6, weight_decay: float = 0.01,
                 adam_w_mode: bool = True, grad_averaging: bool = True,
                 max_grad_norm: float = 1.0, use_nvlamb: bool = False, *,
                 average_grads: bool = True,
                 dp_size: Optional[int] = None,
                 axis_name: str = ps.DATA_AXIS,
                 m_dtype=jnp.float32):
        self.lr = lr
        self.m_dtype = check_m_dtype(m_dtype)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb
        self.average_grads = average_grads
        self.axis_name = axis_name
        self.dp = dp_size if dp_size is not None else \
            ps.get_data_parallel_world_size()
        self._specs = {}

    def _layout(self, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef,
               tuple((l.shape, jnp.dtype(l.dtype)) for l in leaves))
        cached = self._specs.get(key)
        if cached is None:
            spec = _flatten.make_spec(leaves)
            _check_shardable(spec.total_rows, self.dp)
            # per-ROW tensor ids (tail padding -> last tensor; its pad
            # lanes are zero so segment sums are unaffected)
            row_ids = jnp.asarray(
                spec.tile_tensor_ids(tile_rows=1), jnp.int32)
            cached = self._specs[key] = (spec, row_ids)
        return leaves, treedef, cached[0], cached[1]

    def init(self, params: Any) -> DistributedLambState:
        leaves, _, spec, _ = self._layout(params)
        master, _ = _flatten.flatten_tensors(leaves, spec,
                                             dtype=jnp.float32)
        return DistributedLambState(
            step=jnp.zeros((), jnp.int32), master=master,
            m=jnp.zeros(master.shape, self.m_dtype),
            v=jnp.zeros_like(master))

    def partition_spec(self) -> DistributedLambState:
        from jax.sharding import PartitionSpec as P

        row = P(self.axis_name, None)
        return DistributedLambState(step=P(), master=row, m=row, v=row)

    def _local_row_ids(self, row_ids, local_rows):
        d = lax.axis_index(self.axis_name)
        return lax.dynamic_slice_in_dim(row_ids, d * local_rows,
                                        local_rows, 0)

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: DistributedLambState,
             *, lr=None, weight_decay=None, grad_scale=1.0,
             found_inf: Optional[jax.Array] = None
             ) -> Tuple[Any, DistributedLambState]:
        """ZeRO LAMB step (rank-local unreduced ``grads``; ``grad_scale``
        MULTIPLIES — package convention, the reference's scale divides)."""
        leaves, treedef, spec, row_ids = self._layout(params)
        ax = self.axis_name
        T = spec.num_tensors
        lr = f32(self.lr if lr is None else lr)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)
        gs = f32(grad_scale)
        if self.average_grads:
            gs = gs / self.dp
        b1, b2, eps = f32(self.beta1), f32(self.beta2), f32(self.eps)
        t = state.step + 1
        tf = t.astype(jnp.float32)
        beta3 = 1.0 - b1 if self.grad_averaging else jnp.float32(1.0)
        if self.bias_correction:
            c1, c2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf
        else:
            c1 = c2 = jnp.float32(1.0)

        gbuf, _ = _flatten.flatten_tensors(
            jax.tree_util.tree_leaves(grads), spec)
        g = lax.psum_scatter(gbuf, ax, scatter_dimension=0,
                             tiled=True).astype(jnp.float32) * gs

        # stage-1 preamble: GLOBAL grad-norm clip (psum of local ssq —
        # shards are disjoint so this is the exact global norm)
        grad_norm = jnp.sqrt(lax.psum(jnp.sum(g * g), ax))
        max_norm = f32(self.max_grad_norm)
        clip = jnp.where((max_norm > 0) & (grad_norm > max_norm),
                         grad_norm / max_norm, jnp.float32(1.0))
        g = g / clip

        p32 = state.master
        if not self.adam_w_mode:
            g = g + wd * p32
        m = b1 * state.m.astype(jnp.float32) + beta3 * g
        v = b2 * state.v + (1.0 - b2) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if self.adam_w_mode:
            u = u + wd * p32

        # stage 2: per-tensor trust ratios across shard boundaries
        local_ids = self._local_row_ids(row_ids, g.shape[0])
        w_ssq = lax.psum(jax.ops.segment_sum(
            jnp.sum(p32 * p32, axis=1), local_ids, num_segments=T), ax)
        u_ssq = lax.psum(jax.ops.segment_sum(
            jnp.sum(u * u, axis=1), local_ids, num_segments=T), ax)
        w_norm, u_norm = jnp.sqrt(w_ssq), jnp.sqrt(u_ssq)
        ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm,
                          jnp.float32(1.0))
        if not self.use_nvlamb:
            ratio = jnp.where(wd == 0.0, jnp.ones_like(ratio), ratio)
        master = p32 - lr * ratio[local_ids][:, None] * u

        new_state = DistributedLambState(
            step=t, master=master, m=m.astype(self.m_dtype), v=v)
        if found_inf is not None:
            found_inf = lax.pmax(
                jnp.asarray(found_inf).astype(jnp.int32), ax) > 0
        new_state = select_finite(found_inf, new_state, state)

        full = lax.all_gather(new_state.master, ax, axis=0, tiled=True)
        new_params = jax.tree_util.tree_unflatten(
            treedef, _flatten.unflatten_tensors(full, spec))
        return new_params, new_state

    def state_bytes_per_device(self, params: Any) -> int:
        _, _, spec, _ = self._layout(params)
        per_elem = 4 + 4 + jnp.dtype(self.m_dtype).itemsize
        return per_elem * (spec.total_rows // self.dp) * _flatten.LANES
