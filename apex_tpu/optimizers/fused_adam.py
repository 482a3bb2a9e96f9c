"""FusedAdam — ref ``apex/optimizers/fused_adam.py :: class FusedAdam``
(kernel: ``csrc/multi_tensor_adam.cu``).

Two execution paths:

- default: per-leaf jnp updates inside the caller's jit — XLA fuses the
  whole step into a few elementwise kernels (the TPU analogue of the
  single multi-tensor launch);
- ``use_flat_kernel=True``: m/v live as packed ``(rows, 128)`` fp32 buffers
  updated in place by ONE Pallas pass (``kernels.flat_adam``; buffers are
  BLOCK_ROWS-aligned so aliasing is copy-free). Grads and params still
  round-trip through flatten/unflatten each step (~3 extra HBM passes), so
  this path pays off only when per-leaf launch overhead dominates (very
  many small tensors); the tree path is the default for good reason.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import flatten as _flatten
from apex_tpu.multi_tensor_apply import kernels as _kernels
from apex_tpu.optimizers._common import (
    check_m_dtype, finish_compute_params, flat_layout,
    f32, select_finite, tree_unzip, tree_zeros,
)
from apex_tpu.utils.profiler import region


class AdamState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


class FusedAdam:
    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 adam_w_mode: bool = True, weight_decay: float = 0.0,
                 amsgrad: bool = False, *, use_flat_kernel: bool = False,
                 m_dtype=jnp.float32, emit_compute_params: bool = False):
        if amsgrad:
            # matches the reference: FusedAdam raises on amsgrad
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.use_flat_kernel = use_flat_kernel
        # reduced-precision first moment (fp32 accumulate, v stays fp32)
        self.m_dtype = check_m_dtype(m_dtype)
        # fused cast-out: step additionally returns the updated params
        # pre-cast to the compute dtypes (amp-O2 skips model_params_
        # from_master); see _common.finish_compute_params
        self.emit_compute_params = emit_compute_params
        # layout cache keyed by treedef: one optimizer instance may serve
        # several param trees (init called more than once)
        self._specs = {}

    def init(self, params: Any) -> AdamState:
        step = jnp.zeros((), jnp.int32)
        if self.use_flat_kernel:
            leaves, _, spec, _ = flat_layout(self._specs, params)
            return AdamState(step=step,
                             m=_flatten.zeros_buffer(spec, self.m_dtype),
                             v=_flatten.zeros_buffer(spec, jnp.float32))
        return AdamState(step=step, m=tree_zeros(params, self.m_dtype),
                         v=tree_zeros(params, jnp.float32))

    def state_partition_specs(self, param_specs: Any) -> AdamState:
        """PartitionSpecs for the (tree-layout) state, given the params'
        spec tree: moments shard exactly like their params, the step
        counter replicates. The APX702 sharding check verifies the
        partition-rule tables reproduce this tensor-by-tensor. Not valid
        with ``use_flat_kernel`` (the flat buffer has its own layout)."""
        if self.use_flat_kernel:
            raise ValueError(
                "state_partition_specs describes the tree layout; the flat "
                "kernel's packed buffer is sharded by its caller")
        from jax.sharding import PartitionSpec as P

        return AdamState(step=P(), m=param_specs, v=param_specs)

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: AdamState, *,
             lr=None, grad_scale=1.0, weight_decay=None,
             found_inf: Optional[jax.Array] = None,
             compute_params: Optional[Any] = None):
        """One optimizer step.

        ``grad_scale`` MULTIPLIES the gradients (it is the combined
        inverse loss scale: pass ``1 / loss_scale`` to unscale). Note the
        reference's ``FusedAdam.step(scale=...)`` takes the factor to
        DIVIDE by; callers porting from apex must invert. This convention
        is uniform across every ``apex_tpu.optimizers`` step and the flat
        Pallas kernel (``kernels.flat_adam``), chosen so the unscale
        fuses into the update as a multiply without a reciprocal op.

        With ``emit_compute_params`` the return grows to ``(params,
        state, compute)`` where ``compute`` is the updated params cast to
        the dtypes of ``compute_params`` (the previous compute tree —
        pass it; it also provides the cheap overflow-skip fallback) or
        uniformly bf16 when ``compute_params`` is None.
        """
        lr = f32(self.lr if lr is None else lr)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)
        t = state.step + 1

        with jax.named_scope("FusedAdam.step"):
            if self.use_flat_kernel:
                new_params, new_state, pc = self._flat_step(
                    grads, params, state, lr, wd, t, grad_scale)
            else:
                new_params, new_state = self._tree_step(
                    grads, params, state, lr, wd, t, grad_scale)
                pc = None

        # On overflow the reference skips optimizer.step() entirely, so
        # params AND optimizer state (including the step count) stay put.
        new_params = select_finite(found_inf, new_params, params)
        new_state = select_finite(found_inf, new_state, state)
        if not self.emit_compute_params:
            return new_params, new_state
        if pc is not None and compute_params is not None:
            # kernel emits uniform bf16; leaves whose compute dtype
            # differs (e.g. keep-fp32 norms) re-cast from the (selected)
            # master — those leaves are the small minority by bytes
            pc = jax.tree.map(
                lambda c, tmpl, p: c if c.dtype == tmpl.dtype
                else p.astype(tmpl.dtype),
                pc, compute_params, new_params)
        compute = finish_compute_params(new_params, params, compute_params,
                                        found_inf, precomputed=pc)
        return new_params, new_state, compute

    # -- paths ----------------------------------------------------------
    def _tree_step(self, grads, params, state, lr, wd, t, grad_scale):
        b1, b2, eps = f32(self.beta1), f32(self.beta2), f32(self.eps)
        gs = f32(grad_scale)
        tf = t.astype(jnp.float32)
        if self.bias_correction:
            c1 = 1.0 - b1 ** tf
            c2 = 1.0 - b2 ** tf
        else:
            c1 = c2 = jnp.float32(1.0)
        aw = self.adam_w_mode

        md = self.m_dtype

        def upd(g, p, m, v):
            g = g.astype(jnp.float32) * gs
            p32 = p.astype(jnp.float32)
            if not aw:
                g = g + wd * p32
            m = b1 * m.astype(jnp.float32) + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if aw:
                u = u + wd * p32
            return (p32 - lr * u).astype(p.dtype), m.astype(md), v

        out = jax.tree.map(upd, grads, params, state.m, state.v)
        new_params, new_m, new_v = tree_unzip(out, 3)
        return new_params, AdamState(step=t, m=new_m, v=new_v)

    def _flat_step(self, grads, params, state, lr, wd, t, grad_scale):
        leaves, treedef, spec, _ = flat_layout(self._specs, params)
        gbuf, _ = _flatten.flatten_tensors(
            jax.tree_util.tree_leaves(grads), spec)
        pbuf, _ = _flatten.flatten_tensors(leaves, spec)
        emit_dt = jnp.bfloat16 if self.emit_compute_params else None
        outs = _kernels.flat_adam(
            gbuf, pbuf, state.m, state.v,
            lr=lr, beta1=self.beta1, beta2=self.beta2, eps=self.eps,
            step=t, weight_decay=wd, adam_w_mode=self.adam_w_mode,
            bias_correction=self.bias_correction, grad_scale=grad_scale,
            emit_compute_dtype=emit_dt)
        p_new, m_new, v_new = outs[:3]
        new_params = jax.tree_util.tree_unflatten(
            treedef, _flatten.unflatten_tensors(p_new, spec))
        pc = None
        if emit_dt is not None:
            pc = jax.tree_util.tree_unflatten(
                treedef,
                _flatten.unflatten_tensors(outs[3], spec, cast_back=False))
        return new_params, AdamState(step=t, m=m_new, v=v_new), pc
