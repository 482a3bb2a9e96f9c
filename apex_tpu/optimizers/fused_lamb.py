"""FusedLAMB — ref ``apex/optimizers/fused_lamb.py :: class FusedLAMB``
(kernels: ``csrc/multi_tensor_lamb.cu`` / ``_stage_1`` / ``_stage_2``).

The two CUDA stages map onto:
stage 1 — grad clipping by the GLOBAL grad norm, then Adam-style moments and
the raw update ``u = m̂/(√v̂+eps) + wd·p``;
stage 2 — per-TENSOR trust ratio ``||p|| / ||u||`` applied with the lr.

Per-tensor norms are per-leaf reductions here (each leaf IS a tensor);
under sharding the global norm must be psum-ed — pass ``grad_norm`` in if
you computed it with a collective.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers._common import (
    check_m_dtype, f32, finish_compute_params, global_grad_norm,
    select_finite, tree_unzip, tree_zeros, tree_zeros_f32,
)
from apex_tpu.utils.profiler import region


class LambState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


class FusedLAMB:
    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-6,
                 weight_decay: float = 0.01, amsgrad: bool = False,
                 adam_w_mode: bool = True, grad_averaging: bool = True,
                 max_grad_norm: float = 1.0,
                 use_nvlamb: bool = False, *,
                 use_flat_kernel: bool = False,
                 m_dtype=jnp.float32, emit_compute_params: bool = False):
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad variant.")
        self.m_dtype = check_m_dtype(m_dtype)
        self.emit_compute_params = emit_compute_params
        self.lr = lr
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        # NVLAMB: apply the trust ratio even to tensors with no weight decay
        self.use_nvlamb = use_nvlamb
        self.use_flat_kernel = use_flat_kernel
        self._specs = {}

    def _layout(self, params):
        from apex_tpu.optimizers._common import flat_layout

        return flat_layout(self._specs, params)

    def init(self, params: Any) -> LambState:
        step = jnp.zeros((), jnp.int32)
        if self.use_flat_kernel:
            from apex_tpu.multi_tensor_apply import flatten as _flatten

            leaves, _, spec, _ = self._layout(params)
            return LambState(step=step,
                             m=_flatten.zeros_buffer(spec, self.m_dtype),
                             v=_flatten.zeros_buffer(spec, jnp.float32))
        return LambState(step=step,
                         m=tree_zeros(params, self.m_dtype),
                         v=tree_zeros_f32(params))

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: LambState, *,
             lr=None, weight_decay=None, grad_scale=1.0,
             grad_norm: Optional[jax.Array] = None,
             found_inf: Optional[jax.Array] = None,
             compute_params: Optional[Any] = None):
        """``grad_scale`` MULTIPLIES the gradients (combined inverse loss
        scale: pass ``1 / loss_scale``); the reference's ``scale`` arg
        DIVIDES — invert when porting. With ``emit_compute_params`` the
        return grows to ``(params, state, compute)``. See
        ``FusedAdam.step``."""
        lr = f32(self.lr if lr is None else lr)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)
        gs = f32(grad_scale)
        b1, b2, eps = f32(self.beta1), f32(self.beta2), f32(self.eps)
        t = state.step + 1
        tf = t.astype(jnp.float32)
        beta3 = 1.0 - b1 if self.grad_averaging else jnp.float32(1.0)
        if self.bias_correction:
            c1 = 1.0 - b1 ** tf
            c2 = 1.0 - b2 ** tf
        else:
            c1 = c2 = jnp.float32(1.0)

        if self.use_flat_kernel:
            from apex_tpu.multi_tensor_apply import flatten as _flatten
            from apex_tpu.multi_tensor_apply.kernels import flat_lamb

            leaves, treedef, spec, tile_ids = self._layout(params)
            gbuf, _ = _flatten.flatten_tensors(
                jax.tree_util.tree_leaves(grads), spec)
            pbuf, _ = _flatten.flatten_tensors(leaves, spec)
            emit_dt = jnp.bfloat16 if self.emit_compute_params else None
            outs = flat_lamb(
                gbuf, pbuf, state.m, state.v, tile_ids,
                lr=lr, beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                step=t, weight_decay=wd, num_tensors=spec.num_tensors,
                adam_w_mode=self.adam_w_mode,
                grad_averaging=self.grad_averaging,
                bias_correction=self.bias_correction,
                use_nvlamb=self.use_nvlamb,
                max_grad_norm=self.max_grad_norm, grad_scale=gs,
                grad_norm=grad_norm, emit_compute_dtype=emit_dt)
            p_new, m_new, v_new = outs[:3]
            new_params = jax.tree_util.tree_unflatten(
                treedef, _flatten.unflatten_tensors(p_new, spec))
            new_state = LambState(step=t, m=m_new, v=v_new)
            new_params = select_finite(found_inf, new_params, params)
            new_state = select_finite(found_inf, new_state, state)
            if not self.emit_compute_params:
                return new_params, new_state
            pc = jax.tree_util.tree_unflatten(
                treedef,
                _flatten.unflatten_tensors(outs[3], spec, cast_back=False))
            if compute_params is not None:
                pc = jax.tree.map(
                    lambda c, tmpl, p: c if c.dtype == tmpl.dtype
                    else p.astype(tmpl.dtype),
                    pc, compute_params, new_params)
            compute = finish_compute_params(
                new_params, params, compute_params, found_inf,
                precomputed=pc)
            return new_params, new_state, compute

        # stage 1 preamble: global-norm grad clipping
        if grad_norm is None:
            grad_norm = global_grad_norm(
                jax.tree.map(lambda g: g.astype(jnp.float32) * gs, grads))
        max_norm = f32(self.max_grad_norm)
        clip = jnp.where(
            (max_norm > 0) & (grad_norm > max_norm),
            grad_norm / max_norm, jnp.float32(1.0))

        def upd(g, p, m, v):
            g = g.astype(jnp.float32) * gs / clip
            p32 = p.astype(jnp.float32)
            if not self.adam_w_mode:
                g = g + wd * p32
            m = b1 * m.astype(jnp.float32) + beta3 * g
            v = b2 * v + (1.0 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if self.adam_w_mode:
                u = u + wd * p32
            # stage 2: layer-wise trust ratio
            w_norm = jnp.sqrt(jnp.sum(p32 * p32))
            u_norm = jnp.sqrt(jnp.sum(u * u))
            ratio = jnp.where((w_norm > 0) & (u_norm > 0),
                              w_norm / u_norm, jnp.float32(1.0))
            if not self.use_nvlamb:
                # reference: without NVLAMB, params with no weight decay
                # skip the trust-ratio (decoupled_wd group split); wd is a
                # scalar here so the split reduces to this where().
                ratio = jnp.where(wd == 0.0, jnp.float32(1.0), ratio)
            return ((p32 - lr * ratio * u).astype(p.dtype),
                    m.astype(self.m_dtype), v)

        out = jax.tree.map(upd, grads, params, state.m, state.v)
        new_params, new_m, new_v = tree_unzip(out, 3)
        new_state = LambState(step=t, m=new_m, v=new_v)

        new_params = select_finite(found_inf, new_params, params)
        new_state = select_finite(found_inf, new_state, state)
        if not self.emit_compute_params:
            return new_params, new_state
        compute = finish_compute_params(new_params, params, compute_params,
                                        found_inf)
        return new_params, new_state, compute
