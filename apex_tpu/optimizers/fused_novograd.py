"""FusedNovoGrad — ref ``apex/optimizers/fused_novograd.py``
(kernel: ``csrc/multi_tensor_novograd.cu``).

NovoGrad keeps the second moment as ONE scalar per tensor (the layer-wise
EMA of ||g||²), so ``v`` here is a pytree of fp32 scalars. First step seeds
``v`` with ||g||² unless ``init_zero``.

``use_flat_kernel=True`` runs the step on packed ``(rows, 128)`` flat
fp32 buffers (``kernels.flat_novograd``): one l2 pre-pass for the
per-tensor ||g||² (the LAMB-style two-stage reduction over
``tile_tensor_ids``), then ONE in-place Pallas pass for the
moment/param update — the one-fused-pass-per-step property of
``multi_tensor_novograd.cu``. ``v`` is then a ``(num_tensors,)``
vector."""

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


class NovoGradState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any  # per-tensor scalars


class FusedNovoGrad:
    def __init__(self, lr: float = 1e-3,
                 betas: Tuple[float, float] = (0.95, 0.98), eps: float = 1e-8,
                 weight_decay: float = 0.0, amsgrad: bool = False,
                 reg_inside_moment: bool = False, grad_averaging: bool = True,
                 norm_type: int = 2, init_zero: bool = False,
                 bias_correction: bool = True, *,
                 use_flat_kernel: bool = False,
                 m_dtype=jnp.float32, emit_compute_params: bool = False):
        self.m_dtype = check_m_dtype(m_dtype)
        self.emit_compute_params = emit_compute_params
        if amsgrad:
            raise RuntimeError(
                "FusedNovoGrad does not support the AMSGrad variant.")
        if norm_type != 2:
            raise ValueError("FusedNovoGrad only supports norm_type=2")
        self.lr = lr
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.reg_inside_moment = reg_inside_moment
        self.grad_averaging = grad_averaging
        self.init_zero = init_zero
        self.bias_correction = bias_correction
        self.use_flat_kernel = use_flat_kernel
        self._specs = {}

    def init(self, params: Any) -> NovoGradState:
        step = jnp.zeros((), jnp.int32)
        if self.use_flat_kernel:
            leaves, _, spec, _ = flat_layout(self._specs, params)
            return NovoGradState(
                step=step, m=_flatten.zeros_buffer(spec, self.m_dtype),
                v=jnp.zeros((spec.num_tensors,), jnp.float32))
        return NovoGradState(
            step=step,
            m=tree_zeros(params, self.m_dtype),
            v=jax.tree.map(lambda p: jnp.zeros((), jnp.float32), params))

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: NovoGradState, *,
             lr=None, grad_scale=1.0, weight_decay=None,
             found_inf: Optional[jax.Array] = None,
             compute_params: Optional[Any] = None):
        """``grad_scale`` MULTIPLIES the gradients (combined inverse loss
        scale: pass ``1 / loss_scale``); the reference's ``scale`` arg
        DIVIDES — invert when porting. With ``emit_compute_params`` the
        return grows to ``(params, state, compute)``. See
        ``FusedAdam.step``."""
        lr = f32(self.lr if lr is None else lr)
        gs = f32(grad_scale)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)
        t = state.step + 1

        if self.use_flat_kernel:
            leaves, treedef, spec, tile_ids = flat_layout(self._specs,
                                                          params)
            gbuf, _ = _flatten.flatten_tensors(
                jax.tree_util.tree_leaves(grads), spec)
            pbuf, _ = _flatten.flatten_tensors(leaves, spec)
            emit_dt = jnp.bfloat16 if self.emit_compute_params else None
            outs = _kernels.flat_novograd(
                gbuf, pbuf, state.m, state.v,
                tile_ids, lr=lr, beta1=self.beta1,
                beta2=self.beta2, eps=self.eps, step=t, weight_decay=wd,
                num_tensors=spec.num_tensors,
                grad_averaging=self.grad_averaging,
                bias_correction=self.bias_correction,
                reg_inside_moment=self.reg_inside_moment,
                init_zero=self.init_zero, grad_scale=gs,
                emit_compute_dtype=emit_dt)
            p_new, m_new, v_new = outs[:3]
            new_params = jax.tree_util.tree_unflatten(
                treedef, _flatten.unflatten_tensors(p_new, spec))
            new_state = NovoGradState(step=t, m=m_new, v=v_new)
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

        b1, b2, eps = f32(self.beta1), f32(self.beta2), f32(self.eps)
        tf = t.astype(jnp.float32)
        first = (state.step == 0)
        beta3 = 1.0 - b1 if self.grad_averaging else jnp.float32(1.0)
        if self.bias_correction:
            c1 = 1.0 - b1 ** tf
            c2 = 1.0 - b2 ** tf
        else:
            c1 = c2 = jnp.float32(1.0)

        def upd(g, p, m, v):
            g = g.astype(jnp.float32) * gs
            p32 = p.astype(jnp.float32)
            gsq = jnp.sum(g * g)
            if self.init_zero:
                v = b2 * v + (1.0 - b2) * gsq
            else:
                v = jnp.where(first, gsq, b2 * v + (1.0 - b2) * gsq)
            denom = jnp.sqrt(v / c2) + eps
            gn = g / denom
            if self.reg_inside_moment:
                gn = gn + wd * p32
            m = b1 * m.astype(jnp.float32) + beta3 * gn
            u = m / c1
            if not self.reg_inside_moment:
                u = u + wd * p32
            return (p32 - lr * u).astype(p.dtype), m.astype(self.m_dtype), v

        out = jax.tree.map(upd, grads, params, state.m, state.v)
        new_params, new_m, new_v = tree_unzip(out, 3)
        new_state = NovoGradState(step=t, m=new_m, v=new_v)

        new_params = select_finite(found_inf, new_params, params)
        new_state = select_finite(found_inf, new_state, state)
        if not self.emit_compute_params:
            return new_params, new_state
        compute = finish_compute_params(new_params, params, compute_params,
                                        found_inf)
        return new_params, new_state, compute
