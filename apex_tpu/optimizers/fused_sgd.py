"""FusedSGD — ref ``apex/optimizers/fused_sgd.py :: class FusedSGD``
(kernel: ``csrc/multi_tensor_sgd_kernel.cu``).

Momentum/nesterov/dampening/weight-decay semantics follow torch.optim.SGD
as the reference does; the first momentum step seeds the buffer with the
gradient (reference's ``first_run`` flag)."""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.optimizers._common import (
    check_m_dtype, f32, finish_compute_params, select_finite, tree_unzip,
    tree_zeros,
)
from apex_tpu.utils.profiler import region


class SGDState(NamedTuple):
    step: jax.Array
    momentum_buf: Any


class FusedSGD:
    def __init__(self, lr: float, momentum: float = 0.0,
                 dampening: float = 0.0, weight_decay: float = 0.0,
                 nesterov: bool = False, *,
                 wd_after_momentum: bool = False,
                 use_flat_kernel: bool = False,
                 m_dtype=jnp.float32, emit_compute_params: bool = False):
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError(
                "Nesterov momentum requires a momentum and zero dampening")
        # ``m`` here is the momentum buffer (SGD's only moment)
        self.m_dtype = check_m_dtype(m_dtype)
        self.emit_compute_params = emit_compute_params
        self.lr = lr
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.wd_after_momentum = wd_after_momentum
        self.use_flat_kernel = use_flat_kernel
        self._specs = {}

    def _layout(self, params):
        from apex_tpu.optimizers._common import flat_layout

        leaves, treedef, spec, _ = flat_layout(self._specs, params)
        return leaves, treedef, spec

    def init(self, params: Any) -> SGDState:
        step = jnp.zeros((), jnp.int32)
        if self.use_flat_kernel:
            from apex_tpu.multi_tensor_apply import flatten as _flatten

            leaves, _, spec = self._layout(params)
            return SGDState(
                step=step,
                momentum_buf=_flatten.zeros_buffer(spec, self.m_dtype))
        return SGDState(step=step,
                        momentum_buf=tree_zeros(params, self.m_dtype))

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: SGDState, *,
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
        mom, damp = f32(self.momentum), f32(self.dampening)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)
        t = state.step + 1
        first = (state.step == 0)

        if self.use_flat_kernel:
            from apex_tpu.multi_tensor_apply import flatten as _flatten
            from apex_tpu.multi_tensor_apply.kernels import flat_sgd

            leaves, treedef, spec = self._layout(params)
            gbuf, _ = _flatten.flatten_tensors(
                jax.tree_util.tree_leaves(grads), spec)
            pbuf, _ = _flatten.flatten_tensors(leaves, spec)
            emit_dt = jnp.bfloat16 if self.emit_compute_params else None
            outs = flat_sgd(
                gbuf, pbuf, state.momentum_buf, lr=lr,
                momentum=self.momentum, dampening=self.dampening,
                weight_decay=wd, nesterov=self.nesterov,
                wd_after_momentum=self.wd_after_momentum,
                first_run=first, grad_scale=gs, emit_compute_dtype=emit_dt)
            p_new, b_new = outs[:2]
            new_params = jax.tree_util.tree_unflatten(
                treedef, _flatten.unflatten_tensors(p_new, spec))
            new_state = SGDState(step=t, momentum_buf=b_new)
            new_params = select_finite(found_inf, new_params, params)
            new_state = select_finite(found_inf, new_state, state)
            if not self.emit_compute_params:
                return new_params, new_state
            pc = jax.tree_util.tree_unflatten(
                treedef,
                _flatten.unflatten_tensors(outs[2], spec, cast_back=False))
            if compute_params is not None:
                pc = jax.tree.map(
                    lambda c, tmpl, p: c if c.dtype == tmpl.dtype
                    else p.astype(tmpl.dtype),
                    pc, compute_params, new_params)
            compute = finish_compute_params(
                new_params, params, compute_params, found_inf,
                precomputed=pc)
            return new_params, new_state, compute

        def upd(g, p, buf):
            g = g.astype(jnp.float32) * gs
            p32 = p.astype(jnp.float32)
            if not self.wd_after_momentum:
                g = g + wd * p32
            if self.momentum > 0:
                seeded = jnp.where(first, g,
                                   mom * buf.astype(jnp.float32)
                                   + (1.0 - damp) * g)
                d = g + mom * seeded if self.nesterov else seeded
                buf = seeded.astype(self.m_dtype)
            else:
                d = g
            if self.wd_after_momentum:
                d = d + wd * p32
            return (p32 - lr * d).astype(p.dtype), buf

        out = jax.tree.map(upd, grads, params, state.momentum_buf)
        new_params, new_buf = tree_unzip(out, 2)
        new_state = SGDState(step=t, momentum_buf=new_buf)

        new_params = select_finite(found_inf, new_params, params)
        new_state = select_finite(found_inf, new_state, state)
        if not self.emit_compute_params:
            return new_params, new_state
        compute = finish_compute_params(new_params, params, compute_params,
                                        found_inf)
        return new_params, new_state, compute
