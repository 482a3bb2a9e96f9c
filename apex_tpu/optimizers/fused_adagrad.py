"""FusedAdagrad — ref ``apex/optimizers/fused_adagrad.py``
(kernel: ``csrc/multi_tensor_adagrad.cu``).

``use_flat_kernel=True`` packs params/state into ``(rows, 128)`` flat
fp32 buffers and updates them with ONE in-place Pallas pass
(``kernels.flat_adagrad``) — the one-fused-pass-per-step property of the
CUDA multi-tensor kernel; see ``FusedAdam`` for when the flat path pays
off (many small tensors)."""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import flatten as _flatten
from apex_tpu.multi_tensor_apply import kernels as _kernels
from apex_tpu.optimizers._common import (
    finish_compute_params, flat_layout,
    f32, select_finite, tree_unzip, tree_zeros_f32,
)
from apex_tpu.utils.profiler import region


class AdagradState(NamedTuple):
    step: jax.Array
    sum: Any


class FusedAdagrad:
    def __init__(self, lr: float = 1e-2, eps: float = 1e-10,
                 weight_decay: float = 0.0, adagrad_w_mode: bool = False,
                 *, use_flat_kernel: bool = False,
                 emit_compute_params: bool = False):
        self.lr = lr
        self.eps = eps
        self.weight_decay = weight_decay
        self.adagrad_w_mode = adagrad_w_mode
        self.use_flat_kernel = use_flat_kernel
        # Adagrad's only state is the second-moment sum — it has no first
        # moment, so there is no m_dtype knob (``sum`` must stay fp32);
        # the fused cast-out is supported like the other optimizers.
        self.emit_compute_params = emit_compute_params
        self._specs = {}

    def init(self, params: Any) -> AdagradState:
        step = jnp.zeros((), jnp.int32)
        if self.use_flat_kernel:
            leaves, _, spec, _ = flat_layout(self._specs, params)
            buf, _ = _flatten.flatten_tensors(leaves, spec)
            return AdagradState(step=step, sum=jnp.zeros_like(buf))
        return AdagradState(step=step, sum=tree_zeros_f32(params))

    @region("optimizer")
    def step(self, grads: Any, params: Any, state: AdagradState, *,
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
        eps = f32(self.eps)
        wd = f32(self.weight_decay if weight_decay is None else weight_decay)

        if self.use_flat_kernel:
            leaves, treedef, spec, _ = flat_layout(self._specs, params)
            gbuf, _ = _flatten.flatten_tensors(
                jax.tree_util.tree_leaves(grads), spec)
            pbuf, _ = _flatten.flatten_tensors(leaves, spec)
            emit_dt = jnp.bfloat16 if self.emit_compute_params else None
            outs = _kernels.flat_adagrad(
                gbuf, pbuf, state.sum, lr=lr, eps=self.eps,
                weight_decay=wd, adagrad_w_mode=self.adagrad_w_mode,
                grad_scale=gs, emit_compute_dtype=emit_dt)
            p_new, s_new = outs[:2]
            new_params = jax.tree_util.tree_unflatten(
                treedef, _flatten.unflatten_tensors(p_new, spec))
            new_state = AdagradState(step=state.step + 1, sum=s_new)
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

        def upd(g, p, s):
            g = g.astype(jnp.float32) * gs
            p32 = p.astype(jnp.float32)
            if not self.adagrad_w_mode:
                g = g + wd * p32
            s = s + g * g
            u = g / (jnp.sqrt(s) + eps)
            if self.adagrad_w_mode:
                u = u + wd * p32
            return (p32 - lr * u).astype(p.dtype), s

        out = jax.tree.map(upd, grads, params, state.sum)
        new_params, new_sum = tree_unzip(out, 2)
        new_state = AdagradState(step=state.step + 1, sum=new_sum)

        new_params = select_finite(found_inf, new_params, params)
        new_state = select_finite(found_inf, new_state, state)
        if not self.emit_compute_params:
            return new_params, new_state
        compute = finish_compute_params(new_params, params, compute_params,
                                        found_inf)
        return new_params, new_state, compute
