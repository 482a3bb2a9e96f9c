"""AMP frontend: ``initialize`` and the training-step helpers.

Reference: ``apex/amp/frontend.py :: def initialize`` builds a
``Properties`` from the O0..O3 presets plus user overrides, then
``_initialize`` rewires model+optimizer in place. Functional translation:

    amp_h = amp.initialize(opt_level="O2", loss_scale="dynamic")
    master  = amp_h.master_params(params)        # fp32 source of truth
    state   = amp_h.init_state()                 # scaler state (pytree)

    def train_step(master, opt_state, state, batch):
        params = amp_h.cast_model(master)        # O2: bf16 except norms
        (loss, aux), grads, found_inf, state = amp_h.value_and_grad(
            loss_fn, has_aux=True)(params, state, amp_h.cast_input(batch))
        updates, new_opt = optimizer.update(grads, opt_state, master)
        new_master = optax.apply_updates(master, updates)
        master   = amp.apply_if_finite(new_master, master, found_inf)
        opt_state = amp.apply_if_finite(new_opt, opt_state, found_inf)
        return master, opt_state, state, loss

The ``with amp.scale_loss(loss, optimizer) as scaled_loss`` context manager
of the reference has no backward() to wrap in JAX; its three jobs (scale,
unscale-after-backward, update-scale) are the explicit ``scale_loss`` /
``unscale`` / ``update_scale`` methods, or the fused ``value_and_grad``.
"""

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.amp import policy as _policy
from apex_tpu.amp.autocast import autocast
from apex_tpu.amp.properties import Properties, opt_levels
from apex_tpu.amp.scaler import (
    LossScaler,
    LossScalerState,
    apply_if_finite,  # noqa: F401  (re-exported)
)
from apex_tpu.utils.profiler import region


class Amp:
    """Bundle of an opt-level's Properties + a LossScaler + cast helpers.

    ``num_losses`` mirrors the reference's ``amp.initialize(...,
    num_losses=N)``: ``init_state`` then returns a TUPLE of independent
    scaler states, and the reference's ``loss_id`` argument becomes
    plain indexing (``h.scale_loss(loss, state[i])``)."""

    def __init__(self, properties: Properties, num_losses: int = 1):
        self.properties = properties
        self.num_losses = int(num_losses)
        self.scaler = LossScaler(loss_scale=properties.loss_scale)

    # -- model / input casting -----------------------------------------
    @region("amp")
    def cast_model(self, params: Any, precast: Any = None) -> Any:
        """O2/O3 model cast. ``precast`` is an optimizer-emitted compute
        tree (``FusedAdam(emit_compute_params=True)`` etc.): matching-
        dtype leaves are consumed verbatim so the per-step fp32→bf16
        re-cast over the master tree disappears; only leaves the policy
        keeps fp32 (norms under ``keep_batchnorm_fp32``) still come from
        ``params``."""
        p = self.properties
        if p.cast_model_type is None:
            return params
        return _policy.cast_params(
            params,
            p.cast_model_type,
            keep_batchnorm_fp32=bool(p.keep_batchnorm_fp32),
            precast=precast,
        )

    @region("amp")
    def cast_input(self, batch: Any) -> Any:
        p = self.properties
        if p.cast_model_type is None:
            return batch
        # O0 included: the reference casts floating inputs to fp32 there too.
        return _policy.cast_inputs(batch, p.cast_model_type)

    def master_params(self, params: Any) -> Any:
        if not self.properties.master_weights:
            return params
        return _policy.master_params(params)

    def autocast(self):
        """O1 context: op-policy casting for apex_tpu ops in scope."""
        p = self.properties
        dtype = p.cast_model_type or jnp.bfloat16
        return autocast(dtype=dtype, enabled=bool(p.patch_torch_functions))

    # -- scaler ---------------------------------------------------------
    def init_state(self):
        if self.num_losses == 1:
            return self.scaler.init_state()
        return tuple(self.scaler.init_state()
                     for _ in range(self.num_losses))

    @region("amp")
    def scale_loss(self, loss, state: LossScalerState):
        return self.scaler.scale(loss, state)

    @region("amp")
    def unscale(self, grads, state: LossScalerState):
        return self.scaler.unscale(grads, state)

    @region("amp")
    def update_scale(self, state: LossScalerState, found_inf):
        return self.scaler.update_scale(state, found_inf)

    def value_and_grad(
        self, loss_fn: Callable, has_aux: bool = False,
        reduce_grads: Optional[Callable] = None, **grad_kwargs
    ) -> Callable:
        """Scaled value_and_grad: computes grads of the *scaled* loss,
        unscales them, and advances the scaler state.

        ``reduce_grads`` (e.g. ``DistributedDataParallel.allreduce_grads``
        inside ``shard_map``) runs on the still-SCALED grads, before the
        overflow check — the reference's order, where the allreduce fires
        during backward. Every replica then unscales the same grads, so
        ``found_inf`` and the scaler state cannot differ between them.

        Returned callable: ``(params, state, *args, **kw) ->
        (value, grads, found_inf, new_state)`` where ``value`` is the
        unscaled ``loss`` (or ``(loss, aux)`` with has_aux)."""

        def wrapped(params, state: LossScalerState, *args, **kw):
            def scaled_loss_fn(p, *a, **k):
                out = loss_fn(p, *a, **k)
                if has_aux:
                    loss, aux = out
                else:
                    loss, aux = out, None
                return self.scale_loss(loss, state), (loss, aux)

            (_, (loss, aux)), grads = jax.value_and_grad(
                scaled_loss_fn, has_aux=True, **grad_kwargs
            )(params, *args, **kw)
            if reduce_grads is not None:
                grads = reduce_grads(grads)
            grads, found_inf = self.unscale(grads, state)
            new_state = self.update_scale(state, found_inf)
            value = (loss, aux) if has_aux else loss
            return value, grads, found_inf, new_state

        return wrapped

    # -- checkpointing (ref: ``amp.state_dict``) ------------------------
    def state_dict(self, state) -> dict:
        """N-scaler form of the reference's ``amp.state_dict``: one
        ``loss_scalerI`` entry per state (a single state is scaler 0)."""
        states = state if isinstance(state, (list, tuple)) else (state,)
        return {f"loss_scaler{i}": self.scaler.state_dict(s)
                for i, s in enumerate(states)}

    def load_state_dict(self, d: dict):
        """Inverse of :meth:`state_dict`. A loss_scaler COUNT mismatch
        warns and loads the overlap (reference behavior: apex's
        ``load_state_dict`` iterates ``zip(self._loss_scalers, ...)`` —
        silently truncating; we keep the load-what-matches semantics but
        say so out loud): extra checkpoint entries are dropped, missing
        ones fall back to a fresh ``init_state()``. Raising here would
        brick every resume-with-changed-loss-count run for a state that
        is, at worst, a scale-warmup hiccup."""
        keys = sorted((k for k in d if k.startswith("loss_scaler")
                       and k[len("loss_scaler"):].isdigit()),
                      key=lambda k: int(k[len("loss_scaler"):]))
        if len(keys) != self.num_losses:
            import warnings
            warnings.warn(
                f"amp state_dict has {len(keys)} loss_scaler entries but "
                f"this handle was initialized with num_losses="
                f"{self.num_losses}; loading the overlap — surplus "
                "checkpoint entries are ignored, missing scalers start "
                "from a fresh init_state()", stacklevel=2)
        states = tuple(
            self.scaler.load_state_dict(d[keys[i]]) if i < len(keys)
            else self.scaler.init_state()
            for i in range(self.num_losses))
        return states[0] if self.num_losses == 1 else states


def initialize(
    opt_level: str = "O1",
    *,
    cast_model_type=None,
    keep_batchnorm_fp32: Optional[bool] = None,
    master_weights: Optional[bool] = None,
    loss_scale=None,
    enabled: bool = True,
    verbosity: int = 1,
    num_losses: int = 1,
) -> Amp:
    """Build an :class:`Amp` handle from an opt-level + overrides.

    Mirrors ``apex.amp.initialize``'s knobs; model/optimizer are not
    arguments because nothing is mutated — apply ``amp_h.cast_model`` /
    ``amp_h.master_params`` to your param tree instead.
    """
    if opt_level not in opt_levels:
        raise ValueError(
            f"Unexpected optimization level {opt_level!r} "
            "(options are 'O0', 'O1', 'O2', 'O3')."
        )
    props = opt_levels[opt_level](Properties())
    if enabled:
        overrides = {
            "cast_model_type": cast_model_type,
            "keep_batchnorm_fp32": keep_batchnorm_fp32,
            "master_weights": master_weights,
            "loss_scale": loss_scale,
        }
        props._update_options_dict(
            {k: v for k, v in overrides.items() if v is not None}
        )
    else:
        # Hard off-switch (reference parity): all other knobs are ignored.
        props.enabled = False
        props.patch_torch_functions = False
        props.cast_model_type = None
        props.master_weights = False
        props.loss_scale = 1.0
    if verbosity > 0:
        import logging

        logging.getLogger("apex_tpu").info(
            "amp.initialize: opt_level=%s properties=%s", opt_level, props
        )
    return Amp(props, num_losses=num_losses)
