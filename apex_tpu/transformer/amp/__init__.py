"""Grad scaler for TP/PP training (ref: ``apex/transformer/amp/grad_scaler.py``
— a Megatron-style GradScaler whose found_inf is allreduced across the
model-parallel group). The core ``LossScaler`` is shared with ``apex_tpu.amp``;
this wrapper adds the cross-rank OR of found_inf."""

from typing import Any, Tuple

import jax.numpy as jnp
from jax import lax

from apex_tpu.amp.scaler import LossScaler, LossScalerState  # noqa: F401
from apex_tpu.transformer import parallel_state as ps


def _axis_is_bound(name: str) -> bool:
    """True iff ``name`` is a mapped axis in the current trace context.

    Probes with ``lax.axis_size``: pure trace-time metadata — it adds
    nothing to the jaxpr and touches no internals. The unbound case is
    a trace-time ``NameError``, so no runtime branch is compiled.
    """
    try:
        lax.axis_size(name)
        return True
    except NameError:
        # the unbound-axis trace error; anything else must propagate —
        # failing open here would silently skip the cross-rank found_inf
        # OR and let optimizer states diverge across TP ranks
        return False


class GradScaler(LossScaler):
    """``unscale`` additionally ORs found_inf over the TP (and pipe) axes —
    a rank that overflowed must make EVERY rank skip the step (the
    reference allreduces found_inf over the model-parallel group). Call
    inside shard_map. Axes not bound by the enclosing mapped region (a
    tp-only or pp-only shard_map) are skipped rather than erroring."""

    def unscale(self, grads: Any, state: LossScalerState
                ) -> Tuple[Any, jnp.ndarray]:
        grads, found_inf = super().unscale(grads, state)
        for axis in (ps.TENSOR_AXIS, ps.PIPE_AXIS):
            if _axis_is_bound(axis):
                found_inf = lax.pmax(found_inf.astype(jnp.int32), axis) > 0
        return grads, found_inf
