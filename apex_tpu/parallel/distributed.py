"""Data-parallel gradient synchronization.

Reference: ``apex/parallel/distributed.py :: class DistributedDataParallel``
— per-param backward hooks, bucketing with first-iteration structure
discovery, flatten via ``apex_C``, async NCCL allreduce on a side stream,
``delay_allreduce``, ``allreduce_always_fp32``, ``gradient_average``.

On TPU the entire hook/bucket/stream machinery collapses: gradient
"allreduce" is a ``lax.psum`` over the mesh ``data`` axis inside the jitted
step, and overlap with backward compute is XLA's latency-hiding scheduler's
job. What survives of the reference API is the numerics policy:

- ``allreduce_always_fp32`` — upcast grads to fp32 for the reduction;
- ``gradient_average`` — divide by the data-parallel world size;
- ``delay_allreduce`` — moot (there is one fused reduction anyway), kept
  as an accepted no-op for signature parity.

Use it inside ``shard_map`` over the data axis — the one multi-chip route
of this package (a plain ``jit`` over batch-sharded inputs cannot carry the
fused kernels: XLA does not partition a Mosaic kernel)::

    ddp = DistributedDataParallel()
    replica = ddp.local_replica(params)  # per-rank replica (torch-style)
    grads = jax.grad(loss)(replica, shard_of_batch)
    grads = ddp.allreduce_grads(grads)   # psum over "data"

With amp, hand the reduction to the scaler so it runs on the scaled grads
before the overflow check, as the reference's backward hooks do, and
every replica takes the same skip decision::

    loss, grads, found_inf, scaler = h.value_and_grad(
        loss_fn, reduce_grads=ddp.allreduce_grads)(replica, scaler)

``local_replica`` matters under shard_map's varying-axes semantics:
differentiating w.r.t. a REPLICATED (unvarying) input makes JAX insert
the cross-axis psum itself (the transpose of the implicit broadcast),
so grads arrive pre-summed and another allreduce would double-count.
``pcast(..., to='varying')`` gives each rank its own replica — exactly
the torch DDP model — leaving the reduction to this wrapper.
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer import parallel_state as ps
from apex_tpu.utils.profiler import region


class DistributedDataParallel:
    def __init__(self, module=None, *, message_size: int = 10_000_000,
                 delay_allreduce: bool = False,
                 allreduce_always_fp32: bool = False,
                 gradient_average: bool = True,
                 axis_name: Optional[str] = None):
        # ``module`` / ``message_size`` / ``delay_allreduce`` accepted for
        # reference-signature parity; bucketing has no TPU equivalent.
        self.module = module
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.axis_name = axis_name or ps.DATA_AXIS

    def local_replica(self, params: Any) -> Any:
        """Per-rank replica of replicated params (call inside shard_map
        before taking grads) — the torch "module replica" of the
        reference; see the module docstring for why this is load-bearing."""
        return jax.tree.map(
            lambda p: lax.pcast(p, self.axis_name, to="varying"), params)

    @region("grad_sync")
    def allreduce_grads(self, grads: Any) -> Any:
        """psum grads over the data axis (call inside shard_map/pmap).

        Matches the reference reduction numerics: optional fp32 upcast,
        then sum, then average by world size."""
        axis = self.axis_name

        def reduce_leaf(g):
            orig = g.dtype
            if self.allreduce_always_fp32:
                g = g.astype(jnp.float32)
            g = lax.psum(g, axis)
            if self.gradient_average:
                g = g / lax.psum(1, axis)
            return g.astype(orig)

        return jax.tree.map(reduce_leaf, grads)

    def broadcast_params(self, params: Any) -> Any:
        """Make every data-parallel rank hold rank 0's params (the
        reference ctor's ``flat_dist_call(..., broadcast)``); call inside
        shard_map."""
        axis = self.axis_name
        rank = lax.axis_index(axis)

        def bcast(p):
            # Masked psum: every rank but 0 contributes exact zeros, so
            # the sum reproduces rank 0's value EXACTLY in the leaf's own
            # dtype — no fp32 round-trip (which would truncate f64 and
            # corrupt wide-int leaves). Bool/int leaves ride through int32
            # (XLA collectives need an arithmetic type for bool).
            if p.dtype == jnp.bool_:
                masked = jnp.where(rank == 0, p.astype(jnp.int32),
                                   jnp.zeros(p.shape, jnp.int32))
                return lax.psum(masked, axis).astype(jnp.bool_)
            masked = jnp.where(rank == 0, p, jnp.zeros_like(p))
            return lax.psum(masked, axis)

        return jax.tree.map(bcast, params)
