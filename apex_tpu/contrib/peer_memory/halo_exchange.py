"""1-D halo exchange for spatially-sharded tensors.

Reference: ``apex/contrib/peer_memory/peer_halo_exchanger_1d.py`` (+
``peer_memory_cuda``) — spatial parallelism for convolutions: an image's
H dim is sharded across GPUs, and each conv needs ``halo`` rows from its
neighbors, moved over direct peer-to-peer CUDA mappings.

TPU version: neighbor exchange IS ``lax.ppermute`` over the mesh axis —
XLA lowers it to direct ICI sends between logical neighbors, the same
physical pattern peer_memory_cuda hand-builds over NVLink. Two permutes
(up, down) move both halos; autodiff transposes each rotation to its
reverse, so the backward "halo accumulation" of the reference falls out
for free. Non-periodic edges zero-fill (the reference's default conv
padding behavior at the outer boundary).
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer import parallel_state as ps


def halo_exchange_1d(x: jax.Array, halo: int, *, axis: int = 1,
                     axis_name: str = ps.CONTEXT_AXIS,
                     periodic: bool = False) -> jax.Array:
    """Concatenate neighbors' boundary slices onto this rank's shard.

    Args:
      x: the local shard; the sharded spatial dim is ``axis``.
      halo: rows to fetch from EACH neighbor.
      periodic: wrap around the ring instead of zero-filling the edges.

    Returns x extended to ``2*halo + x.shape[axis]`` along ``axis``:
    ``[prev-rank's last halo | x | next-rank's first halo]``.
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    if halo <= 0:
        raise ValueError(f"halo must be positive, got {halo}")
    if halo > x.shape[axis]:
        raise ValueError(
            f"halo {halo} exceeds local extent {x.shape[axis]}")

    down = [(i, (i + 1) % n) for i in range(n)]   # send toward rank+1
    up = [(i, (i - 1) % n) for i in range(n)]     # send toward rank-1

    bottom = lax.slice_in_dim(x, x.shape[axis] - halo, x.shape[axis],
                              axis=axis)
    top = lax.slice_in_dim(x, 0, halo, axis=axis)
    from_prev = lax.ppermute(bottom, axis_name, down)  # prev's bottom
    from_next = lax.ppermute(top, axis_name, up)       # next's top
    if not periodic:
        # first rank has no prev, last has no next: zero-fill
        from_prev = jnp.where(rank == 0, jnp.zeros_like(from_prev),
                              from_prev)
        from_next = jnp.where(rank == n - 1, jnp.zeros_like(from_next),
                              from_next)
    return jnp.concatenate([from_prev, x, from_next], axis=axis)


class PeerHaloExchanger1d:
    """Module-shaped wrapper keeping the reference's constructor shape
    (``peer_ranks`` becomes the mesh axis; ``peer_pool`` has no TPU
    analogue — ICI buffers are XLA-managed)."""

    def __init__(self, axis_name: str = ps.CONTEXT_AXIS,
                 halo: int = 1, *, axis: int = 1,
                 periodic: bool = False):
        self.axis_name = axis_name
        self.halo = halo
        self.axis = axis
        self.periodic = periodic

    def __call__(self, x: jax.Array,
                 halo: Optional[int] = None) -> jax.Array:
        return halo_exchange_1d(
            x, halo if halo is not None else self.halo, axis=self.axis,
            axis_name=self.axis_name, periodic=self.periodic)
