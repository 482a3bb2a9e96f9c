"""Group-synchronized NHWC BatchNorm with fused add+ReLU epilogue.

Reference: ``apex/contrib/groupbn/batch_norm.py :: BatchNorm2d_NHWC``
(CUDA in ``csrc/groupbn/*``) — the MLPerf ResNet block: NHWC batch norm
whose statistics sync across a GROUP of ``bn_group`` GPUs (not the whole
world), with the residual add and ReLU fused into the normalization
kernel's epilogue.

TPU mapping: group-limited stat sync is ``lax.pmean`` with
``axis_index_groups`` partitioning the data axis into consecutive groups
of ``bn_group`` ranks — XLA emits the reduced-scope allreduce over ICI
exactly as the CUDA kernels run NCCL on a sub-communicator. The
add+ReLU epilogue is ordinary code XLA fuses into the normalization's
elementwise chain (the "let XLA fuse" rule); stats are fp32.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models import layers as L
from apex_tpu.transformer import parallel_state as ps


class BatchNorm2d_NHWC:
    """``init() -> (params, running_state)``; ``apply(params, state, x,
    z=None, train=...) -> (y, new_state)``. ``bn_group=0`` syncs across
    the WHOLE axis; ``bn_group=1`` is rank-local (the reference
    default); ``k > 1`` syncs consecutive groups of k ranks.

    The stat machinery is ``layers.batchnorm`` (the one SyncBN uses)
    with an ``axis_index_groups`` restriction — one implementation, one
    momentum convention (this class exposes torch's UPDATE fraction,
    default 0.1, and hands the keep fraction down)."""

    def __init__(self, num_features: int, *, fuse_relu: bool = False,
                 bn_group: int = 1, momentum: float = 0.1,
                 eps: float = 1e-5,
                 axis_name: Optional[str] = None):
        self.num_features = num_features
        self.fuse_relu = fuse_relu
        self.bn_group = bn_group
        self.momentum = momentum
        self.eps = eps
        self.axis_name = axis_name if axis_name is not None else \
            ps.DATA_AXIS

    def init(self) -> Tuple[Dict, Dict]:
        return L.init_batchnorm(self.num_features)

    def _groups(self):
        n = lax.axis_size(self.axis_name)
        k = n if self.bn_group == 0 else self.bn_group
        if n % k:
            raise ValueError(
                f"bn_group {k} does not divide axis size {n}")
        return [list(range(g * k, (g + 1) * k)) for g in range(n // k)]

    def apply(self, params: Dict, state: Dict, x: jax.Array,
              z: Optional[jax.Array] = None, *, train: bool = True
              ) -> Tuple[jax.Array, Dict]:
        sync = self.bn_group != 1  # bn_group=1: rank-local, no collective
        y, new_state = L.batchnorm(
            params, state, x, train=train,
            momentum=1.0 - self.momentum, eps=self.eps,
            axis_name=self.axis_name if (sync and train) else None,
            axis_index_groups=self._groups() if (sync and train) else None)
        if z is not None or self.fuse_relu:
            # the fused add+ReLU epilogue (reference: bn_add_relu kernel);
            # XLA fuses this into the normalization's elementwise chain
            y32 = y.astype(jnp.float32)
            if z is not None:
                y32 = y32 + z.astype(jnp.float32)
            if self.fuse_relu:
                y32 = jax.nn.relu(y32)
            y = y32.astype(x.dtype)
        return y, new_state

    __call__ = apply
