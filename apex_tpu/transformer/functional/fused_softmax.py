"""Fused scale + mask + softmax — Pallas kernels + dispatcher.

Reference: ``apex/transformer/functional/fused_softmax.py ::
FusedScaleMaskSoftmax`` over the CUDA kernels
``csrc/megatron/scaled_masked_softmax_cuda.cu`` (additive/boolean padding
mask) and ``scaled_upper_triang_masked_softmax_cuda.cu`` (implicit causal
mask). The CUDA kernels are seqlen-templated (<= 2k/4k); the Pallas
kernels are seqlen-generic: the grid walks (batch*heads, q-tiles) with the
full key dim resident per tile, fp32 softmax arithmetic, and a fused
backward ``dx = scale * (dy - sum(dy*y)) * y``.

Masking follows the reference convention: ``mask == True`` (or 1) means
MASKED OUT, implemented additively with -10000 like the CUDA kernel.
"""

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.utils.math import cdiv, round_up_to_multiple
from apex_tpu.utils.pallas import dimsem as _dimsem
from apex_tpu.utils.platform import pallas_interpret

_MASK_VALUE = -10000.0  # the reference kernels' masked-score constant
_TILE_Q = 128


def _pad_q(x, tile):
    q = x.shape[1]
    pq = round_up_to_multiple(q, tile)
    if pq != q:
        x = jnp.pad(x, ((0, 0), (0, pq - q), (0, 0)))
    return x


# -- forward kernels --------------------------------------------------------

def _softmax_rows(z):
    m = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _masked_fwd_kernel(sc_ref, x_ref, m_ref, y_ref):
    z = x_ref[:].astype(jnp.float32) * sc_ref[0, 0]
    z = jnp.where(m_ref[:] != 0, _MASK_VALUE, z)
    y_ref[:] = _softmax_rows(z).astype(y_ref.dtype)


def _causal_fwd_kernel(sc_ref, x_ref, y_ref):
    _, tq, sk = x_ref.shape
    qt = pl.program_id(1)
    z = x_ref[:].astype(jnp.float32) * sc_ref[0, 0]
    qpos = qt * tq + jax.lax.broadcasted_iota(jnp.int32, (1, tq, sk), 1)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, tq, sk), 2)
    z = jnp.where(kpos > qpos, _MASK_VALUE, z)
    y_ref[:] = _softmax_rows(z).astype(y_ref.dtype)


def _bwd_kernel(sc_ref, y_ref, dy_ref, dx_ref):
    y = y_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    s = jnp.sum(y * dy, axis=-1, keepdims=True)
    dx_ref[:] = (sc_ref[0, 0] * (dy - s) * y).astype(dx_ref.dtype)


def _row_specs(tile, sk):
    return pl.BlockSpec((1, tile, sk), lambda i, j: (i, j, 0),
                        memory_space=pltpu.VMEM)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _bwd_call(y3, dy3, scale, interpret):
    batches, q, sk = y3.shape
    tile = min(_TILE_Q, round_up_to_multiple(q, 8))
    yp, dyp = _pad_q(y3, tile), _pad_q(dy3, tile)
    grid = (batches, yp.shape[1] // tile)
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    with jax.named_scope("apex_softmax_bwd"):
        dx = pl.pallas_call(
            _bwd_kernel,
            grid=grid,
            in_specs=[_smem(), _row_specs(tile, sk), _row_specs(tile, sk)],
            out_specs=_row_specs(tile, sk),
            out_shape=jax.ShapeDtypeStruct(yp.shape, y3.dtype),
            compiler_params=_dimsem("parallel", "parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_softmax_bwd",
        )(sc, yp, dyp)
    return dx[:, :q]


# -- scaled masked softmax (padding mask) -----------------------------------

def _sms_fwd(x, mask, scale, interpret):
    b, np_, sq, sk = x.shape
    # the mask stays (b, sq, sk) in HBM — identical across heads, so the
    # grid indexes it by i // np_ instead of replicating it per head (the
    # CUDA kernel does the same via its batch stride)
    m3 = jnp.broadcast_to(mask.astype(jnp.int32), (b, 1, sq, sk))[:, 0]
    x3 = x.reshape(b * np_, sq, sk)
    tile = min(_TILE_Q, round_up_to_multiple(sq, 8))
    xp, mp = _pad_q(x3, tile), _pad_q(m3, tile)
    grid = (b * np_, xp.shape[1] // tile)
    mask_spec = pl.BlockSpec((1, tile, sk), lambda i, j: (i // np_, j, 0),
                             memory_space=pltpu.VMEM)
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    with jax.named_scope("apex_softmax_masked_fwd"):
        y = pl.pallas_call(
            _masked_fwd_kernel,
            grid=grid,
            in_specs=[_smem(), _row_specs(tile, sk), mask_spec],
            out_specs=_row_specs(tile, sk),
            out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
            compiler_params=_dimsem("parallel", "parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_softmax_masked_fwd",
        )(sc, xp, mp)
    return y[:, :sq].reshape(b, np_, sq, sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scaled_masked_softmax_core(scale, interpret, x, mask):
    return _sms_fwd(x, mask, scale, interpret)


def _sms_fwd_vjp(scale, interpret, x, mask):
    y = _sms_fwd(x, mask, scale, interpret)
    return y, y


def _sms_bwd_vjp(scale, interpret, y, dy):
    b, np_, sq, sk = y.shape
    dx = _bwd_call(y.reshape(b * np_, sq, sk), dy.reshape(b * np_, sq, sk),
                   scale, interpret)
    return dx.reshape(b, np_, sq, sk), None


_scaled_masked_softmax_core.defvjp(_sms_fwd_vjp, _sms_bwd_vjp)


def scaled_masked_softmax(x, mask, scale=1.0,
                          interpret: Optional[bool] = None):
    """x: (b, np, sq, sk); mask: (b, 1, sq, sk) or broadcastable, nonzero =
    masked out (ref convention). Returns probabilities in x.dtype."""
    return _scaled_masked_softmax_core(float(scale), interpret, x, mask)


# -- scaled upper-triangular (causal) softmax -------------------------------

def _sut_fwd(x3, scale, interpret):
    batches, sq, sk = x3.shape
    tile = min(_TILE_Q, round_up_to_multiple(sq, 8))
    xp = _pad_q(x3, tile)
    grid = (batches, xp.shape[1] // tile)
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    with jax.named_scope("apex_softmax_causal_fwd"):
        y = pl.pallas_call(
            _causal_fwd_kernel,
            grid=grid,
            in_specs=[_smem(), _row_specs(tile, sk)],
            out_specs=_row_specs(tile, sk),
            out_shape=jax.ShapeDtypeStruct(xp.shape, x3.dtype),
            compiler_params=_dimsem("parallel", "parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_softmax_causal_fwd",
        )(sc, xp)
    return y[:, :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scaled_upper_triang_core(scale, interpret, x3):
    return _sut_fwd(x3, scale, interpret)

def _sut_fwd_vjp(scale, interpret, x3):
    y = _sut_fwd(x3, scale, interpret)
    return y, y

def _sut_bwd_vjp(scale, interpret, y, dy):
    return (_bwd_call(y, dy, scale, interpret),)

_scaled_upper_triang_core.defvjp(_sut_fwd_vjp, _sut_bwd_vjp)


def scaled_upper_triang_masked_softmax(x, scale=1.0,
                                       interpret: Optional[bool] = None):
    """Causal softmax. x: (attn_batches, sq, sk) like the CUDA kernel, or
    (b, np, sq, sk) which is flattened."""
    if x.ndim == 4:
        b, np_, sq, sk = x.shape
        return _scaled_upper_triang_core(
            float(scale), interpret, x.reshape(b * np_, sq, sk)
        ).reshape(x.shape)
    return _scaled_upper_triang_core(float(scale), interpret, x)


# -- dispatcher (ref: class FusedScaleMaskSoftmax) --------------------------

class FusedScaleMaskSoftmax:
    """Picks the fused kernel when eligible, else the jnp fallback —
    mirroring the reference's ``is_kernel_available`` dispatch (dtype +
    fusion flag; the CUDA seqlen limits don't apply to Pallas)."""

    def __init__(self, input_in_fp16: bool = False,
                 input_in_bf16: bool = False,
                 attn_mask_type: AttnMaskType = AttnMaskType.padding,
                 scaled_masked_softmax_fusion: bool = True,
                 mask_func: Optional[Callable] = None,
                 softmax_in_fp32: bool = True,
                 scale: Optional[float] = None):
        if input_in_fp16 and input_in_bf16:
            raise RuntimeError("both fp16 and bf16 flags are set")
        self.input_in_float16 = input_in_fp16 or input_in_bf16
        self.attn_mask_type = attn_mask_type
        self.fusion = scaled_masked_softmax_fusion
        self.mask_func = mask_func
        self.softmax_in_fp32 = softmax_in_fp32
        self.scale = scale
        if scale is not None and not softmax_in_fp32:
            raise RuntimeError("softmax should be in fp32 when scaled")

    def is_kernel_available(self, mask, b, np_, sq, sk) -> bool:
        """Mirrors the reference's gate with the gates that still apply.

        Kept from the reference (``fused_softmax.py ::
        is_kernel_available``): the user fusion flag and the
        input-in-float16 requirement — the fused path is specified for
        half-precision inputs (fp32 callers get the fp32-softmax fallback
        with identical numerics, as upstream).  Dropped, with reason: the
        CUDA tiling limits (16 < sk <= 16384, sq/sk % 4, attn_batches %
        batch_per_block) exist because the CUDA kernels are compiled for
        fixed tile geometries; the Pallas kernels pad to (8,128) lanes and
        take seqlen as a grid parameter, so every shape is eligible.
        Added: ``sq > 1`` — a single-query (decode) softmax is one VPU row
        where kernel dispatch is pure overhead.
        """
        return bool(self.fusion) and self.input_in_float16 and sq > 1

    def __call__(self, x, mask=None):
        scale = self.scale if self.scale is not None else 1.0
        b, np_, sq, sk = x.shape
        if self.is_kernel_available(mask, b, np_, sq, sk):
            if self.attn_mask_type == AttnMaskType.causal:
                return scaled_upper_triang_masked_softmax(x, scale)
            if mask is not None:
                return scaled_masked_softmax(x, mask, scale)
            # no mask: scale-only softmax = masked kernel with a zero mask
            zero = jnp.zeros((b, 1, sq, sk), jnp.int32)
            return scaled_masked_softmax(x, zero, scale)
        return self.forward_torch_softmax(x, mask)

    forward_fused_softmax = __call__

    def forward_torch_softmax(self, x, mask=None):
        """jnp fallback (the reference's ``forward_torch_softmax``)."""
        z = x.astype(jnp.float32) if self.softmax_in_fp32 else x
        if self.scale is not None:
            z = z * self.scale
        if self.attn_mask_type == AttnMaskType.causal:
            sq, sk = z.shape[-2:]
            causal = jnp.tril(jnp.ones((sq, sk), bool))
            z = jnp.where(causal, z, _MASK_VALUE)
        elif mask is not None:
            f = self.mask_func or (lambda z, m: jnp.where(m != 0,
                                                          _MASK_VALUE, z))
            z = f(z, mask)
        y = jax.nn.softmax(z, axis=-1)
        return y.astype(x.dtype) if self.softmax_in_fp32 else y
