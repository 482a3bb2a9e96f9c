"""Fused LayerNorm / RMSNorm — Pallas TPU kernels with custom VJPs.

TPU-native equivalent of the reference's ``fused_layer_norm_cuda`` extension
(ref: ``csrc/layer_norm_cuda.cpp`` + ``csrc/layer_norm_cuda_kernel.cu``,
consumed by ``apex/normalization/fused_layer_norm.py :: FusedLayerNormAffineFunction``
/ ``FusedRMSNormAffineFunction`` / ``class FusedLayerNorm`` / ``class FusedRMSNorm``).

Design (vs. the CUDA reference):

- The CUDA kernels do a per-row Welford mean/var with warp reductions; on TPU
  a row tile of shape ``(TILE_R, H)`` sits in VMEM and the VPU reduces the
  hidden dim directly in fp32 — no Welford needed because the whole row is
  resident.
- The CUDA backward does a two-stage dgamma/dbeta reduction across threadblocks;
  here partial ``(1, H)`` sums are accumulated across sequential grid steps
  into a single fp32 output block (TPU grids execute sequentially, so the
  revisited output block is the accumulator).
- "Mixed" (fp16/bf16 activations with fp32 params and fp32 statistics) is the
  only behavior: statistics and all accumulation are always fp32; outputs take
  the input dtype, weight grads take the weight dtype.

Forward saves ``(x, weight[, bias-not-needed], mean, rstd)`` — the same
residual set the reference saves with ``ctx.save_for_backward``.
"""

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.math import round_up_to_multiple
from apex_tpu.utils.pallas import dimsem as _dimsem
from apex_tpu.utils.platform import pallas_interpret

Shape = Union[int, Sequence[int]]

_LANE = 128
_SUBLANE = 8

# VMEM working-set budget for choosing the row tile. A tile touches ~6 fp32
# row-blocks (x, y, dy, dx, xhat temp, wdy temp) at H columns each.
_VMEM_BUDGET = 8 * 1024 * 1024


def _normalized_size(normalized_shape: Shape) -> int:
    if isinstance(normalized_shape, int):
        return normalized_shape
    return int(np.prod(tuple(normalized_shape)))


def _row_tile(n_rows: int, h: int, n_bufs: int = 6) -> int:
    """Pick a row-tile size: multiple of the fp32 sublane count, bounded by
    the VMEM budget and the (padded) row count."""
    by_vmem = _VMEM_BUDGET // max(1, n_bufs * h * 4)
    tile = max(_SUBLANE, min(512, (by_vmem // _SUBLANE) * _SUBLANE))
    padded_rows = round_up_to_multiple(n_rows, _SUBLANE)
    return min(tile, max(_SUBLANE, padded_rows))


def _pad_rows(x2d: jax.Array, tile: int) -> Tuple[jax.Array, int]:
    rows = x2d.shape[0]
    padded = round_up_to_multiple(rows, tile)
    if padded != rows:
        x2d = jnp.pad(x2d, ((0, padded - rows), (0, 0)))
    return x2d, padded


# ---------------------------------------------------------------------------
# Kernels. ``mode`` is "ln" or "rms"; affine params are optional positionals.
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, mode: str, eps: float, has_w: bool, has_b: bool):
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it) if has_w else None
    b_ref = next(it) if has_b else None
    y_ref = next(it)
    mean_ref = next(it) if mode == "ln" else None
    rstd_ref = next(it)

    x = x_ref[:].astype(jnp.float32)
    if mode == "ln":
        mean = jnp.mean(x, axis=1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = xc * rstd
        mean_ref[:] = mean
    else:
        ms = jnp.mean(x * x, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(ms + eps)
        xhat = x * rstd
    rstd_ref[:] = rstd

    y = xhat
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    if has_b:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _bwd_kernel(*refs, mode: str, has_w: bool, has_b: bool,
                accum_parts: bool = False):
    it = iter(refs)
    dy_ref = next(it)
    x_ref = next(it)
    w_ref = next(it) if has_w else None
    mean_ref = next(it) if mode == "ln" else None
    rstd_ref = next(it)
    dx_ref = next(it)
    dw_ref = next(it) if has_w else None
    db_ref = next(it) if has_b else None

    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    if mode == "ln":
        xhat = (x - mean_ref[:]) * rstd
    else:
        xhat = x * rstd

    wdy = dy * w_ref[:].astype(jnp.float32) if has_w else dy
    c1 = jnp.mean(xhat * wdy, axis=1, keepdims=True)
    if mode == "ln":
        c2 = jnp.mean(wdy, axis=1, keepdims=True)
        dx = (wdy - xhat * c1 - c2) * rstd
    else:
        dx = (wdy - xhat * c1) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # dgamma/dbeta — stage 2 of the CUDA kernel's two-stage threadblock
    # reduction, with a tile-size-dependent strategy (both measured on
    # v5e, 8192 rows):
    # - big tiles (h<=~2k): one (8, H) partial PER grid step (row 0 live,
    #   rows 1-7 zero for the sublane rule), summed by XLA outside —
    #   avoids the revisited output block that stalls the pipeline's
    #   output stage (h=1024: 90 -> 83 us/iter fwd+bwd);
    # - small tiles (big h): accumulate into one revisited (1, H) block —
    #   the per-step partial writes cost 8/tile of the stream bytes,
    #   a 10% regression at tile 80 (h=4096: 801 -> 841 us with partials).
    if accum_parts:
        if has_w:
            dw_ref[:] = jnp.concatenate(
                [jnp.sum(dy * xhat, axis=0, keepdims=True),
                 jnp.zeros((7, dy.shape[1]), jnp.float32)], axis=0)
        if has_b:
            db_ref[:] = jnp.concatenate(
                [jnp.sum(dy, axis=0, keepdims=True),
                 jnp.zeros((7, dy.shape[1]), jnp.float32)], axis=0)
        return
    step = pl.program_id(0)
    if has_w:
        @pl.when(step == 0)
        def _():
            dw_ref[:] = jnp.zeros_like(dw_ref)
        dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    if has_b:
        @pl.when(step == 0)
        def _():
            db_ref[:] = jnp.zeros_like(db_ref)
        db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


# -- column-split backward (large H) ----------------------------------------
#
# At big H the full-row tile is VMEM-starved (h=4096: 80 rows/step) and the
# measured bandwidth collapses (420 GB/s vs ~1040 at h=1024) — the single
# revisited (1, H) dgamma accumulator is the wrong structure, not the wrong
# tile size. Column-split restructuring: two passes over (TR, TC) blocks.
#
#   pass A (grid ri × ci, ci inner): accumulate the per-row sums that need
#     the whole row — c1s = Σ_h xhat·wdy and (LN) c2s = Σ_h wdy — into a
#     revisited (TR, 1) block, AND the per-column dgamma/dbeta partials
#     into a (1, H_p) accumulator that lives in VMEM for the whole grid
#     (16 KB at h=4096), written via a pl.ds column slice.
#   pass B (grid ci × ri, ri inner): dx = (wdy − xhat·c1 − c2)·rstd with
#     c1/c2 read back as (TR, 1) blocks — pure streaming, no reductions.
#
# Costs one extra read of (x, dy) vs the single-pass kernel, but every
# block is MXU/VPU-sized (512×512) regardless of H, which is the point.

_COL_TILE = 512
_ROW_TILE_CAP = 512  # colsplit row-block cap


def _bwd_colsum_kernel(*refs, mode, has_w, has_b):
    it = iter(refs)
    dy_ref = next(it)
    x_ref = next(it)
    w_ref = next(it) if has_w else None
    mean_ref = next(it) if mode == "ln" else None
    rstd_ref = next(it)
    c1_ref = next(it)
    c2_ref = next(it) if mode == "ln" else None
    dw_ref = next(it) if has_w else None
    db_ref = next(it) if has_b else None

    ri, ci = pl.program_id(0), pl.program_id(1)
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = (x - mean_ref[:]) * rstd if mode == "ln" else x * rstd
    wdy = dy * w_ref[:].astype(jnp.float32) if has_w else dy

    @pl.when(ci == 0)
    def _():
        c1_ref[:] = jnp.zeros_like(c1_ref)
        if mode == "ln":
            c2_ref[:] = jnp.zeros_like(c2_ref)
    c1_ref[:] += jnp.sum(xhat * wdy, axis=1, keepdims=True)
    if mode == "ln":
        c2_ref[:] += jnp.sum(wdy, axis=1, keepdims=True)

    first = jnp.logical_and(ri == 0, ci == 0)
    tc = dy.shape[1]
    if has_w:
        @pl.when(first)
        def _():
            dw_ref[:] = jnp.zeros_like(dw_ref)
        dw_ref[0:1, pl.ds(ci * tc, tc)] += jnp.sum(
            dy * xhat, axis=0, keepdims=True)
    if has_b:
        @pl.when(first)
        def _():
            db_ref[:] = jnp.zeros_like(db_ref)
        db_ref[0:1, pl.ds(ci * tc, tc)] += jnp.sum(
            dy, axis=0, keepdims=True)


def _bwd_dx_kernel(*refs, mode, has_w, inv_h):
    it = iter(refs)
    dy_ref = next(it)
    x_ref = next(it)
    w_ref = next(it) if has_w else None
    mean_ref = next(it) if mode == "ln" else None
    rstd_ref = next(it)
    c1_ref = next(it)
    c2_ref = next(it) if mode == "ln" else None
    dx_ref = next(it)

    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = (x - mean_ref[:]) * rstd if mode == "ln" else x * rstd
    wdy = dy * w_ref[:].astype(jnp.float32) if has_w else dy
    c1 = c1_ref[:] * inv_h
    if mode == "ln":
        dx = (wdy - xhat * c1 - c2_ref[:] * inv_h) * rstd
    else:
        dx = (wdy - xhat * c1) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _pad_cols(x2d, h_p):
    h = x2d.shape[1]
    if h_p != h:
        x2d = jnp.pad(x2d, ((0, 0), (0, h_p - h)))
    return x2d


def _bwd_call_colsplit(dy2d, x2d, w, mean, rstd, mode, has_b, interpret):
    rows, h = x2d.shape
    tc = _COL_TILE
    tr = min(_ROW_TILE_CAP, round_up_to_multiple(rows, _SUBLANE))
    has_w = w is not None
    h_p = round_up_to_multiple(h, tc)
    xp, padded = _pad_rows(_pad_cols(x2d, h_p), tr)
    dyp, _ = _pad_rows(_pad_cols(dy2d, h_p), tr)
    meanp = _pad_rows(mean, tr)[0] if mode == "ln" else None
    rstdp, _ = _pad_rows(rstd, tr)
    wp = _pad_cols(w.reshape(1, h), h_p) if has_w else None
    nri, nci = padded // tr, h_p // tc

    blk = pl.BlockSpec((tr, tc), lambda ri, ci: (ri, ci),
                       memory_space=pltpu.VMEM)
    wspec = pl.BlockSpec((1, tc), lambda ri, ci: (0, ci),
                         memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((tr, 1), lambda ri, ci: (ri, 0),
                        memory_space=pltpu.VMEM)
    grow = pl.BlockSpec((1, h_p), lambda ri, ci: (0, 0),
                        memory_space=pltpu.VMEM)

    in_specs = [blk, blk]
    args = [dyp, xp]
    if has_w:
        in_specs.append(wspec)
        args.append(wp)
    if mode == "ln":
        in_specs.append(stat)
        args.append(meanp)
    in_specs.append(stat)
    args.append(rstdp)

    out_specs = [stat]
    out_shape = [jax.ShapeDtypeStruct((padded, 1), jnp.float32)]
    if mode == "ln":
        out_specs.append(stat)
        out_shape.append(jax.ShapeDtypeStruct((padded, 1), jnp.float32))
    if has_w:
        out_specs.append(grow)
        out_shape.append(jax.ShapeDtypeStruct((1, h_p), jnp.float32))
    if has_b:
        out_specs.append(grow)
        out_shape.append(jax.ShapeDtypeStruct((1, h_p), jnp.float32))

    with jax.named_scope("apex_ln_bwd_colsum"):
        outs = pl.pallas_call(
            functools.partial(_bwd_colsum_kernel, mode=mode, has_w=has_w,
                              has_b=has_b),
            grid=(nri, nci),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=_dimsem("arbitrary", "arbitrary"),
            interpret=pallas_interpret(interpret),
            name="apex_ln_bwd_colsum",
        )(*args)
    outs = list(outs)
    c1s = outs.pop(0)
    c2s = outs.pop(0) if mode == "ln" else None
    dw = outs.pop(0)[0, :h] if has_w else None
    db = outs.pop(0)[0, :h] if has_b else None

    # pass B: ri innermost so dx blocks stream; stats re-read per row tile
    blk2 = pl.BlockSpec((tr, tc), lambda ci, ri: (ri, ci),
                        memory_space=pltpu.VMEM)
    wspec2 = pl.BlockSpec((1, tc), lambda ci, ri: (0, ci),
                          memory_space=pltpu.VMEM)
    stat2 = pl.BlockSpec((tr, 1), lambda ci, ri: (ri, 0),
                         memory_space=pltpu.VMEM)
    in_specs2 = [blk2, blk2]
    args2 = [dyp, xp]
    if has_w:
        in_specs2.append(wspec2)
        args2.append(wp)
    if mode == "ln":
        in_specs2.append(stat2)
        args2.append(meanp)
    in_specs2.append(stat2)
    args2.append(rstdp)
    in_specs2.append(stat2)
    args2.append(c1s)
    if mode == "ln":
        in_specs2.append(stat2)
        args2.append(c2s)

    with jax.named_scope("apex_ln_bwd_coldx"):
        dx = pl.pallas_call(
            functools.partial(_bwd_dx_kernel, mode=mode, has_w=has_w,
                              inv_h=1.0 / h),
            grid=(nci, nri),
            in_specs=in_specs2,
            out_specs=blk2,
            out_shape=jax.ShapeDtypeStruct((padded, h_p), x2d.dtype),
            compiler_params=_dimsem("parallel", "parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_ln_bwd_coldx",
        )(*args2)
    return dx[:rows, :h], dw, db


def _row_spec(tile: int, h: int):
    return pl.BlockSpec((tile, h), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _stat_spec(tile: int):
    return pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _full_spec(h: int):
    return pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)


def _fwd_call(x2d, w, b, mode, eps, interpret):
    rows, h = x2d.shape
    tile = _row_tile(rows, h, n_bufs=4)
    xp, padded = _pad_rows(x2d, tile)
    grid = padded // tile

    in_specs = [_row_spec(tile, h)]
    args = [xp]
    if w is not None:
        in_specs.append(_full_spec(h))
        args.append(w.reshape(1, h))
    if b is not None:
        in_specs.append(_full_spec(h))
        args.append(b.reshape(1, h))

    out_shape = [jax.ShapeDtypeStruct((padded, h), x2d.dtype)]
    out_specs = [_row_spec(tile, h)]
    if mode == "ln":
        out_shape.append(jax.ShapeDtypeStruct((padded, 1), jnp.float32))
        out_specs.append(_stat_spec(tile))
    out_shape.append(jax.ShapeDtypeStruct((padded, 1), jnp.float32))
    out_specs.append(_stat_spec(tile))

    kernel = functools.partial(
        _fwd_kernel, mode=mode, eps=eps, has_w=w is not None, has_b=b is not None
    )
    with jax.named_scope("apex_ln_fwd"):
        outs = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_ln_fwd",
        )(*args)
    outs = [o[:rows] for o in outs]
    if mode == "ln":
        y, mean, rstd = outs
        return y, mean, rstd
    y, rstd = outs
    return y, None, rstd


def _bwd_call(dy2d, x2d, w, mean, rstd, mode, has_b, interpret):
    rows, h = x2d.shape
    tile = _row_tile(rows, h, n_bufs=6)
    # dispatch on the VMEM-derived tile (NOT the row-count-clamped one:
    # a short input at moderate H is not a reason to pay two passes)
    vmem_tile = (_VMEM_BUDGET // (6 * h * 4) // _SUBLANE) * _SUBLANE
    if vmem_tile < 128 and h >= _COL_TILE:
        # full-row tiles have shrunk below the pipelining sweet spot —
        # switch to the column-split structure (measured: h=4096 fwd+bwd
        # 420 GB/s single-pass vs the colsplit restructure; see above)
        return _bwd_call_colsplit(dy2d, x2d, w, mean, rstd, mode, has_b,
                                  interpret)
    xp, padded = _pad_rows(x2d, tile)
    dyp, _ = _pad_rows(dy2d, tile)
    meanp = _pad_rows(mean, tile)[0] if mode == "ln" else None
    rstdp, _ = _pad_rows(rstd, tile)
    grid = padded // tile
    has_w = w is not None

    in_specs = [_row_spec(tile, h), _row_spec(tile, h)]
    args = [dyp, xp]
    if has_w:
        in_specs.append(_full_spec(h))
        args.append(w.reshape(1, h))
    if mode == "ln":
        in_specs.append(_stat_spec(tile))
        args.append(meanp)
    in_specs.append(_stat_spec(tile))
    args.append(rstdp)

    # partial-per-step writes cost 8/tile of the row streams: worth it
    # only when tiles are big (see the kernel's strategy note)
    accum_parts = tile >= 128
    if accum_parts:
        gw_spec = pl.BlockSpec((8, h), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)
        gw_shape = jax.ShapeDtypeStruct((grid * 8, h), jnp.float32)
    else:
        gw_spec = _full_spec(h)
        gw_shape = jax.ShapeDtypeStruct((1, h), jnp.float32)
    out_shape = [jax.ShapeDtypeStruct((padded, h), x2d.dtype)]
    out_specs = [_row_spec(tile, h)]
    if has_w:
        out_shape.append(gw_shape)
        out_specs.append(gw_spec)
    if has_b:
        out_shape.append(gw_shape)
        out_specs.append(gw_spec)

    kernel = functools.partial(
        _bwd_kernel, mode=mode, has_w=has_w, has_b=has_b,
        accum_parts=accum_parts,
    )
    with jax.named_scope("apex_ln_bwd"):
        outs = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=_dimsem("arbitrary"),
            interpret=pallas_interpret(interpret),
            name="apex_ln_bwd",
        )(*args)
    outs = list(outs)
    dx = outs.pop(0)[:rows]
    dw = outs.pop(0).sum(axis=0) if has_w else None
    db = outs.pop(0).sum(axis=0) if has_b else None
    return dx, dw, db


# ---------------------------------------------------------------------------
# custom_vjp cores. eps/interpret are non-diff leading args (hashable
# statics), mirroring the reference's autograd.Function ctx attributes.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ln_affine(eps, interpret, x2d, w, b):
    y, _, _ = _fwd_call(x2d, w, b, "ln", eps, interpret)
    return y

def _ln_affine_fwd(eps, interpret, x2d, w, b):
    y, mean, rstd = _fwd_call(x2d, w, b, "ln", eps, interpret)
    # b rides along only to carry its dtype for the cotangent (it is (H,),
    # negligible next to the x residual).
    return y, (x2d, w, b, mean, rstd)

def _ln_affine_bwd(eps, interpret, res, dy):
    x2d, w, b, mean, rstd = res
    dx, dw, db = _bwd_call(dy, x2d, w, mean, rstd, "ln", True, interpret)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)

_ln_affine.defvjp(_ln_affine_fwd, _ln_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ln_plain(eps, interpret, x2d):
    y, _, _ = _fwd_call(x2d, None, None, "ln", eps, interpret)
    return y

def _ln_plain_fwd(eps, interpret, x2d):
    y, mean, rstd = _fwd_call(x2d, None, None, "ln", eps, interpret)
    return y, (x2d, mean, rstd)

def _ln_plain_bwd(eps, interpret, res, dy):
    x2d, mean, rstd = res
    dx, _, _ = _bwd_call(dy, x2d, None, mean, rstd, "ln", False, interpret)
    return (dx,)

_ln_plain.defvjp(_ln_plain_fwd, _ln_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rms_affine(eps, interpret, x2d, w):
    y, _, _ = _fwd_call(x2d, w, None, "rms", eps, interpret)
    return y

def _rms_affine_fwd(eps, interpret, x2d, w):
    y, _, rstd = _fwd_call(x2d, w, None, "rms", eps, interpret)
    return y, (x2d, w, rstd)

def _rms_affine_bwd(eps, interpret, res, dy):
    x2d, w, rstd = res
    dx, dw, _ = _bwd_call(dy, x2d, w, None, rstd, "rms", False, interpret)
    return dx, dw.astype(w.dtype)

_rms_affine.defvjp(_rms_affine_fwd, _rms_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rms_plain(eps, interpret, x2d):
    y, _, _ = _fwd_call(x2d, None, None, "rms", eps, interpret)
    return y

def _rms_plain_fwd(eps, interpret, x2d):
    y, _, rstd = _fwd_call(x2d, None, None, "rms", eps, interpret)
    return y, (x2d, rstd)

def _rms_plain_bwd(eps, interpret, res, dy):
    x2d, rstd = res
    dx, _, _ = _bwd_call(dy, x2d, None, None, rstd, "rms", False, interpret)
    return (dx,)

_rms_plain.defvjp(_rms_plain_fwd, _rms_plain_bwd)


# ---------------------------------------------------------------------------
# Public functional API (names mirror apex/normalization/fused_layer_norm.py).
# ---------------------------------------------------------------------------

def fused_layer_norm_affine(x, weight, bias, normalized_shape: Shape,
                            eps: float = 1e-5, *, interpret: Optional[bool] = None):
    """LayerNorm over the trailing ``normalized_shape`` dims with affine
    params (ref: ``fused_layer_norm_affine``)."""
    h = _normalized_size(normalized_shape)
    y = _ln_affine(float(eps), interpret, x.reshape(-1, h),
                   weight.reshape(h), bias.reshape(h))
    return y.reshape(x.shape)


def fused_layer_norm(x, normalized_shape: Shape, eps: float = 1e-5,
                     *, interpret: Optional[bool] = None):
    h = _normalized_size(normalized_shape)
    return _ln_plain(float(eps), interpret, x.reshape(-1, h)).reshape(x.shape)


def fused_rms_norm_affine(x, weight, normalized_shape: Shape,
                          eps: float = 1e-5, *, interpret: Optional[bool] = None):
    h = _normalized_size(normalized_shape)
    y = _rms_affine(float(eps), interpret, x.reshape(-1, h), weight.reshape(h))
    return y.reshape(x.shape)


def fused_rms_norm(x, normalized_shape: Shape, eps: float = 1e-5,
                   *, interpret: Optional[bool] = None):
    h = _normalized_size(normalized_shape)
    return _rms_plain(float(eps), interpret, x.reshape(-1, h)).reshape(x.shape)


# ---------------------------------------------------------------------------
# Module-shaped API. Functional modules: ``init()`` -> params dict,
# ``apply(params, x)`` -> output (ref: ``class FusedLayerNorm(torch.nn.Module)``).
# ---------------------------------------------------------------------------

class FusedLayerNorm:
    """LayerNorm module (ref: ``apex/normalization/fused_layer_norm.py ::
    class FusedLayerNorm``). Params live in a dict pytree; stats are fp32."""

    mode = "ln"

    def __init__(self, normalized_shape: Shape, eps: float = 1e-5,
                 elementwise_affine: bool = True, param_dtype=jnp.float32):
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.eps = float(eps)
        self.elementwise_affine = bool(elementwise_affine)
        self.param_dtype = param_dtype

    def init(self, key: Optional[jax.Array] = None) -> dict:
        del key  # LN init is deterministic (weight=1, bias=0)
        if not self.elementwise_affine:
            return {}
        params = {"weight": jnp.ones(self.normalized_shape, self.param_dtype)}
        if self.mode == "ln":
            params["bias"] = jnp.zeros(self.normalized_shape, self.param_dtype)
        return params

    def apply(self, params: dict, x, *, interpret: Optional[bool] = None):
        if self.mode == "ln":
            if self.elementwise_affine:
                return fused_layer_norm_affine(
                    x, params["weight"], params["bias"],
                    self.normalized_shape, self.eps, interpret=interpret)
            return fused_layer_norm(x, self.normalized_shape, self.eps,
                                    interpret=interpret)
        if self.elementwise_affine:
            return fused_rms_norm_affine(x, params["weight"],
                                         self.normalized_shape, self.eps,
                                         interpret=interpret)
        return fused_rms_norm(x, self.normalized_shape, self.eps,
                              interpret=interpret)

    __call__ = apply


class FusedRMSNorm(FusedLayerNorm):
    """RMSNorm module (ref: ``class FusedRMSNorm``): no mean subtraction,
    no bias."""

    mode = "rms"


class MixedFusedLayerNorm(FusedLayerNorm):
    """fp16/bf16 activations with fp32 params & stats (ref:
    ``class MixedFusedLayerNorm``). Our kernels always keep stats fp32, so
    "mixed" only pins the param dtype."""

    def __init__(self, normalized_shape: Shape, eps: float = 1e-5, **kw):
        kw.pop("param_dtype", None)
        super().__init__(normalized_shape, eps, param_dtype=jnp.float32, **kw)


class MixedFusedRMSNorm(FusedRMSNorm):
    def __init__(self, normalized_shape: Shape, eps: float = 1e-5, **kw):
        kw.pop("param_dtype", None)
        super().__init__(normalized_shape, eps, param_dtype=jnp.float32, **kw)
