"""Fused softmax-cross-entropy — Pallas kernels.

Reference: ``apex/contrib/csrc/xentropy/xentropy_kernel.cu`` +
``apex/contrib/xentropy :: SoftmaxCrossEntropyLoss`` — loss (with
in-place label smoothing) computed WITHOUT materializing the softmax /
log-softmax over the vocabulary.

The naive jnp path materializes an (N, V) fp32 log-softmax (≈4 GB for
a 32×512 batch over a 30k vocab) plus the gather; here the forward is a
flash-style online logsumexp sweep over vocab tiles producing only the
per-row ``(loss, lse)`` — O(N) HBM output — and the backward emits
``dx = (softmax(x) - target) * dloss`` tile by tile, recomputing
``exp(x - lse)`` from the saved lse instead of re-normalizing.

Semantics (matching the reference kernel):
- ``loss = lse - (1-eps) * x[label] - eps * mean_valid(x)``
  (label smoothing spreads eps uniformly over the vocab);
- rows with ``label < 0`` are ignored (zero loss, zero grad) — the
  functional analogue of the reference's padding handling.

Shapes: any ``n``, any ``v``, float32 or bfloat16. The kernels read the
logits, and write ``dx``, at the shape the caller hands over, in blocks of
256 rows x 2,048 vocabulary lanes (fewer lanes for a vocabulary under
2,048). Nothing is padded in HBM: where ``n`` or ``v`` is no multiple of
the block, the last block is ragged. Pallas fills the out-of-bounds part
of an input block with unspecified values (NaN in interpret mode) and
drops out-of-bounds writes, so every use of ``x`` sits behind the
``col < v`` mask. Rows need none: every reduction runs along the
vocabulary, so what a ragged row block holds out of bounds stays in rows
whose loss is zeroed (``row >= n``) and whose ``dx`` is never written.
Only the four 1-D vectors (labels, loss, lse, dloss) are padded, to a
multiple of 256 rows.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.math import round_up_to_multiple
from apex_tpu.utils.pallas import (
    dimsem as _dimsem,
    NEG_INF as _NEG,
    pad_axis as _pad_axis,
)
from apex_tpu.utils.platform import pallas_interpret

_BR = 256     # rows per block (sublane dim)
_BV = 2048    # vocab lanes per block


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref,
                m_ref, l_ref, xy_ref, xsum_ref, *, n, v, eps):
    rt, vt = pl.program_id(0), pl.program_id(1)
    nv = pl.num_programs(1)
    br = x_ref.shape[0]

    @pl.when(vt == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        xy_ref[:] = jnp.zeros_like(xy_ref)
        xsum_ref[:] = jnp.zeros_like(xsum_ref)

    x = x_ref[:].astype(jnp.float32)
    bv = x.shape[1]
    col = vt * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    in_vocab = col < v
    # the one read of the block: lanes out of bounds (a ragged last block
    # holds unspecified values there) are replaced before any use
    x = jnp.where(in_vocab, x, _NEG)

    m_prev = m_ref[:, 0:1]
    m_cur = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(in_vocab, jnp.exp(x - m_cur), 0.0)
    l_ref[:, 0:1] = l_ref[:, 0:1] * alpha + jnp.sum(p, 1, keepdims=True)
    m_ref[:, 0:1] = m_cur

    labels = lab_ref[0, pl.ds(rt * br, br)][:, None]  # (br, 1)
    xy_ref[:, 0:1] += jnp.sum(jnp.where(col == labels, x, 0.0), 1,
                              keepdims=True)
    if eps > 0.0:
        xsum_ref[:, 0:1] += jnp.sum(jnp.where(in_vocab, x, 0.0), 1,
                                    keepdims=True)

    @pl.when(vt == nv - 1)
    def _():
        lse = m_ref[:, 0] + jnp.log(l_ref[:, 0])
        labels_row = lab_ref[0, pl.ds(rt * br, br)]
        row = rt * br + jax.lax.broadcasted_iota(
            jnp.int32, (br, 1), 0)[:, 0]
        ignore = (labels_row < 0) | (row >= n)
        loss = lse - (1.0 - eps) * xy_ref[:, 0]
        if eps > 0.0:
            loss = loss - eps * xsum_ref[:, 0] / v
        loss_ref[0, pl.ds(rt * br, br)] = jnp.where(ignore, 0.0, loss)
        lse_ref[0, pl.ds(rt * br, br)] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, dl_ref, dx_ref, *, n, v, eps):
    rt, vt = pl.program_id(0), pl.program_id(1)
    br = x_ref.shape[0]
    x = x_ref[:].astype(jnp.float32)
    bv = x.shape[1]
    col = vt * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    in_vocab = col < v
    lse = lse_ref[0, pl.ds(rt * br, br)][:, None]
    labels = lab_ref[0, pl.ds(rt * br, br)][:, None]
    dloss = dl_ref[0, pl.ds(rt * br, br)][:, None]
    row = rt * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    live = jnp.logical_not((labels < 0) | (row >= n))
    # x is unspecified out of bounds (ragged last block) and soft may be
    # anything there: the select below keeps it out of the block, and the
    # block's out-of-bounds part is never written to dx
    soft = jnp.exp(x - lse)
    target = (1.0 - eps) * (col == labels).astype(jnp.float32)
    if eps > 0.0:
        target = target + eps / v
    g = jnp.where(in_vocab & live, (soft - target) * dloss, 0.0)
    dx_ref[:] = g.astype(dx_ref.dtype)


def _row_spec(n_p):
    return pl.BlockSpec((1, n_p), lambda rt, vt: (0, 0),
                        memory_space=pltpu.VMEM)


def _tiling(n, v):
    """``(n_p, grid, x_spec)`` for (n, v) logits: the logits' blocks cover
    the operand as it is (ragged last blocks), the row vectors are n_p
    long."""
    n_p = round_up_to_multiple(n, _BR)
    bv = min(_BV, round_up_to_multiple(v, 128))
    x_spec = pl.BlockSpec((_BR, bv), lambda rt, vt: (rt, vt),
                          memory_space=pltpu.VMEM)
    return n_p, (n_p // _BR, pl.cdiv(v, bv)), x_spec


def _fwd_call(logits, labels, eps, interpret):
    n, v = logits.shape
    n_p, grid, x_spec = _tiling(n, v)
    lab = _pad_axis(labels.astype(jnp.int32), n_p, 0, -1)[None, :]
    with jax.named_scope("apex_xentropy_fwd"):
        loss, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, n=n, v=v, eps=eps),
            grid=grid,
            in_specs=[x_spec, _row_spec(n_p)],
            out_specs=(_row_spec(n_p), _row_spec(n_p)),
            out_shape=(jax.ShapeDtypeStruct((1, n_p), jnp.float32),
                       jax.ShapeDtypeStruct((1, n_p), jnp.float32)),
            scratch_shapes=[pltpu.VMEM((_BR, 128), jnp.float32)] * 4,
            # BOTH dims arbitrary: the (1, n_p) loss/lse outputs are one
            # revisited block each row-tile writes a slice of — a "parallel"
            # rt could be split across megacore TensorCores, each holding a
            # private copy and losing the other's slices
            compiler_params=_dimsem("arbitrary", "arbitrary"),
            interpret=pallas_interpret(interpret),
            name="apex_xentropy_fwd",
        )(logits, lab)
    return loss[0, :n], lse  # lse stays padded (1, n_p)


def _bwd_call(logits, labels, lse_p, dloss, eps, interpret):
    n, v = logits.shape
    n_p, grid, x_spec = _tiling(n, v)
    lab = _pad_axis(labels.astype(jnp.int32), n_p, 0, -1)[None, :]
    dl = _pad_axis(dloss.astype(jnp.float32), n_p, 0)[None, :]
    with jax.named_scope("apex_xentropy_bwd"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, n=n, v=v, eps=eps),
            grid=grid,
            in_specs=[x_spec, _row_spec(n_p), _row_spec(n_p), _row_spec(n_p)],
            out_specs=x_spec,
            out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype),
            compiler_params=_dimsem("parallel", "parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_xentropy_bwd",
        )(logits, lab, lse_p, dl)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _xent_core(cfg, logits, labels):
    eps, interpret = cfg
    loss, _ = _fwd_call(logits, labels, eps, interpret)
    return loss


def _xent_fwd(cfg, logits, labels):
    eps, interpret = cfg
    loss, lse_p = _fwd_call(logits, labels, eps, interpret)
    return loss, (logits, labels, lse_p)


def _xent_bwd(cfg, res, dloss):
    eps, interpret = cfg
    logits, labels, lse_p = res
    dx = _bwd_call(logits, labels, lse_p, dloss, eps, interpret)
    return dx, None


_xent_core.defvjp(_xent_fwd, _xent_bwd)


def softmax_cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                               smoothing: float = 0.0,
                               interpret: Optional[bool] = None
                               ) -> jax.Array:
    """Per-row cross entropy without materializing log-softmax.

    logits: (N, V); labels: (N,) int, negative = ignore. Returns (N,)
    fp32 losses (ref: ``xentropy :: SoftmaxCrossEntropyLoss.apply``).
    """
    return _xent_core((float(smoothing), interpret), logits, labels)


class SoftmaxCrossEntropyLoss:
    """API-parity shim for the reference module (``half_to_float`` is
    implicit: losses are always fp32)."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=None,
              half_to_float=True):
        if padding_idx is not None:
            labels = jnp.where(labels == padding_idx, -1, labels)
        return softmax_cross_entropy_loss(logits, labels, smoothing)
