"""Pallas flat-buffer kernels — the TPU-native ``amp_C``.

Each kernel walks a ``(rows, 128)`` flat buffer (see ``flatten.py``) in
``(BLOCK_ROWS, 128)`` tiles, one grid step per tile, double-buffered by the
Pallas pipeline. Reductions emit per-tile partials that are combined outside
the kernel (the CUDA two-stage reduction pattern of
``csrc/multi_tensor_l2norm_kernel.cu``); the overflow flag of
``csrc/multi_tensor_scale_kernel.cu`` becomes a per-tile finite bit reduced
with ``jnp.all``. Optimizer updates alias their state buffers in place
(``input_output_aliases``) so a step is a single read-modify-write pass over
HBM, matching the one-kernel-per-step property of ``csrc/multi_tensor_adam.cu``.

Hyperparameters arrive as a ``(1, N)`` fp32 array in SMEM so that traced
values (schedules, dynamic loss scale) never trigger recompilation.

Reduced-precision state: the first-moment buffer of Adam/LAMB/NovoGrad (and
the SGD momentum buffer) may be bf16 — kernels load it with an fp32 upcast,
accumulate in fp32, and store back in the buffer's own dtype (plain
round-to-nearest-even, no stochastic rounding; the fp32 master keeps the
update unbiased enough — see ``docs/source/optimizer_states.rst``). ``v``
stays fp32 always. BLOCK_ROWS=256 is divisible by the bf16 min-tile
sublane count (16), so bf16 buffers reuse the same ``(256, 128)`` grid.
The optimizer kernels can additionally emit the updated params pre-cast to
a compute dtype (``emit_compute_dtype=jnp.bfloat16``) as one extra output
written from registers — the fused cast-out that lets amp-O2 skip its
separate fp32→bf16 ``model_params_from_master`` pass over the master tree.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.multi_tensor_apply.flatten import ALIGN_ROWS, LANES
from apex_tpu.utils.math import cdiv
from apex_tpu.utils.pallas import dimsem as _dimsem
from apex_tpu.utils.platform import pallas_interpret

BLOCK_ROWS = ALIGN_ROWS  # (256, 128) fp32 tile = 128 KiB per buffer;
# equals the FlatSpec whole-buffer alignment so flat buffers never need
# pad/slice here (input_output_aliases stays a true in-place update)


def _pad_to_block(buf: jax.Array) -> jax.Array:
    rows = buf.shape[0]
    padded = cdiv(rows, BLOCK_ROWS) * BLOCK_ROWS
    if padded != rows:
        buf = jnp.pad(buf, ((0, padded - rows), (0, 0)))
    return buf


def _tile_spec():
    return pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _partial_spec():
    return pl.BlockSpec((1, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# scale (+ overflow check) — ref csrc/multi_tensor_scale_kernel.cu
# ---------------------------------------------------------------------------

def _scale_kernel(sc_ref, x_ref, out_ref, finite_ref):
    x = x_ref[:].astype(jnp.float32)
    out_ref[:] = (x * sc_ref[0, 0]).astype(out_ref.dtype)
    # Overflow is judged on the INCOMING values (pre-unscale), as the
    # reference's overflow_buf does.
    finite_ref[0, 0] = jnp.all(jnp.isfinite(x)).astype(jnp.int32)


def flat_scale(buf: jax.Array, scale, out_dtype=None,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Returns (buf * scale, found_inf: bool scalar)."""
    rows = buf.shape[0]
    x = _pad_to_block(buf)
    n_tiles = x.shape[0] // BLOCK_ROWS
    sc = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    with jax.named_scope("apex_mt_scale"):
        out, finite = pl.pallas_call(
            _scale_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec(), _tile_spec()],
            out_specs=[_tile_spec(), _partial_spec()],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, out_dtype or buf.dtype),
                jax.ShapeDtypeStruct((n_tiles, 1), jnp.int32),
            ],
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_scale",
        )(sc, x)
    return out[:rows], jnp.logical_not(jnp.all(finite == 1))


# ---------------------------------------------------------------------------
# axpby — ref csrc/multi_tensor_axpby_kernel.cu
# ---------------------------------------------------------------------------

def _axpby_kernel(sc_ref, x_ref, y_ref, out_ref, finite_ref):
    x = x_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    r = sc_ref[0, 0] * x + sc_ref[0, 1] * y
    out_ref[:] = r.astype(out_ref.dtype)
    finite_ref[0, 0] = jnp.all(jnp.isfinite(r)).astype(jnp.int32)


def flat_axpby(a, x: jax.Array, b, y: jax.Array, out_dtype=None,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    rows = x.shape[0]
    xp, yp = _pad_to_block(x), _pad_to_block(y)
    n_tiles = xp.shape[0] // BLOCK_ROWS
    sc = jnp.stack([jnp.asarray(a, jnp.float32),
                    jnp.asarray(b, jnp.float32)]).reshape(1, 2)
    with jax.named_scope("apex_mt_axpby"):
        out, finite = pl.pallas_call(
            _axpby_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec(), _tile_spec(), _tile_spec()],
            out_specs=[_tile_spec(), _partial_spec()],
            out_shape=[
                jax.ShapeDtypeStruct(xp.shape, out_dtype or x.dtype),
                jax.ShapeDtypeStruct((n_tiles, 1), jnp.int32),
            ],
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_axpby",
        )(sc, xp, yp)
    return out[:rows], jnp.logical_not(jnp.all(finite == 1))


# ---------------------------------------------------------------------------
# L2 norm — ref csrc/multi_tensor_l2norm_kernel.cu (two-stage reduction)
# ---------------------------------------------------------------------------

_SUB = 8  # fine-partial granularity = one (8, 128) fp32 tile
_SUBS_PER_BLOCK = BLOCK_ROWS // _SUB


def _l2_kernel(x_ref, part_ref):
    x = x_ref[:].astype(jnp.float32)
    # one partial per (8, 128) sub-tile — tensor spans are 8-row aligned
    # (flatten.TILE_ELEMS), so each partial belongs to exactly one tensor.
    part_ref[0, :] = jnp.sum((x * x).reshape(_SUBS_PER_BLOCK, _SUB * LANES),
                             axis=1)


def flat_l2norm_partials(buf: jax.Array,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Per-(8, 128)-sub-tile sum-of-squares partials, fp32, shape (rows/8,)
    (padded up to a whole number of blocks; pad partials are zero).

    ``sqrt(sum(partials))`` is the global norm; a segment-sum of partials by
    ``FlatSpec.tile_tensor_ids(8)`` gives per-tensor norms (used by LAMB
    trust ratios) — stage 2 of the CUDA two-stage reduction, done by XLA.
    """
    x = _pad_to_block(buf)
    n_tiles = x.shape[0] // BLOCK_ROWS
    with jax.named_scope("apex_mt_l2norm"):
        parts = pl.pallas_call(
            _l2_kernel,
            grid=(n_tiles,),
            in_specs=[_tile_spec()],
            out_specs=pl.BlockSpec((1, _SUBS_PER_BLOCK), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n_tiles, _SUBS_PER_BLOCK),
                                           jnp.float32),
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_l2norm",
        )(x)
    return parts.reshape(-1)


def flat_l2norm(buf: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    return jnp.sqrt(jnp.sum(flat_l2norm_partials(buf, interpret)))


# ---------------------------------------------------------------------------
# Adam / AdamW — ref csrc/multi_tensor_adam.cu
# ---------------------------------------------------------------------------

def _adam_kernel(sc_ref, g_ref, p_ref, m_ref, v_ref,
                 p_out, m_out, v_out, *pc_out):
    lr = sc_ref[0, 0]
    b1 = sc_ref[0, 1]
    b2 = sc_ref[0, 2]
    eps = sc_ref[0, 3]
    wd = sc_ref[0, 4]
    c1 = sc_ref[0, 5]       # 1 - b1^t   (1.0 when bias_correction off)
    c2 = sc_ref[0, 6]       # 1 - b2^t
    adam_w = sc_ref[0, 7]   # 1.0 => decoupled (AdamW), 0.0 => L2 into grad
    grad_scale = sc_ref[0, 8]  # combined inv-loss-scale (1.0 when unused)

    g = g_ref[:].astype(jnp.float32) * grad_scale
    p = p_ref[:]
    m = m_ref[:].astype(jnp.float32)   # fp32 accumulate for bf16 moments
    v = v_ref[:]

    g_l2 = g + (1.0 - adam_w) * wd * p
    m = b1 * m + (1.0 - b1) * g_l2
    v = b2 * v + (1.0 - b2) * g_l2 * g_l2
    update = (m / c1) / (jnp.sqrt(v / c2) + eps) + adam_w * wd * p
    p_new = p - lr * update
    p_out[:] = p_new
    m_out[:] = m.astype(m_out.dtype)
    v_out[:] = v
    if pc_out:  # fused cast-out: compute params written from registers
        pc_out[0][:] = p_new.astype(pc_out[0].dtype)


def _sgd_kernel(sc_ref, g_ref, p_ref, buf_ref, p_out, buf_out, *pc_out):
    lr = sc_ref[0, 0]
    mom = sc_ref[0, 1]
    damp = sc_ref[0, 2]
    wd = sc_ref[0, 3]
    nesterov = sc_ref[0, 4]        # 1.0 / 0.0
    wd_after = sc_ref[0, 5]        # 1.0 => wd after momentum
    first = sc_ref[0, 6]           # 1.0 on the seeding step
    grad_scale = sc_ref[0, 7]
    use_mom = sc_ref[0, 8]         # momentum > 0

    g = g_ref[:].astype(jnp.float32) * grad_scale
    p = p_ref[:]
    buf = buf_ref[:].astype(jnp.float32)

    g = g + (1.0 - wd_after) * wd * p
    seeded = jnp.where(first > 0, g, mom * buf + (1.0 - damp) * g)
    d_mom = jnp.where(nesterov > 0, g + mom * seeded, seeded)
    d = jnp.where(use_mom > 0, d_mom, g)
    buf_out[:] = jnp.where(use_mom > 0, seeded, buf).astype(buf_out.dtype)
    d = d + wd_after * wd * p
    p_new = p - lr * d
    p_out[:] = p_new
    if pc_out:
        pc_out[0][:] = p_new.astype(pc_out[0].dtype)


def flat_sgd(grads: jax.Array, params: jax.Array, momentum_buf: jax.Array,
             *, lr, momentum: float, dampening: float, weight_decay,
             nesterov: bool, wd_after_momentum: bool, first_run,
             grad_scale=1.0, emit_compute_dtype=None,
             interpret: Optional[bool] = None):
    """One fused SGD step over flat buffers (ref:
    ``csrc/multi_tensor_sgd_kernel.cu`` incl. the ``first_run`` buffer
    seeding and ``wd_after_momentum``). ``params``/``momentum_buf`` alias
    in place; ``first_run`` may be a traced bool. ``momentum_buf`` may be
    bf16 (fp32 accumulate); ``emit_compute_dtype`` appends the fused
    cast-out output (return grows to ``(p, buf, compute)``)."""
    rows = params.shape[0]
    gp, pp, bp = (_pad_to_block(b) for b in (grads, params, momentum_buf))
    n_tiles = pp.shape[0] // BLOCK_ROWS
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.float32(momentum),
        jnp.float32(dampening), jnp.asarray(weight_decay, jnp.float32),
        jnp.float32(1.0 if nesterov else 0.0),
        jnp.float32(1.0 if wd_after_momentum else 0.0),
        jnp.asarray(first_run, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
        jnp.float32(1.0 if momentum > 0 else 0.0),
    ]).reshape(1, 9)
    out_shape = [jax.ShapeDtypeStruct(pp.shape, jnp.float32),
                 jax.ShapeDtypeStruct(pp.shape, bp.dtype)]
    if emit_compute_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct(pp.shape, emit_compute_dtype))
    with jax.named_scope("apex_mt_sgd"):
        outs = pl.pallas_call(
            _sgd_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec()] + [_tile_spec()] * 3,
            out_specs=[_tile_spec()] * len(out_shape),
            out_shape=out_shape,
            input_output_aliases={2: 0, 3: 1},
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_sgd",
        )(sc, gp, pp, bp)
    return tuple(o[:rows] for o in outs)


# ---------------------------------------------------------------------------
# LAMB — ref csrc/multi_tensor_lamb.cu (_stage_1 + _stage_2)
# ---------------------------------------------------------------------------

def _lamb_stage1_kernel(sc_ref, g_ref, p_ref, m_ref, v_ref,
                        m_out, v_out, u_out, p_ssq, u_ssq):
    b1 = sc_ref[0, 0]
    b2 = sc_ref[0, 1]
    eps = sc_ref[0, 2]
    wd = sc_ref[0, 3]
    c1 = sc_ref[0, 4]
    c2 = sc_ref[0, 5]
    adam_w = sc_ref[0, 6]
    beta3 = sc_ref[0, 7]          # 1-b1 (grad averaging) or 1.0
    gs_over_clip = sc_ref[0, 8]   # grad_scale / clip, combined

    g = g_ref[:].astype(jnp.float32) * gs_over_clip
    p = p_ref[:]
    m = m_ref[:].astype(jnp.float32)   # fp32 accumulate for bf16 moments
    v = v_ref[:]

    g_l2 = g + (1.0 - adam_w) * wd * p
    m = b1 * m + beta3 * g_l2
    v = b2 * v + (1.0 - b2) * g_l2 * g_l2
    u = (m / c1) / (jnp.sqrt(v / c2) + eps) + adam_w * wd * p
    m_out[:] = m.astype(m_out.dtype)
    v_out[:] = v
    u_out[:] = u
    # fused stage-2 preamble: per-(8,128)-sub-tile ||p||², ||u||² partials
    # (tensor spans are 8-row aligned, so each partial maps to one tensor)
    p_ssq[0, :] = jnp.sum((p * p).reshape(_SUBS_PER_BLOCK, _SUB * LANES), 1)
    u_ssq[0, :] = jnp.sum((u * u).reshape(_SUBS_PER_BLOCK, _SUB * LANES), 1)


def flat_lamb(grads: jax.Array, params: jax.Array, m: jax.Array,
              v: jax.Array, tile_ids, *, lr, beta1: float, beta2: float,
              eps: float, step, weight_decay, num_tensors: int,
              adam_w_mode: bool = True, grad_averaging: bool = True,
              bias_correction: bool = True, use_nvlamb: bool = False,
              max_grad_norm: float = 1.0, grad_scale=1.0,
              grad_norm=None, emit_compute_dtype=None,
              interpret: Optional[bool] = None):
    """Fused LAMB step over flat buffers, following the CUDA
    two-stage split: stage 1 (one Pallas pass) produces moments, the raw
    update AND the per-sub-tile ||p||²/||u||² partials; the per-tensor
    trust-ratio combine (segment-sum + ratio) and the stage-2
    ``p -= lr·ratio·u`` are XLA elementwise/reduction ops that fuse into
    two trivial passes. ``tile_ids`` is ``FlatSpec.tile_tensor_ids(8)``.
    The global grad-norm clip uses one ``flat_l2norm`` pre-pass over the
    scaled grads (the reference likewise pre-reduces). ``m`` may be bf16
    (fp32 accumulate in stage 1); ``emit_compute_dtype`` appends the
    cast-out params to the return (the cast fuses into the XLA stage-2
    pass — no extra read of the fp32 params)."""
    rows = params.shape[0]
    gs = jnp.asarray(grad_scale, jnp.float32)
    if grad_norm is None:
        grad_norm = jnp.sqrt(jnp.sum(
            flat_l2norm_partials(grads, interpret)) * gs * gs)
    max_norm = jnp.float32(max_grad_norm)
    clip = jnp.where((max_norm > 0) & (grad_norm > max_norm),
                     grad_norm / max_norm, jnp.float32(1.0))

    gp, pp, mp, vp = (_pad_to_block(b) for b in (grads, params, m, v))
    n_tiles = pp.shape[0] // BLOCK_ROWS
    t = jnp.asarray(step, jnp.float32)
    if bias_correction:
        c1 = 1.0 - jnp.float32(beta1) ** t
        c2 = 1.0 - jnp.float32(beta2) ** t
    else:
        c1 = c2 = jnp.float32(1.0)
    sc = jnp.stack([
        jnp.float32(beta1), jnp.float32(beta2), jnp.float32(eps),
        jnp.asarray(weight_decay, jnp.float32), c1, c2,
        jnp.float32(1.0 if adam_w_mode else 0.0),
        jnp.float32(1.0 - beta1 if grad_averaging else 1.0),
        gs / clip,
    ]).reshape(1, 9)
    part_spec = pl.BlockSpec((1, _SUBS_PER_BLOCK), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    with jax.named_scope("apex_mt_lamb"):
        m_new, v_new, u, p_parts, u_parts = pl.pallas_call(
            _lamb_stage1_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec()] + [_tile_spec()] * 4,
            out_specs=[_tile_spec()] * 3 + [part_spec] * 2,
            out_shape=[jax.ShapeDtypeStruct(pp.shape, mp.dtype),
                       jax.ShapeDtypeStruct(pp.shape, jnp.float32),
                       jax.ShapeDtypeStruct(pp.shape, jnp.float32)]
            + [jax.ShapeDtypeStruct((n_tiles, _SUBS_PER_BLOCK),
                                    jnp.float32)] * 2,
            input_output_aliases={3: 0, 4: 1},
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_lamb",
        )(sc, gp, pp, mp, vp)

    # stage 2: per-tensor trust ratios from the fused partials
    ids = jnp.asarray(tile_ids, jnp.int32)
    n_sub = rows // _SUB
    w_norm = jnp.sqrt(jax.ops.segment_sum(
        p_parts.reshape(-1)[:n_sub], ids, num_segments=num_tensors))
    u_norm = jnp.sqrt(jax.ops.segment_sum(
        u_parts.reshape(-1)[:n_sub], ids, num_segments=num_tensors))
    ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm,
                      jnp.float32(1.0))
    if not use_nvlamb:
        wd_t = jnp.asarray(weight_decay, jnp.float32)
        ratio = jnp.where(wd_t == 0.0, jnp.ones_like(ratio), ratio)
    row_ratio = jnp.repeat(ratio[ids], _SUB)[:, None]  # (rows, 1)
    lr_t = jnp.asarray(lr, jnp.float32)
    p_new = pp[:rows] - lr_t * row_ratio * u[:rows]
    if emit_compute_dtype is not None:
        return (p_new, m_new[:rows], v_new[:rows],
                p_new.astype(emit_compute_dtype))
    return p_new, m_new[:rows], v_new[:rows]


# ---------------------------------------------------------------------------
# Adagrad — ref csrc/multi_tensor_adagrad.cu
# ---------------------------------------------------------------------------

def _adagrad_kernel(sc_ref, g_ref, p_ref, s_ref, p_out, s_out, *pc_out):
    lr = sc_ref[0, 0]
    eps = sc_ref[0, 1]
    wd = sc_ref[0, 2]
    adagrad_w = sc_ref[0, 3]   # 1.0 => decoupled decay, 0.0 => L2 into grad
    grad_scale = sc_ref[0, 4]

    g = g_ref[:].astype(jnp.float32) * grad_scale
    p = p_ref[:]
    s = s_ref[:]

    g = g + (1.0 - adagrad_w) * wd * p
    s = s + g * g
    u = g / (jnp.sqrt(s) + eps) + adagrad_w * wd * p
    p_new = p - lr * u
    p_out[:] = p_new
    s_out[:] = s
    if pc_out:
        pc_out[0][:] = p_new.astype(pc_out[0].dtype)


def flat_adagrad(grads: jax.Array, params: jax.Array, gsum: jax.Array,
                 *, lr, eps: float, weight_decay,
                 adagrad_w_mode: bool = False, grad_scale=1.0,
                 emit_compute_dtype=None,
                 interpret: Optional[bool] = None):
    """One fused Adagrad step over flat fp32 buffers (ref:
    ``csrc/multi_tensor_adagrad.cu``); ``params``/``gsum`` alias in
    place. ``emit_compute_dtype`` appends the fused cast-out output."""
    rows = params.shape[0]
    gp, pp, sp = (_pad_to_block(b) for b in (grads, params, gsum))
    n_tiles = pp.shape[0] // BLOCK_ROWS
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.float32(eps),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.float32(1.0 if adagrad_w_mode else 0.0),
        jnp.asarray(grad_scale, jnp.float32),
    ]).reshape(1, 5)
    out_shape = [jax.ShapeDtypeStruct(pp.shape, jnp.float32)] * 2
    if emit_compute_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct(pp.shape, emit_compute_dtype))
    with jax.named_scope("apex_mt_adagrad"):
        outs = pl.pallas_call(
            _adagrad_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec()] + [_tile_spec()] * 3,
            out_specs=[_tile_spec()] * len(out_shape),
            out_shape=out_shape,
            input_output_aliases={2: 0, 3: 1},
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_adagrad",
        )(sc, gp, pp, sp)
    return tuple(o[:rows] for o in outs)


# ---------------------------------------------------------------------------
# NovoGrad — ref csrc/multi_tensor_novograd.cu (per-tensor second moment)
# ---------------------------------------------------------------------------

def _novograd_kernel(sc_ref, denom_ref, g_ref, p_ref, m_ref, p_out, m_out,
                     *pc_out):
    lr = sc_ref[0, 0]
    b1 = sc_ref[0, 1]
    beta3 = sc_ref[0, 2]       # 1-b1 (grad averaging) or 1.0
    wd = sc_ref[0, 3]
    c1 = sc_ref[0, 4]          # 1 - b1^t
    reg_inside = sc_ref[0, 5]  # 1.0 => wd folded into the moment
    grad_scale = sc_ref[0, 6]

    g = g_ref[:].astype(jnp.float32) * grad_scale
    p = p_ref[:]
    m = m_ref[:].astype(jnp.float32)   # fp32 accumulate for bf16 moments

    gn = g / denom_ref[:]      # per-row broadcast of the per-tensor denom
    gn = gn + reg_inside * wd * p
    m = b1 * m + beta3 * gn
    u = m / c1 + (1.0 - reg_inside) * wd * p
    p_new = p - lr * u
    p_out[:] = p_new
    m_out[:] = m.astype(m_out.dtype)
    if pc_out:
        pc_out[0][:] = p_new.astype(pc_out[0].dtype)


def flat_novograd(grads: jax.Array, params: jax.Array, m: jax.Array,
                  v: jax.Array, tile_ids, *, lr, beta1: float, beta2: float,
                  eps: float, step, weight_decay, num_tensors: int,
                  grad_averaging: bool = True, bias_correction: bool = True,
                  reg_inside_moment: bool = False, init_zero: bool = False,
                  grad_scale=1.0, emit_compute_dtype=None,
                  interpret: Optional[bool] = None):
    """Fused NovoGrad step over flat fp32 buffers. NovoGrad's second
    moment is ONE scalar per tensor (the layer-wise EMA of ||g||², ref
    ``multi_tensor_novograd.cu``), so ``v`` is a ``(num_tensors,)`` fp32
    vector: the per-sub-tile ||g||² partials come from one l2 pre-pass
    (the same two-stage reduction LAMB uses), the tiny v-EMA update is
    XLA, and the elementwise moment/param update is one Pallas pass with
    the per-tensor denominator broadcast in as a ``(rows, 1)`` column.
    ``tile_ids`` is ``FlatSpec.tile_tensor_ids(8)``. ``m`` may be bf16
    (fp32 accumulate); ``emit_compute_dtype`` appends the fused cast-out
    output (return grows to ``(p, m, v, compute)``).
    """
    rows = params.shape[0]
    gs = jnp.asarray(grad_scale, jnp.float32)
    ids = jnp.asarray(tile_ids, jnp.int32)
    n_sub = rows // _SUB
    gsq = jax.ops.segment_sum(
        flat_l2norm_partials(grads, interpret)[:n_sub], ids,
        num_segments=num_tensors) * gs * gs
    b2 = jnp.float32(beta2)
    first = jnp.asarray(step, jnp.int32) <= 1
    ema = b2 * v + (1.0 - b2) * gsq
    v_new = ema if init_zero else jnp.where(first, gsq, ema)

    t = jnp.asarray(step, jnp.float32)
    if bias_correction:
        c1 = 1.0 - jnp.float32(beta1) ** t
        c2 = 1.0 - b2 ** t
    else:
        c1 = c2 = jnp.float32(1.0)
    denom = jnp.sqrt(v_new / c2) + jnp.float32(eps)
    row_denom = jnp.repeat(denom[ids], _SUB)[:, None]  # (rows, 1)
    row_denom = _pad_to_block(row_denom)
    row_denom = jnp.where(row_denom == 0, 1.0, row_denom)  # block-pad rows

    gp, pp, mp = (_pad_to_block(b) for b in (grads, params, m))
    n_tiles = pp.shape[0] // BLOCK_ROWS
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.float32(beta1),
        jnp.float32(1.0 - beta1 if grad_averaging else 1.0),
        jnp.asarray(weight_decay, jnp.float32), c1,
        jnp.float32(1.0 if reg_inside_moment else 0.0), gs,
    ]).reshape(1, 7)
    denom_spec = pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    out_shape = [jax.ShapeDtypeStruct(pp.shape, jnp.float32),
                 jax.ShapeDtypeStruct(pp.shape, mp.dtype)]
    if emit_compute_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct(pp.shape, emit_compute_dtype))
    with jax.named_scope("apex_mt_novograd"):
        outs = pl.pallas_call(
            _novograd_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec(), denom_spec] + [_tile_spec()] * 3,
            out_specs=[_tile_spec()] * len(out_shape),
            out_shape=out_shape,
            input_output_aliases={3: 0, 4: 1},
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_novograd",
        )(sc, row_denom, gp, pp, mp)
    if emit_compute_dtype is not None:
        return outs[0][:rows], outs[1][:rows], v_new, outs[2][:rows]
    return outs[0][:rows], outs[1][:rows], v_new


def flat_adam(grads: jax.Array, params: jax.Array, m: jax.Array, v: jax.Array,
              *, lr, beta1: float, beta2: float, eps: float, step,
              weight_decay, adam_w_mode: bool = True,
              bias_correction: bool = True, grad_scale=1.0,
              emit_compute_dtype=None,
              interpret: Optional[bool] = None):
    """One fused Adam/AdamW step over flat buffers.

    ``params``/``m``/``v`` are aliased in place (donate them under jit).
    All hyperparameters may be traced scalars. ``m`` may be bf16 (loaded
    with an fp32 upcast, stored back in its own dtype); ``v`` must stay
    fp32. With ``emit_compute_dtype`` the kernel writes one extra
    (non-aliased) output — the updated params cast to that dtype — and the
    return grows to ``(p, m, v, compute)``.
    """
    rows = params.shape[0]
    gp, pp, mp, vp = (_pad_to_block(b) for b in (grads, params, m, v))
    n_tiles = pp.shape[0] // BLOCK_ROWS
    t = jnp.asarray(step, jnp.float32)
    if bias_correction:
        c1 = 1.0 - jnp.asarray(beta1, jnp.float32) ** t
        c2 = 1.0 - jnp.asarray(beta2, jnp.float32) ** t
    else:
        c1 = jnp.float32(1.0)
        c2 = jnp.float32(1.0)
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.float32(beta1), jnp.float32(beta2),
        jnp.float32(eps), jnp.asarray(weight_decay, jnp.float32), c1, c2,
        jnp.float32(1.0 if adam_w_mode else 0.0),
        jnp.asarray(grad_scale, jnp.float32),
    ]).reshape(1, 9)
    n_out = 3 + (1 if emit_compute_dtype is not None else 0)
    out_shape = [
        jax.ShapeDtypeStruct(pp.shape, jnp.float32),
        jax.ShapeDtypeStruct(pp.shape, mp.dtype),
        jax.ShapeDtypeStruct(pp.shape, jnp.float32),
    ]
    if emit_compute_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct(pp.shape, emit_compute_dtype))
    with jax.named_scope("apex_mt_adam"):
        outs = pl.pallas_call(
            _adam_kernel,
            grid=(n_tiles,),
            in_specs=[_smem_spec()] + [_tile_spec()] * 4,
            out_specs=[_tile_spec()] * n_out,
            out_shape=out_shape,
            input_output_aliases={2: 0, 3: 1, 4: 2},
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_mt_adam",
        )(sc, gp, pp, mp, vp)
    return tuple(o[:rows] for o in outs)
