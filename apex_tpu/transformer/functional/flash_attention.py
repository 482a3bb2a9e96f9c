"""Fused multi-head attention — flash-attention Pallas kernels.

Reference: ``apex/contrib/csrc/multihead_attn/*`` (fused QKV-softmax-
dropout-PV fwd/bwd, ~8k CUDA LoC) and ``apex/contrib/csrc/fmha/*``
(short-seqlen fused MHA) — SURVEY.md §2b calls this the largest single
kernel work item. Both are subsumed by one seqlen-generic flash-style
kernel pair:

- **forward**: grid ``(batch*heads, q_tiles, k_tiles)``; per q-tile a
  running (max, sum, acc) in VMEM scratch implements the online softmax
  (FlashAttention-2 recurrence); scores never touch HBM. Saves the
  per-row logsumexp for the backward.
- **backward**: the standard two-pass split — a dq kernel (k innermost)
  and a dk/dv kernel (q innermost) — recomputing score tiles from
  (q, k, lse) instead of materializing the (s, s) probability matrix,
  with ``D = rowsum(dout * out)`` precomputed outside.
- **dropout** follows the reference's saved-mask semantics
  (``masked_softmax_dropout_func``): probabilities are dropped AFTER
  normalization. The keep mask is never stored — it is regenerated in
  the backward from a counter-based hash of (seed, head, q, k), the
  TPU-friendly analogue of the CUDA kernels' saved-RNG-state replay.

Numerics: softmax in fp32 (scores masked to -1e30, matching the
``-10000``-additive convention of the fused softmax kernels for any
realistically-scaled logits); fully-masked rows return 0 (the
flash/fmha convention). ``mask`` is (b, s_k) with 1 = attend.

VPU diet (the d=64 lever — BERT-Large's own head shape ran at 18% of
peak while d=128 hit 38% at identical FLOPs, so the cost is per score
ELEMENT, not MXU occupancy):

- **base-2 online softmax**: ``log2(e)`` is folded into the
  q prescale that already exists, so every ``exp`` in the three kernels
  becomes the cheaper ``exp2`` (the hardware primitive ``exp`` lowers
  to — one fewer VPU multiply per score element per exponential) and
  the running max / logsumexp live in base 2 end to end. The backward
  kernels consume the base-2 lse directly (``exp2(s2 - lse2)`` is
  exactly the base-e probability); the only base conversion anywhere is
  ONE ln(2) multiply on the final dk tile (see ``_bwd_call`` — dk is
  ``ds^T @ (scale*log2e*q)``, i.e. log2e too big, and the fixup is
  d-sized, not s²-sized).
- **probability tiles in the operands' dtype**: p / ds are consumed only
  by MXU ``dot_general``s, so with bf16 operands they are cast to bf16
  immediately after the fp32 (m, l) statistics are updated, and the
  dropout keep/scale ops run on the bf16 tile. m, l, lse, acc stay fp32.
  fp32 inputs keep fp32 tiles (golden-test tolerances are tight).

Both, and the choices below them (no causal tile-skipping, tiles of up
to 512 at every head dim, ``dimension_semantics`` always given), were
settled by same-process A/B runs on a v5e whose records were deleted
(PR 21); no record of those measurements remains, and no benchmark cell
runs the other side of any of them, so the kernels are built one way.

Dropout masks are position-hashed (``_hash_keep``) and therefore
bit-identical between forward and backward, and independent of the
tile size.

A sliding window (``window``, forward only, with ``causal``): query ``i``
attends key ``j`` iff ``0 <= i - j < window``. Here whole k tiles outside
the band ARE left out, and not behind ``pl.when``: the grid's k extent is
the number of tiles a q tile's band can touch (2 for a band of 128 under
tiles of 512, whatever the sequence), and the index map names the band's
tiles, so what is skipped is never fetched. That is a different trade from
full causal's above: a band leaves out all but a constant number of tiles a
q tile, where the diagonal leaves out half. A step whose tile would lie
above the diagonal (the first q tiles, whose band is cut short by position
0) does nothing.

``precision`` (forward only, as ``window``): what the MXU does with the two
products. ``None`` is what it does by itself, ONE bfloat16 pass whatever the
operands' dtype; with float32 operands ``lax.Precision.HIGHEST`` takes its
full-precision passes, and the probabilities, the accumulator and the output
stay float32: a serving prompt path whose decode path is exact in q and p
attends that way over the rows its cache keeps (``models.exaone_moe``).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.math import round_up_to_multiple
from apex_tpu.utils.pallas import (
    NEG_INF as _NEG,
    dimsem as _dimsem,
    pad_axis as _pad_axis,
)
from apex_tpu.utils.platform import pallas_interpret


def _block(s_padded: int) -> int:
    """Largest of 512/256/128 that divides the padded length — bigger
    blocks amortize grid overhead and feed the MXU larger matmuls.
    Causal tiles above the diagonal are NOT skipped: gating whole tiles
    behind ``pl.when`` cost more than the skipped matmuls saved (the
    kernels are VPU-bound, and per-tile control flow defeats Mosaic's
    copy/compute overlap); the win that landed is the mask-free
    interior-tile path (``_needs_mask``)."""
    for cand in (512, 256, 128):
        if s_padded % cand == 0:
            return cand
    return 128


_LOG2E = 1.4426950408889634  # log2(e): folded into the q prescale
_LN2 = 0.6931471805599453    # 1/log2(e): the one dk fixup multiply


def _cparams():
    """(batch*heads, outer, inner-reduction) -> the first two grid dims
    are parallel, the innermost accumulates into scratch."""
    return _dimsem("parallel", "parallel", "arbitrary")


def _hash_keep(qpos, kpos, head, seed_lo, seed_hi, rate):
    """splitmix32-style integer mix over the GLOBAL (head, q, k) position so
    forward and backward regenerate bit-identical masks from the seed — no
    (s, s) mask tensor is ever materialized. 64 bits of PRNG-key entropy
    are folded in as two uint32 words (seed_lo, seed_hi) so per-call seeds
    do not birthday-collide at ~2^16 calls the way a single uint32 did.
    Pure jnp — usable both inside the Pallas kernels and on the unfused
    dispatch path (identical masks either way)."""
    x = (qpos * jnp.uint32(0x9E3779B9)) ^ (kpos * jnp.uint32(0x85EBCA6B))
    x = x ^ (seed_lo + head.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (seed_hi + (x >> 15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
    return x >= thresh  # keeps ~(1-rate) of positions


def _keep_mask(seed_ref, head, q0, k0, shape, rate):
    """Deterministic dropout keep-mask for a (TQ, TK) tile (kernel view)."""
    qpos = (q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)).astype(
        jnp.uint32)
    kpos = (k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)).astype(
        jnp.uint32)
    return _hash_keep(qpos, kpos, head, seed_ref[0, 0], seed_ref[0, 1],
                      rate)


def _score_mask(s, qt, kt, mask_row, sk, causal, window=None):
    """Validity mask for a score tile; every component is optional so the
    callers only pay for the masking a tile actually needs (``sk=None``
    skips the padding check, ``mask_row=None`` the user mask)."""
    tq, tk = s.shape
    kpos = kt * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = None
    if sk is not None:
        valid = kpos < sk
    if mask_row is not None:
        user = mask_row[None, :] != 0
        valid = user if valid is None else valid & user
    if causal:
        qpos = qt * tq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        tri = kpos <= qpos
        if window is not None:
            tri &= qpos - kpos < window
        valid = tri if valid is None else valid & tri
    return valid


# -- forward ----------------------------------------------------------------

def _needs_mask(causal, pad, qt, kt, bq, bk, nk, window=None):
    """Traced predicate: does tile (qt, kt) need any masking? Only tiles
    crossing the causal diagonal and (under k-padding) the last k tile do;
    interior tiles take a mask-free path with roughly half the VPU work —
    which is the bound that matters (measured on v5e: causal tile-skipping
    alone moved a seq-2048 fwd+bwd timing <5%, because the kernels are
    VPU-bound on mask construction + softmax, not MXU-bound)."""
    needs = None
    if causal:
        needs = (kt + 1) * bk - 1 > qt * bq
    if window is not None:      # the tile's far corner lies below the band
        needs |= (qt + 1) * bq - 1 - kt * bk >= window
    if pad:
        pad_t = kt == nk - 1
        needs = pad_t if needs is None else needs | pad_t
    return needs


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sk, causal, rate, has_mask, pad, window=None, nk=None,
                precision=None):
    i, qt, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    steps = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    step = kt
    if window is None:
        nk = steps
    else:       # the grid walks the band: this step's k tile (_band_first)
        kt = _band_first(qt, bq, bk, window) + step

    @pl.when(step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile(masked):
        def go():
            # q arrives PRE-SCALED by softmax_scale * log2e — folded
            # outside the kernel, so no per-score-element scale op;
            # scores are base-2 logits and every exp below is exp2
            q, k, v = q_ref[0], k_ref[0], v_ref[0]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
            if masked:
                valid = _score_mask(
                    s, qt, kt, mask_ref[0, 0, :] if has_mask else None,
                    sk if pad else None, causal, window)
                s = jnp.where(valid, s, _NEG)
            m_prev = m_ref[:, 0:1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_cur)
            p = jnp.exp2(s - m_cur)
            if masked:
                p = jnp.where(valid, p, 0.0)
            # (m, l) statistics stay fp32: l sums the fp32 tile BEFORE
            # the bf16 cast so the normalizer keeps full precision
            l_ref[:, 0:1] = l_ref[:, 0:1] * alpha + jnp.sum(p, -1,
                                                            keepdims=True)
            m_ref[:, 0:1] = m_cur
            # p is consumed only by the PV matmul from here on — cast to
            # v's dtype now so the dropout keep/scale ops below run on
            # the narrow tile too (precision loss bounded by the fp32
            # matmul accumulate)
            p = p.astype(v.dtype)
            if rate > 0.0:
                keep = _keep_mask(seed_ref, i, qt * bq, kt * bk,
                                  p.shape, rate)
                p = jnp.where(keep, p * p.dtype.type(1.0 / (1.0 - rate)),
                              p.dtype.type(0.0))
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
        return go

    if window is not None:
        # a step past the q tile's last row names no tile of the band
        in_band = kt * bk <= (qt + 1) * bq - 1
        if has_mask:
            pl.when(in_band)(tile(True))
        else:
            needs = _needs_mask(causal, pad, qt, kt, bq, bk, nk, window)
            pl.when(in_band & needs)(tile(True))
            pl.when(in_band & ~needs)(tile(False))
    elif has_mask:
        tile(True)()
    else:
        needs = _needs_mask(causal, pad, qt, kt, bq, bk, nk)
        if needs is None:
            tile(False)()
        else:
            jax.lax.cond(needs, tile(True), tile(False))

    @pl.when(step == steps - 1)
    def _():
        l = l_ref[:, 0:1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(l > 0, acc_ref[:] / safe, 0.0).astype(
            o_ref.dtype)
        # lse block is (1, 1, bq) indexed BY qt — each qt owns its own
        # output block, so qt can stay 'parallel' in dimension_semantics
        # without megacore cores clobbering each other's slices of a
        # shared full-row block (a (1,1,sq_p) block indexed (i,0,0) is
        # revisited across qt; on v4/v5p each TensorCore's private copy
        # would lose the other core's rows on write-back).
        # The stored value is the BASE-2 logsumexp (m2 + log2 l); the
        # backward kernels consume it as-is — no base conversion ever
        # happens on an s²-sized tile.
        lse_ref[0, 0, :] = jnp.where(
            l[:, 0] > 0, m_ref[:, 0] + jnp.log2(l[:, 0]), jnp.inf)


# -- backward: dq -----------------------------------------------------------

def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_acc, *, sk, causal, rate,
               has_mask, pad):
    i, qt, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(kt == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(masked):
        def go():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            lse_row = lse_ref[0, 0, pl.ds(qt * bq, bq)]
            delta_row = delta_ref[0, 0, pl.ds(qt * bq, bq)]
            # q pre-scaled; the kernel emits d(q*scale) and the caller
            # multiplies the final dq by softmax_scale once. s and
            # lse_row are both base-2, so exp2(s - lse2) is the
            # base-e probability and ds needs NO base fixup here (dL/ds
            # is taken w.r.t. the base-e logit, whose gradient path the
            # caller's single scale multiply completes).
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp2(s - lse_row[:, None])
            if masked:
                valid = _score_mask(
                    s, qt, kt, mask_ref[0, 0, :] if has_mask else None,
                    sk if pad else None, causal)
                p = jnp.where(valid, p, 0.0)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            if rate > 0.0:
                keep = _keep_mask(seed_ref, i, qt * bq, kt * bk,
                                  p.shape, rate)
                dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
            ds = p * (dp - delta_row[:, None])
            dq_acc[:] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return go

    if has_mask:
        tile(True)()
    else:
        needs = _needs_mask(causal, pad, qt, kt, bq, bk, nk)
        if needs is None:
            tile(False)()
        else:
            jax.lax.cond(needs, tile(True), tile(False))

    @pl.when(kt == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# -- backward: dk, dv -------------------------------------------------------

def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, sk, causal, rate, has_mask, pad):
    i, kt, qt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(qt == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(masked):
        def go():
            q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
            lse_row = lse_ref[0, 0, pl.ds(qt * bq, bq)]
            delta_row = delta_ref[0, 0, pl.ds(qt * bq, bq)]
            # q pre-scaled: dk = ds^T @ (scale*log2e*q); the caller
            # multiplies the FINAL dk tile by ln2 once (d-sized, not
            # s²-sized)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp2(s - lse_row[:, None])
            if masked:
                valid = _score_mask(
                    s, qt, kt, mask_ref[0, 0, :] if has_mask else None,
                    sk if pad else None, causal)
                p = jnp.where(valid, p, 0.0)
            # p feeds only the dv matmul past this point (ds re-derives
            # from the fp32 copy below) — narrow tile for keep/scale + MXU
            pd = do.dtype
            if rate > 0.0:
                keep = _keep_mask(seed_ref, i, qt * bq, kt * bk,
                                  p.shape, rate)
                p_drop = jnp.where(
                    keep, p.astype(pd) * pd.type(1.0 / (1.0 - rate)),
                    pd.type(0.0))
            else:
                p_drop = p.astype(pd)
            # dv += p_drop^T @ do
            dv_acc[:] += jax.lax.dot_general(
                p_drop, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            if rate > 0.0:
                dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
            ds = p * (dp - delta_row[:, None])
            dk_acc[:] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return go

    if has_mask:
        tile(True)()
    else:
        needs = _needs_mask(causal, pad, qt, kt, bq, bk,
                            pl.num_programs(1))
        if needs is None:
            tile(False)()
        else:
            jax.lax.cond(needs, tile(True), tile(False))

    @pl.when(qt == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# -- padding / call plumbing ------------------------------------------------

def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _qkv_spec(tile, d):
    return pl.BlockSpec((1, tile, d), lambda i, q, k: (i, q, 0),
                        memory_space=pltpu.VMEM)


def _prep(q, k, v, mask, b, h):
    """Flatten (b,h,s,d) -> (b*h,s,d), pad s to tile multiples.

    head_dim is padded only to a sublane multiple (8), NOT to 128: a
    block whose last dim equals the array dim is legal, and padding
    d=64 to 128 would double the QK/PV matmul FLOPs for nothing.
    """
    _, _, sq, d = q.shape
    sk = k.shape[2]
    sq_p = round_up_to_multiple(sq, 128)
    sk_p = round_up_to_multiple(sk, 128)
    d_p = round_up_to_multiple(d, 8)

    def flat(x, s_p):
        x = x.reshape(b * h, x.shape[2], d)
        return _pad_axis(_pad_axis(x, s_p, 1), d_p, 2)

    q3, k3, v3 = flat(q, sq_p), flat(k, sk_p), flat(v, sk_p)
    if mask is None:
        m3 = jnp.ones((b, 1, sk_p), jnp.int32)
    else:
        m3 = _pad_axis(mask.astype(jnp.int32).reshape(b, 1, sk), sk_p, 2)
    return q3, k3, v3, m3, sq_p, sk_p, d_p


def _prescale_q(q3, scale):
    """Fold softmax_scale into q (fp32 multiply, one rounding back to
    the storage dtype) so no kernel pays a per-score-element scale op.
    The SAME multiply also carries log2(e): the kernels' score tiles
    come out as base-2 logits for free."""
    return (q3.astype(jnp.float32)
            * jnp.float32(scale * _LOG2E)).astype(q3.dtype)


def _band_first(qt, bq, bk, window):
    """The first k tile that q tile ``qt``'s band touches: the tile of
    position ``qt * bq - (window - 1)``, or tile 0."""
    return jnp.maximum(qt * bq - (window - 1), 0) // bk


def _band_tiles(bq, bk, window, nk):
    """K tiles a q tile's band can touch, at most: its ``bq + window - 1``
    positions begin at a multiple of ``gcd(bq, bk)`` less ``window - 1``."""
    g = math.gcd(bq, bk)
    worst = max((off - (window - 1)) % bk
                for off in range(0, bk, g))     # the band's offset in a tile
    return min(nk, (worst + bq + window - 2) // bk + 1)


def _fwd_call(q, k, v, mask, *, causal, scale, rate, seed, interpret,
              window=None, precision=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    q3, k3, v3, m3, sq_p, sk_p, d_p = _prep(q, k, v, mask, b, h)
    q3 = _prescale_q(q3, scale)
    bq, bk = _block(sq_p), _block(sk_p)
    grid = (b * h, sq_p // bq, sk_p // bk)
    sd = jnp.asarray(seed, jnp.uint32).reshape(1, 2)
    kernel = functools.partial(_fwd_kernel, sk=sk, causal=causal, rate=rate,
                               has_mask=mask is not None, pad=sk != sk_p)
    at = lambda qt, kt: kt
    if precision is not None:
        kernel = functools.partial(kernel, precision=precision)
    if window is not None:
        nk = grid[2]
        grid = grid[:2] + (_band_tiles(bq, bk, window, nk),)
        kernel = functools.partial(kernel, window=window, nk=nk)
        # a step past the band's last tile (the q tile's own) does nothing:
        # it names that tile again, which is no new fetch
        at = lambda qt, kt: jnp.minimum(
            _band_first(qt, bq, bk, window) + kt, ((qt + 1) * bq - 1) // bk)
    kv_spec = pl.BlockSpec((1, bk, d_p),
                           lambda i, qt, kt: (i, at(qt, kt), 0),
                           memory_space=pltpu.VMEM)
    mask_spec = pl.BlockSpec((1, 1, bk),
                             lambda i, qt, kt: (i // h, 0, at(qt, kt)),
                             memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, 1, bq), lambda i, qt, kt: (i, 0, qt),
                            memory_space=pltpu.VMEM)
    with jax.named_scope("apex_flash_fwd"):
        o, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[_smem(), _qkv_spec(bq, d_p), kv_spec, kv_spec,
                      mask_spec],
            out_specs=(_qkv_spec(bq, d_p), lse_spec),
            out_shape=(jax.ShapeDtypeStruct((b * h, sq_p, d_p), q.dtype),
                       jax.ShapeDtypeStruct((b * h, 1, sq_p), jnp.float32)),
            scratch_shapes=[pltpu.VMEM((bq, d_p), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32)],
            compiler_params=_cparams(),
            interpret=pallas_interpret(interpret),
            name="apex_flash_fwd",
        )(sd, q3, k3, v3, m3)
    out = o[:, :sq, :d].reshape(b, h, sq, d)
    return out, lse  # lse stays padded (b*h, 1, sq_p)


def _bwd_call(q, k, v, mask, out, lse_p, do, *, causal, scale, rate, seed,
              interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    q3, k3, v3, m3, sq_p, sk_p, d_p = _prep(q, k, v, mask, b, h)
    q3 = _prescale_q(q3, scale)
    do3 = _pad_axis(_pad_axis(do.reshape(b * h, sq, d), sq_p, 1), d_p, 2)
    o3 = _pad_axis(_pad_axis(out.reshape(b * h, sq, d), sq_p, 1), d_p, 2)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    -1)[:, None, :]  # (bh, 1, sq_p) like lse
    sd = jnp.asarray(seed, jnp.uint32).reshape(1, 2)

    bq, bk = _block(sq_p), _block(sk_p)
    row_spec = pl.BlockSpec((1, 1, sq_p), lambda i, qt, kt: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bk, d_p), lambda i, qt, kt: (i, kt, 0),
                           memory_space=pltpu.VMEM)
    mask_spec = pl.BlockSpec((1, 1, bk),
                             lambda i, qt, kt: (i // h, 0, kt),
                             memory_space=pltpu.VMEM)
    with jax.named_scope("apex_flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, sk=sk, causal=causal, rate=rate,
                              has_mask=mask is not None, pad=sk != sk_p),
            grid=(b * h, sq_p // bq, sk_p // bk),
            in_specs=[_smem(), _qkv_spec(bq, d_p), kv_spec, kv_spec,
                      mask_spec, _qkv_spec(bq, d_p), row_spec, row_spec],
            out_specs=_qkv_spec(bq, d_p),
            out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d_p), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d_p), jnp.float32)],
            compiler_params=_cparams(),
            interpret=pallas_interpret(interpret),
            name="apex_flash_bwd_dq",
        )(sd, q3, k3, v3, m3, do3, lse_p, delta)

    # dkv: k outer / q inner — index maps swap roles
    q_spec2 = pl.BlockSpec((1, bq, d_p), lambda i, kt, qt: (i, qt, 0),
                           memory_space=pltpu.VMEM)
    kv_spec2 = pl.BlockSpec((1, bk, d_p), lambda i, kt, qt: (i, kt, 0),
                            memory_space=pltpu.VMEM)
    mask_spec2 = pl.BlockSpec((1, 1, bk),
                              lambda i, kt, qt: (i // h, 0, kt),
                              memory_space=pltpu.VMEM)
    row_spec2 = pl.BlockSpec((1, 1, sq_p), lambda i, kt, qt: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    with jax.named_scope("apex_flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, sk=sk, causal=causal, rate=rate,
                              has_mask=mask is not None, pad=sk != sk_p),
            grid=(b * h, sk_p // bk, sq_p // bq),
            in_specs=[_smem(), q_spec2, kv_spec2, kv_spec2, mask_spec2,
                      q_spec2, row_spec2, row_spec2],
            out_specs=(kv_spec2, kv_spec2),
            out_shape=(jax.ShapeDtypeStruct((b * h, sk_p, d_p), k.dtype),
                       jax.ShapeDtypeStruct((b * h, sk_p, d_p), v.dtype)),
            scratch_shapes=[pltpu.VMEM((bk, d_p), jnp.float32),
                            pltpu.VMEM((bk, d_p), jnp.float32)],
            compiler_params=_cparams(),
            interpret=pallas_interpret(interpret),
            name="apex_flash_bwd_dkv",
        )(sd, q3, k3, v3, m3, do3, lse_p, delta)

    # dq kernel produced d(scale*q); one fused XLA multiply finishes it
    dq = (dq[:, :sq, :d].astype(jnp.float32) * jnp.float32(scale)
          ).astype(q.dtype).reshape(b, h, sq, d)
    # the dkv kernel's dk = ds^T @ (scale*log2e*q) — one ln(2) multiply
    # on the final (s, d) tile undoes the log2e (the ONLY base-conversion
    # cost of the base-2 softmax; it fuses with the slice)
    dk = (dk[:, :sk, :d].astype(jnp.float32)
          * jnp.float32(_LN2)).astype(k.dtype).reshape(b, h, sk, d)
    dv = dv[:, :sk, :d].reshape(b, h, sk, d)
    return dq, dk, dv


# -- custom_vjp + public API ------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(cfg, q, k, v, mask, seed):
    causal, scale, rate, interpret = cfg
    out, _ = _fwd_call(q, k, v, mask, causal=causal, scale=scale, rate=rate,
                       seed=seed, interpret=interpret)
    return out


def _flash_fwd(cfg, q, k, v, mask, seed):
    causal, scale, rate, interpret = cfg
    out, lse_p = _fwd_call(q, k, v, mask, causal=causal, scale=scale,
                           rate=rate, seed=seed, interpret=interpret)
    return out, (q, k, v, mask, out, lse_p, seed)


def _flash_bwd(cfg, res, do):
    causal, scale, rate, interpret = cfg
    q, k, v, mask, out, lse_p, seed = res
    dq, dk, dv = _bwd_call(q, k, v, mask, out, lse_p, do, causal=causal,
                           scale=scale, rate=rate, seed=seed,
                           interpret=interpret)
    return dq, dk, dv, None, None


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# Measured crossover on TPU v5e (b=16, h=16, d=64, fwd+bwd): at padded
# seq <= 256 XLA's single batched einsum+softmax beats the tiled kernel
# (the kernel degenerates to b*h sequential one-tile programs), while at
# >= 512 the kernel wins and at 2048 it is ~2x faster. Dispatch on size
# so every caller gets the better path at its shape.
_UNFUSED_MAX_SEQ = 256


def _unfused_attention(q, k, v, mask, seed, *, causal, scale, rate,
                       window=None, precision=None):
    """Mathematically-identical XLA path for short sequences.

    Same masking convention (fully-masked rows return 0) and the SAME
    ``_hash_keep`` dropout mask as the kernels, so dispatch never changes
    training randomness semantics; autodiff replays the mask bit-exactly
    in the backward because the hash is deterministic in its inputs.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=precision,
                   preferred_element_type=jnp.float32) * scale
    if mask is None:
        valid = jnp.ones((1, 1, 1, sk), bool)
    else:
        valid = (mask[:, None, None, :] != 0)
    if causal:
        tri = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None])
        if window is not None:
            tri &= jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :] < window
        valid = valid & tri[None, None]
    s = jnp.where(valid, s, _NEG)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    p = jnp.where(l > 0, p / jnp.where(l > 0, l, 1.0), 0.0)
    if rate > 0.0:
        # global (bh, q, k) positions — identical mask to the kernel's
        bh = jnp.arange(b * h, dtype=jnp.uint32).reshape(b, h, 1, 1)
        qpos = jnp.arange(sq, dtype=jnp.uint32).reshape(1, 1, sq, 1)
        kpos = jnp.arange(sk, dtype=jnp.uint32).reshape(1, 1, 1, sk)
        keep = _hash_keep(qpos, kpos, bh, seed[0], seed[1], rate)
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      precision=precision,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None, *,
                    causal: bool = False,
                    softmax_scale: Optional[float] = None,
                    dropout_rate: float = 0.0,
                    dropout_rng: Optional[jax.Array] = None,
                    use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None,
                    precision: Optional[jax.lax.Precision] = None
                    ) -> jax.Array:
    """Fused scaled-dot-product attention.

    Args:
      q, k, v: (batch, heads, seq, head_dim).
      mask: optional (batch, s_k) with 1 = attend (BERT convention).
      causal: apply the implicit upper-triangular mask.
      softmax_scale: defaults to 1/sqrt(head_dim).
      dropout_rate: attention-probability dropout (after normalization,
        reference semantics); active only when ``dropout_rng`` is given.
      dropout_rng: PRNG key; 64 bits folded into the dropout-hash seed.
      use_kernel: force the Pallas kernel (True) or the XLA path (False);
        None auto-dispatches on sequence length (kernel when the padded
        seq exceeds ``_UNFUSED_MAX_SEQ`` — the measured v5e crossover).
      window: with ``causal``, query ``i`` attends key ``j`` iff ``0 <= i -
        j < window`` (the token itself counts). Forward only: the kernel
        visits the band's k tiles and no others; there is no backward.
      precision: of the two products (the module's docstring). Forward
        only.

    Returns (batch, heads, seq, head_dim) in q's dtype.
    """
    if softmax_scale is None:
        softmax_scale = 1.0 / (q.shape[-1] ** 0.5)
    rate = float(dropout_rate) if dropout_rng is not None else 0.0
    if rate > 0.0:
        seed = jax.random.bits(dropout_rng, (2,), jnp.uint32)
    else:
        seed = jnp.zeros((2,), jnp.uint32)
    if use_kernel is None:
        use_kernel = max(q.shape[2], k.shape[2]) > _UNFUSED_MAX_SEQ
    if window is not None and (not causal or window < 1
                               or q.shape[2] != k.shape[2]):
        raise ValueError(
            f"a window ({window}) is a band below the diagonal of a "
            "square causal attention")
    if window is not None or precision is not None:     # forward only
        window = None if window is None else int(window)
        if not use_kernel:
            return _unfused_attention(
                q, k, v, mask, seed, causal=bool(causal),
                scale=float(softmax_scale), rate=rate, window=window,
                precision=precision)
        return _fwd_call(q, k, v, mask, causal=bool(causal),
                         scale=float(softmax_scale), rate=rate, seed=seed,
                         interpret=interpret, window=window,
                         precision=precision)[0]
    if not use_kernel:
        return _unfused_attention(q, k, v, mask, seed, causal=bool(causal),
                                  scale=float(softmax_scale), rate=rate)
    cfg = (bool(causal), float(softmax_scale), rate, interpret)
    return _flash_core(cfg, q, k, v, mask, seed)
