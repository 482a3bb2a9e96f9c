"""Fused multi-head attention — flash-attention Pallas kernels.

Reference: ``apex/contrib/csrc/multihead_attn/*`` (fused QKV-softmax-
dropout-PV fwd/bwd, ~8k CUDA LoC) and ``apex/contrib/csrc/fmha/*``
(short-seqlen fused MHA) — SURVEY.md §2b calls this the largest single
kernel work item. The first is subsumed by one seqlen-generic flash-style
kernel pair; ``fmha``'s own case, bidirectional attention over 128 or 256
positions, is a whole-sequence pair of its own further down
(``apex_fmha_fwd`` / ``apex_fmha_bwd``, "short sequences"), which
``flash_attention_packed`` runs on a fused q / k / v projection as it lies.
The tiled pair:

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


# -- short sequences: the whole sequence is one tile (fmha) ------------------
#
# At a sequence of 128 or 256 the tiled kernels above degenerate to one
# program a (batch, head) pair with a single k step each (24,576 programs a
# BERT-Large forward at b64), and XLA's own path writes the scores and the
# probabilities to HBM and keeps them for the backward. Here the whole
# sequence is the tile: no online softmax, no k loop, no scratch, and the
# backward is ONE program where the tiled kernels need two, because nothing
# accumulates across grid steps.
#
# The operand is a fused projection as it lies, ``(b, s, 3 * h * d)``: q, k
# and v are thirds of ONE array that three BlockSpecs index in place, the
# context comes out ``(b, s, h * d)`` and the gradient as ONE array of the
# operand's shape, so nothing is transposed, sliced or concatenated on
# either side of a kernel. (On ``(b, h, s, d)`` operands the same bodies
# lost to XLA, alone and inside BERT's step: rows of 64 columns are half a
# lane tile, and the layout copies cost what the kernels saved; PERF.md,
# PR 41. ``flash_attention`` therefore never takes this pair by itself.)
#
# A grid step takes a few batch rows. A row's heads are taken a lane block
# (128 columns) at a time; where a block holds two heads of 64 each is
# picked by zeroing the other's columns of k (and of do) in front of the
# product, and each product's output keeps its own head's columns: every
# product runs at the block's full width, which is what a [128, 64] operand
# costs the MXU anyway, and no lane is ever shifted. The heads of a row are
# independent chains laid side by side, which is what fills the units.

_FMHA_HEAD_DIMS = (64, 128)     # the head widths the pair is built for
_FMHA_BLOCK_BYTES = 512 * 1024  # of q's block in VMEM


def _takes_fmha(s, h, d):
    """Is a packed projection a shape the pair is built for: one tile of 128
    or 256 positions, heads of 64 or 128 that fill whole lane blocks."""
    return (s in (128, 256) and d in _FMHA_HEAD_DIMS
            and (h * d) % 128 == 0)


def _fmha_group(rows, s, cols, itemsize):
    """Batch rows a grid step takes: as many as keep q's block at 512 KiB
    (2 rows of BERT-Large's 16 x 64 columns at s128 in bfloat16), so that
    the backward's blocks (q, k, v, do and the three gradients, double
    buffered) stay at half the 16 MiB a kernel may use; one row where a
    row alone is more. The last grid step may be ragged."""
    return max(1, min(rows, _FMHA_BLOCK_BYTES // (s * cols * itemsize)))


def _fmha_rows(g, heads, row):
    """Run ``row(j)`` for the ``g`` rows of a grid step. One head's work is
    a chain (scores, max, exp, sum, product) that leaves the units idle in
    turn, and only independent heads fill them: rows of fewer than eight
    heads are laid side by side until eight are (the loop's own ``unroll``
    is all or nothing in a kernel)."""
    side = math.gcd(g, max(1, 8 // heads))      # 8 // heads: a power of 2

    def rows(i, carry):
        for r in range(side):
            row(i * side + r)
        return carry

    jax.lax.fori_loop(0, g // side, rows, 0)


def _fmha_picks(d):
    """For each head of a lane block, which columns are its own: ``None``
    where the block is one head."""
    if d == 128:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return [(lane >= t * d) & (lane < (t + 1) * d) for t in range(128 // d)]


def _fmha_only(pick, x):
    return x if pick is None else jnp.where(pick, x, jnp.zeros_like(x))


def _fmha_merge(pick, new, old):
    return new if pick is None or old is None else jnp.where(pick, new, old)


def _fmha_key_bias(bias_ref, j, s):
    """The bias of row ``j``'s keys, keys DOWN the sublanes and the same in
    every lane, ``(s_k, s_q)``: one transpose a row, shared by its heads."""
    return jnp.broadcast_to(bias_ref[j], (s, s)).T


def _fmha_probs(q, k, bias, scale2, shift):
    """``exp2(scale2 * k.qT + bias - shift)`` of one head, float32, keys
    down the sublanes and queries along the lanes, so that a query's maximum
    and sum are elementwise work over vregs and a statistic of the queries
    is a ROW, as the log-sum-exp is stored; with the column maxima where
    ``shift`` is None (the forward). Base 2: ``log2(e)`` rides in the one
    multiply that applies the softmax scale; the key mask is one add."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale2
    s = s + bias
    m = jnp.max(s, axis=0, keepdims=True) if shift is None else shift
    return jnp.exp2(s - m), m


def _fmha_keep(seed_ref, head, shape, rate):
    """The dropout keep mask of (head, q, k) for a tile that holds keys down
    its sublanes: ``_keep_mask``'s hash at the same positions."""
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.uint32)
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1).astype(jnp.uint32)
    return _hash_keep(qpos, kpos, head, seed_ref[0, 0], seed_ref[0, 1], rate)


def _fmha_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                     lse_ref, *, d, scale2, rate):
    g, s, cols = q_ref.shape
    heads = cols // d
    first = pl.program_id(0) * g
    picks = _fmha_picks(d)

    def row(j):
        bias = _fmha_key_bias(bias_ref, j, s)
        # every query sees the same keys: a query with none is a row with
        # none, whose exp2(0) terms below mean nothing
        live = jnp.max(bias_ref[j], axis=-1, keepdims=True) > _NEG / 2
        for c in range(0, cols, 128):
            at = (j, slice(None), pl.ds(c, 128))
            q, k, v = q_ref[at], k_ref[at], v_ref[at]
            out = None
            for t, pick in enumerate(picks):
                head = c // d + t
                p, m = _fmha_probs(q, _fmha_only(pick, k), bias, scale2,
                                   None)
                l = jnp.sum(p, axis=0, keepdims=True)
                # base 2, as the backward reads it
                lse_ref[j, head, :] = jnp.where(
                    live, m + jnp.log2(l), jnp.inf)[0]
                # normalized in float32, then for the MXU only
                p = (p * (1.0 / l)).astype(v.dtype)
                if rate > 0.0:
                    keep = _fmha_keep(seed_ref, (first + j) * heads + head,
                                      p.shape, rate)
                    p = jnp.where(keep, p * p.dtype.type(1.0 / (1.0 - rate)),
                                  p.dtype.type(0.0))
                out = _fmha_merge(pick, jax.lax.dot_general(
                    p, v, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), out)
            o_ref[at] = jnp.where(live, out, 0.0).astype(o_ref.dtype)

    _fmha_rows(g, heads, row)


def _fmha_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                     lse_ref, dqkv_ref, *, d, scale, rate):
    g, s, cols = q_ref.shape
    heads = cols // d
    first = pl.program_id(0) * g
    picks = _fmha_picks(d)

    def row(j):
        bias = _fmha_key_bias(bias_ref, j, s)
        for c in range(0, cols, 128):
            at = (j, slice(None), pl.ds(c, 128))
            q, k, v, do = q_ref[at], k_ref[at], v_ref[at], do_ref[at]
            dq = dk = dv = None
            for t, pick in enumerate(picks):
                head = c // d + t
                # an unseen row's lse is +inf: p, and every gradient, is 0
                p, _ = _fmha_probs(q, _fmha_only(pick, k), bias,
                                   scale * _LOG2E,
                                   lse_ref[j, head, :][None, :])
                dp = jax.lax.dot_general(
                    v, _fmha_only(pick, do), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                p_drop = p
                if rate > 0.0:
                    keep = _fmha_keep(seed_ref, (first + j) * heads + head,
                                      p.shape, rate)
                    dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
                    p_drop = jnp.where(keep, p / (1.0 - rate), 0.0)
                # rowsum(do * o) is sum_k p * dp, all of it in this tile and
                # in float32: the (rounded) output is not read again
                delta = jnp.sum(p * dp, axis=0, keepdims=True)
                # d / d(raw score): the softmax scale rides in ds, so dq
                # and dk need no fixup
                ds = (p * (dp - delta) * scale).astype(q.dtype)
                dv = _fmha_merge(pick, jax.lax.dot_general(
                    p_drop.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), dv)
                dk = _fmha_merge(pick, jax.lax.dot_general(
                    ds, q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), dk)
                dq = _fmha_merge(pick, jax.lax.dot_general(
                    ds, k, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32), dq)
            # the thirds of ONE array, as q, k and v are
            for third, grad in enumerate((dq, dk, dv)):
                dqkv_ref[j, :, pl.ds(third * cols + c, 128)] = grad.astype(
                    dqkv_ref.dtype)

    _fmha_rows(g, heads, row)


def _fmha_operands(qkv, mask):
    """``(b, s, 3, h, d)`` as the kernels take it: the array ``(b, s, 3 * h
    * d)``, the key mask as ONE additive float32 row a batch row, the grid
    and the BlockSpecs (q's / k's / v's thirds, a ``cols``-wide tile, the
    whole width, ``n`` rows of ``s``)."""
    b, s, _, h, d = qkv.shape
    cols = h * d
    x = qkv.reshape(b, s, 3 * cols)
    bias = jnp.zeros((b, 1, s), jnp.float32) if mask is None else jnp.where(
        mask != 0, 0.0, _NEG).astype(jnp.float32).reshape(b, 1, s)
    g = _fmha_group(b, s, cols, qkv.dtype.itemsize)
    block = lambda shape, at=0: pl.BlockSpec(           # noqa: E731
        (g,) + shape, lambda i: (i, 0, at), memory_space=pltpu.VMEM)
    thirds = [block((s, cols), t) for t in range(3)]
    return (x, bias, (pl.cdiv(b, g),), thirds, block((s, cols)),
            block((s, 3 * cols)), lambda n: block((n, s)))


# Both launches are ``jit``s of their own: a model's layers call them with
# one static configuration and one set of shapes, so the kernel is traced
# and lowered ONCE a program and not once a layer (48 lowerings of a body
# unrolled over 16 heads cost BERT-Large's step 11 s of set-up, compile
# cache or not: PERF.md, PR 41).

@functools.partial(jax.jit, static_argnums=(0,))
def _fmha_forward(cfg, qkv, mask, seed):
    scale, rate, interpret = cfg
    b, s, _, h, d = qkv.shape
    x, bias, grid, thirds, tile, _, rows = _fmha_operands(qkv, mask)
    with jax.named_scope("apex_fmha_fwd"):
        return pl.pallas_call(
            functools.partial(_fmha_fwd_kernel, d=d, scale2=scale * _LOG2E,
                              rate=rate),
            grid=grid,
            in_specs=[_smem(), *thirds, rows(1)],
            out_specs=(tile, rows(h)),
            out_shape=(jax.ShapeDtypeStruct((b, s, h * d), qkv.dtype),
                       jax.ShapeDtypeStruct((b, h, s), jnp.float32)),
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_fmha_fwd",
        )(jnp.asarray(seed, jnp.uint32).reshape(1, 2), x, x, x, bias)


@functools.partial(jax.jit, static_argnums=(0,))
def _fmha_backward(cfg, qkv, mask, lse, seed, do):
    scale, rate, interpret = cfg
    d = qkv.shape[4]
    x, bias, grid, thirds, tile, whole, rows = _fmha_operands(qkv, mask)
    with jax.named_scope("apex_fmha_bwd"):
        dqkv = pl.pallas_call(
            functools.partial(_fmha_bwd_kernel, d=d, scale=scale, rate=rate),
            grid=grid,
            in_specs=[_smem(), *thirds, rows(1), tile, rows(lse.shape[1])],
            out_specs=whole,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_dimsem("parallel"),
            interpret=pallas_interpret(interpret),
            name="apex_fmha_bwd",
        )(jnp.asarray(seed, jnp.uint32).reshape(1, 2), x, x, x, bias, do,
          lse)
    return dqkv.reshape(qkv.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fmha_core(cfg, qkv, mask, seed):
    return _fmha_forward(cfg, qkv, mask, seed)[0]


def _fmha_fwd(cfg, qkv, mask, seed):
    out, lse = _fmha_forward(cfg, qkv, mask, seed)
    return out, (qkv, mask, lse, seed)


def _fmha_bwd(cfg, res, do):
    qkv, mask, lse, seed = res
    return _fmha_backward(cfg, qkv, mask, lse, seed, do), None, None


_fmha_core.defvjp(_fmha_fwd, _fmha_bwd)


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


# Two paths of ``flash_attention`` itself, and what decides between them
# when ``use_kernel`` is None: above a padded sequence of 256 the tiled
# kernels (``_flash_core``), at 256 and under plain XLA
# (``_unfused_attention``). Measured crossover on TPU v5e (b=16, h=16, d=64,
# fwd+bwd): at 512 the kernel wins and at 2048 it is ~2x faster, while at
# 256 and under it degenerates to b*h sequential one-tile programs and loses
# to XLA by 5x (PR 41: 2.35 ms against 0.46 at b64 h16 s128), and XLA's one
# batched einsum + softmax is the right program for serving's short causal
# buckets. The third path is not reached from here: the whole-sequence pair
# (``_fmha_core``) takes a packed projection, through
# ``flash_attention_packed``; on ``(b, h, s, d)`` operands it lost to XLA
# too (0.78 ms against 0.46 alone, 141.1 ms against 135.1 a BERT step).
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


def _dropout_seed(dropout_rate, dropout_rng):
    """(rate, the two uint32 words of the dropout hash's seed): dropout is
    active only when a key is given."""
    rate = float(dropout_rate) if dropout_rng is not None else 0.0
    if rate > 0.0:
        return rate, jax.random.bits(dropout_rng, (2,), jnp.uint32)
    return rate, jnp.zeros((2,), jnp.uint32)


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
      use_kernel: force the tiled Pallas kernels (True) or the XLA path
        (False); None auto-dispatches on sequence length (the kernels when
        the padded seq exceeds ``_UNFUSED_MAX_SEQ`` — the measured v5e
        crossover). A model that holds a packed q / k / v projection calls
        ``flash_attention_packed`` instead, which at a sequence of 128 or
        256 runs a third path, the whole-sequence pair ``apex_fmha_fwd`` /
        ``apex_fmha_bwd`` (the comment at ``_UNFUSED_MAX_SEQ``).
      window: with ``causal``, query ``i`` attends key ``j`` iff ``0 <= i -
        j < window`` (the token itself counts). Forward only: the kernel
        visits the band's k tiles and no others; there is no backward.
      precision: of the two products (the module's docstring). Forward
        only.

    Returns (batch, heads, seq, head_dim) in q's dtype.
    """
    if softmax_scale is None:
        softmax_scale = 1.0 / (q.shape[-1] ** 0.5)
    rate, seed = _dropout_seed(dropout_rate, dropout_rng)
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


def flash_attention_packed(qkv: jax.Array, mask: Optional[jax.Array] = None,
                           *, softmax_scale: Optional[float] = None,
                           dropout_rate: float = 0.0,
                           dropout_rng: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Bidirectional self-attention over a packed projection.

    ``qkv`` is ``(batch, seq, 3, heads, head_dim)``, a fused q / k / v
    projection's output as it lies in memory; returns ``(batch, seq, heads
    * head_dim)``, what the output projection reads: ``flash_attention``
    over the three transposed slices, and the same values. At a sequence of
    128 or 256 and heads of 64 or 128 that fill whole lane blocks
    (``_takes_fmha``) the whole-sequence kernel pair ``apex_fmha_fwd`` /
    ``apex_fmha_bwd`` indexes the thirds of ``qkv`` in place, and no
    score-shaped array reaches HBM, forward or backward; every other shape
    is transposed and handed to ``flash_attention``, which picks the tiled
    kernels or XLA as for any caller. ``mask``, ``softmax_scale`` and the
    dropout arguments are ``flash_attention``'s.
    """
    b, s, three, h, d = qkv.shape
    assert three == 3, qkv.shape
    if _takes_fmha(s, h, d):
        if softmax_scale is None:
            softmax_scale = 1.0 / (d ** 0.5)
        rate, seed = _dropout_seed(dropout_rate, dropout_rng)
        return _fmha_core((float(softmax_scale), rate, interpret), qkv, mask,
                          seed)
    q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
    ctx = flash_attention(q, k, v, mask, softmax_scale=softmax_scale,
                          dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                          interpret=interpret)
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)
