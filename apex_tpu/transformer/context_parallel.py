"""Context parallelism — ring attention over the ``context`` mesh axis.

Reference scope: the reference's long-sequence story is fused/flash
attention on one GPU plus Megatron sequence parallelism; it has no ring
attention. SURVEY §2c therefore lists CP as not-required — but
``parallel_state`` reserves a first-class ``context`` axis, and on TPU
ring attention is the natural long-context design (Liu et al., "Ring
Attention with Blockwise Transformers"; the public JAX implementations
in PAPERS.md/SNIPPETS.md follow the same shape): sequence-shard q/k/v,
rotate k/v shards around the ring with ``lax.ppermute`` while each rank
accumulates its queries' attention online, so no rank ever materializes
the full (s, s) score matrix OR the full k/v sequence.

Design:

- one ``lax.scan`` over the ``cp`` ring steps; the carry is the flash
  recurrence state (running max, running sum, output accumulator) plus
  the in-flight k/v block — compute on the current block overlaps the
  ppermute of the next by XLA's latency-hiding scheduler, the TPU
  analogue of the reference kernels' compute/NCCL overlap;
- blockwise math is the SAME fp32 online-softmax recurrence as the flash
  kernel (fully-masked rows return 0, additive -1e30 masking), so CP=1
  reproduces ``flash_attention`` numerics;
- causal masking uses GLOBAL positions derived from ``axis_index``, so
  the triangle is exact across shards;
- backward is plain autodiff: the transpose of a ppermute rotation is
  the reverse rotation, and ``jax.checkpoint`` around the per-step block
  keeps live memory at one block per step (blockwise-transformer remat).

Call inside ``parallel_state.shard_map`` with q/k/v (b, h, s_local, d)
sharded along seq over ``CONTEXT_AXIS`` (mask (b, s_local) likewise).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer import parallel_state as ps

_NEG = -1e30


def _ring_perm(cp: int):
    # send to the NEXT rank: after j steps, rank i holds block (i - j) % cp
    return [(i, (i + 1) % cp) for i in range(cp)]


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array] = None, *,
                   causal: bool = False,
                   softmax_scale: Optional[float] = None,
                   axis_name: str = ps.CONTEXT_AXIS,
                   checkpoint_blocks: bool = True) -> jax.Array:
    """Exact attention over a context-sharded sequence.

    Args:
      q, k, v: (b, h, s_local, d) — the rank's sequence shard.
      mask: optional (b, s_local) key-padding mask (1 = attend).
      causal: global upper-triangular masking.
      axis_name: the mesh axis the sequence is sharded over.

    Returns (b, h, s_local, d) in q's dtype — the rank's output shard.
    """
    if softmax_scale is None:
        softmax_scale = 1.0 / (q.shape[-1] ** 0.5)
    cp = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    perm = _ring_perm(cp)

    q32 = q.astype(jnp.float32)
    q_pos = rank * s_loc + jnp.arange(s_loc)          # global q positions

    def block(carry_qstate, kv_block, src_rank):
        """One flash-recurrence update against the k/v block that
        originated on ``src_rank``."""
        m_run, l_run, acc = carry_qstate
        k_blk, v_blk, kmask_blk = kv_block
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_blk.astype(jnp.float32)) * softmax_scale
        valid = None
        if kmask_blk is not None:
            valid = (kmask_blk[:, None, None, :] != 0)
        if causal:
            k_pos = src_rank * s_loc + jnp.arange(s_loc)
            tri = (k_pos[None, None, None, :]
                   <= q_pos[None, None, :, None])
            valid = tri if valid is None else (valid & tri)
        if valid is None:
            valid = jnp.ones(s.shape, bool)
        s = jnp.where(valid, s, _NEG)
        m_cur = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_cur)
        p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
        l_run = l_run * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return m_cur, l_run, acc

    if checkpoint_blocks:
        block = jax.checkpoint(block)

    # the mask rides the ring only when one exists (causal needs none)
    mask_loc = None if mask is None else mask.astype(jnp.int32)

    def step(carry, j):
        qstate, k_cur, v_cur, km_cur = carry
        src = (rank - j) % cp                 # who this block belongs to
        qstate = block(qstate, (k_cur, v_cur, km_cur), src)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        m_nxt = None if km_cur is None else \
            lax.ppermute(km_cur, axis_name, perm)
        return (qstate, k_nxt, v_nxt, m_nxt), None

    m0 = jnp.full((b, h, s_loc, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    # cp-1 rotate-and-consume steps, then the final block OUTSIDE the
    # scan — rotating after the last consume would send a full k/v/mask
    # round over ICI just to discard it
    carry = ((m0, l0, acc0), k, v, mask_loc)
    if cp > 1:
        carry, _ = lax.scan(step, carry, jnp.arange(cp - 1))
    qstate, k_last, v_last, km_last = carry
    qstate = block(qstate, (k_last, v_last, km_last),
                   (rank - (cp - 1)) % cp)
    _, l_run, acc = qstate
    out = jnp.where(l_run > 0, acc / jnp.where(l_run > 0, l_run, 1.0), 0.0)
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      mask: Optional[jax.Array] = None, *,
                      causal: bool = False,
                      softmax_scale: Optional[float] = None,
                      axis_name: str = ps.CONTEXT_AXIS,
                      attention_fn=None) -> jax.Array:
    """Ulysses-style sequence parallelism (DeepSpeed-Ulysses; see
    PAPERS.md): two ``all_to_all``s swap the sharded dimension so each
    rank runs EXACT attention over the FULL sequence for ``h/cp`` heads,
    then swap back. The alternative long-context strategy to
    :func:`ring_attention` — comm is exactly TWO all-to-alls per call
    (q/k/v ride one stacked collective in, the output one back; O(1)
    collectives vs the ring's cp-1 rotations of k/v), at the cost of
    requiring ``heads % cp == 0``.

    Args:
      q, k, v: (b, h, s_local, d) — the rank's sequence shard along the
        ``context`` axis (the same activation contract as ring).
      mask: optional (b, s_local) key-validity shard (1 = attend); it is
        all-gathered to the full sequence (tiny next to activations).
      attention_fn: the full-sequence attention to run per head group;
        defaults to :func:`...functional.flash_attention.flash_attention`
        (so the Pallas kernel serves long sequences, the XLA path short
        ones — the usual dispatch).

    Returns (b, h, s_local, d) in q's dtype.
    """
    cp = lax.axis_size(axis_name)
    b, h, s_loc, d = q.shape
    if h % cp:
        raise ValueError(
            f"ulysses_attention needs heads % cp == 0, got {h} % {cp}")
    if attention_fn is None:
        from apex_tpu.transformer.functional.flash_attention import (
            flash_attention,
        )
        attention_fn = flash_attention

    # ONE stacked all-to-all for q/k/v: (3, b, h, s/cp, d) with head
    # shards scattering over ranks while the sequence gathers
    qkv = lax.all_to_all(jnp.stack([q, k, v]), axis_name, split_axis=2,
                         concat_axis=3, tiled=True)
    qf, kf, vf = qkv[0], qkv[1], qkv[2]
    full_mask = None if mask is None else \
        lax.all_gather(mask, axis_name, axis=1, tiled=True)
    out = attention_fn(qf, kf, vf, full_mask, causal=causal,
                       softmax_scale=softmax_scale)
    # inverse swap: heads gather back, the sequence re-shards
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True).astype(q.dtype)
