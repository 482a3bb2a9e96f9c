"""Single-query attention straight out of the paged KV pool (Pallas).

The decode step's attention reads only what a slot has mapped: the kernel
walks the slot's block-table row up to ``pos``, brings those pages of the
stacked pool from HBM to VMEM by DMA (double-buffered, several pages a
step) and runs an online softmax over them in float32. The pool is never
gathered, transposed or upcast in HBM, and it is only read: the new
token's K/V row comes in as an operand standing at position ``pos``, and
the caller writes it into the (donated) pool afterwards.

A page is ``(page_size, kv_heads * head_dim)``: rows of all local K/V heads
side by side, so one page is one contiguous run of whole (sublane, 128-lane)
tiles and the MXU does the per-head work. Scores are ``Q_blk @ K^T`` with
``Q_blk`` ``(heads, kv_heads * head_dim)`` holding query head ``h`` in the
columns of ITS K/V head (``h // (heads / kv_heads)``: its own when the counts
are equal, one block shared by ``heads / kv_heads`` query rows when K/V heads
are fewer) and zeros elsewhere; the context is the matching blocks of ``P @
V``.

Placement invariance: a slot's output depends on the pages its table names
below ``pos`` and on nothing else. Pages past ``cdiv(pos, page_size)`` are
never fetched, so NULL table entries are never dereferenced, and rows of
the last page at or past ``pos`` are masked in the scores AND zeroed in the
values, so what they hold (stale rows, NaN) cannot reach the output.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

# cache positions brought to VMEM per DMA step (one buffer of K and one of
# V; two of each are resident)
_CHUNK_POSITIONS = 128
# head rows of the score matrix are padded to whole bfloat16 sublane tiles
_ROW_TILE = 16

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot_f32(a, b, dims):
    """``a`` (float32) contracted with ``b`` (a chunk in the pool's dtype),
    products exact and accumulation in float32. A bfloat16 chunk goes to
    the MXU as it is, against the three bfloat16 pieces whose sum is ``a``
    (stacked, so the chunk is pushed once); a float32 chunk takes the
    MXU's full-precision passes."""
    if b.dtype != jnp.bfloat16:
        return lax.dot_general(a, b.astype(jnp.float32), dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    pieces = []
    for _ in range(3):
        piece = a.astype(jnp.bfloat16)
        pieces.append(piece)
        a = a - piece.astype(jnp.float32)
    rows = pieces[0].shape[0]
    out = lax.dot_general(jnp.concatenate(pieces, axis=0), b, dims,
                          preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _decode_kernel(bt_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref,
                   k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *, heads,
                   kv_heads, page_size):
    span, width = kbuf.shape[1:]
    hd = width // kv_heads
    per = heads // kv_heads             # query heads that share a K/V head
    chunk = span // page_size
    rows = -(-heads // _ROW_TILE) * _ROW_TILE
    slot = pl.program_id(0)
    pos = pos_ref[slot]
    layer = layer_ref[0]
    # pages holding rows below pos: the new row itself is an operand
    n_pages = (pos + page_size - 1) // page_size
    n_chunks = (n_pages + chunk - 1) // chunk

    def dma(c, buf, fn):
        first = c * chunk

        def page(j, _):
            src = bt_ref[slot, first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            fn(pltpu.make_async_copy(k_hbm.at[layer, src],
                                     kbuf.at[buf, dst], sem.at[0, buf]))
            fn(pltpu.make_async_copy(v_hbm.at[layer, src],
                                     vbuf.at[buf, dst], sem.at[1, buf]))
            return 0

        lax.fori_loop(0, jnp.minimum(chunk, n_pages - first), page, 0)

    @pl.when(n_chunks > 0)
    def _():
        dma(0, 0, lambda d: d.start())

    if per == 1:
        # head h's query in columns [h * hd, (h + 1) * hd) of row h
        own = (lax.broadcasted_iota(jnp.int32, (rows, width), 1) // hd
               == lax.broadcasted_iota(jnp.int32, (rows, width), 0))
        q_blk = jnp.where(own, q_ref[0].astype(jnp.float32), 0.0)
    else:
        # fewer K/V heads: q_ref holds (heads, hd), row h's query goes to the
        # columns of K/V head h // per
        own = (lax.broadcasted_iota(jnp.int32, (rows, width), 1) // hd
               == lax.broadcasted_iota(jnp.int32, (rows, width), 0) // per)
        q_rows = q_ref[0].astype(jnp.float32)
        if rows > heads:
            q_rows = jnp.concatenate(
                [q_rows, jnp.zeros((rows - heads, hd), jnp.float32)], 0)
        q_blk = jnp.where(own, jnp.concatenate([q_rows] * kv_heads, 1), 0.0)
    norm = math.sqrt(hd)
    neg = jnp.finfo(jnp.float32).min
    at_lane = lax.broadcasted_iota(jnp.int32, (rows, span), 1)
    at_row = lax.broadcasted_iota(jnp.int32, (span, 1), 0)

    def step(c, carry):
        m, l, acc = carry
        buf = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            dma(c + 1, 1 - buf, lambda d: d.start())

        dma(c, buf, lambda d: d.wait())
        left = pos - c * span                   # positions below pos here
        valid = at_lane < left
        s = jnp.where(valid, _dot_f32(q_blk, kbuf[buf], _NT) / norm, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)      # (rows, span)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        v = vbuf[buf]
        v = jnp.where(at_row < left, v, jnp.zeros_like(v))
        return m_new, l, alpha * acc + _dot_f32(p, v, _NN)

    m, l, acc = lax.fori_loop(
        0, n_chunks, step,
        (jnp.full((rows, 1), neg, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, width), jnp.float32)))
    # the new token's own row, at position pos
    s_new = jnp.sum(q_blk * kn_ref[0].astype(jnp.float32), axis=1,
                    keepdims=True) / norm
    m_new = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_new)
    p_new = jnp.exp(s_new - m_new)
    acc = alpha * acc + p_new * vn_ref[0].astype(jnp.float32)
    ctx = jnp.where(own, acc / (alpha * l + p_new), 0.0)
    if per == 1:
        o_ref[0] = jnp.sum(ctx, axis=0, keepdims=True).astype(o_ref.dtype)
    else:       # row h's context stands in the columns of its K/V head
        o_ref[0] = sum(ctx[:heads, j * hd:(j + 1) * hd]
                       for j in range(kv_heads)).astype(o_ref.dtype)


def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, block_tables,
                           pos, layer, *, heads, kv_heads=None,
                           interpret=None):
    """Attention of one query row per slot over the slot's mapped pages.

    ``q`` ``(b, 1, heads * hd)``, heads side by side (the query-rows axis
    is static 1: verify's k1 rows are a later kernel); ``k_new`` /
    ``v_new`` ``(b, 1, kv_heads * hd)``: the new token's row, attended to at
    position ``pos`` as if it were already written (it is rounded to the
    pool's dtype first, as a written row would be); ``k_pool`` / ``v_pool``
    ``[L, pages, page_size, kv_heads * hd]``, the whole stacked pool, left in
    HBM and only read; ``block_tables`` ``(b, max_pages)`` int32; ``pos``
    ``(b,)`` int32; ``layer`` a scalar int32 (traced under the layer
    scan). ``kv_heads`` (``heads`` when not given) divides ``heads``: query
    head ``h`` reads K/V head ``h // (heads / kv_heads)``. Scores, softmax
    and the context accumulate in float32 with the mask ``s <= pos``; returns
    the context ``(b, 1, heads * hd)`` in ``q``'s dtype.
    """
    b, k1, q_width = q.shape
    kv_heads = heads if kv_heads is None else kv_heads
    if k1 != 1:
        raise ValueError(f"paged decode attention takes one query row per "
                         f"slot, got {k1}")
    if heads % kv_heads or q_width % heads:
        raise ValueError(f"{heads} query heads of a {q_width} row over "
                         f"{kv_heads} K/V heads")
    width = q_width // heads * kv_heads
    if k_pool.shape != v_pool.shape or k_pool.ndim != 4 \
            or k_pool.shape[3] != width:
        raise ValueError(f"pool {k_pool.shape} / {v_pool.shape} does not "
                         f"hold [L, pages, page_size, {width}] rows of "
                         f"{kv_heads} heads")
    page_size = k_pool.shape[2]
    chunk = max(1, min(_CHUNK_POSITIONS // page_size,
                       block_tables.shape[1]))
    row = pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    q_row = row
    if kv_heads != heads:       # a query head a row: (b, heads, hd)
        q = q.reshape(b, heads, q_width // heads)
        q_row = pl.BlockSpec((1,) + q.shape[1:], lambda i, *_: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    pool = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, chunk * page_size, width), k_pool.dtype)
    with jax.named_scope("apex_paged_decode_fwd"):
        return pl.pallas_call(
            functools.partial(_decode_kernel, heads=heads,
                              kv_heads=kv_heads, page_size=page_size),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b,),
                in_specs=[q_row, row, row, pool, pool],
                out_specs=q_row,
                scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))]),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=pallas_interpret(interpret),
            name="apex_paged_decode_fwd",
        )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
          jnp.reshape(layer, (1,)).astype(jnp.int32), q,
          k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype),
          k_pool, v_pool).reshape(b, 1, q_width)
