"""Absorbed single-query latent attention (MLA) straight out of a paged
LATENT pool (Pallas).

Multi-head latent attention caches, per token and layer, one row shared by
every head: the normed compressed latent ``c_kv`` and the roped shared key
``k_pe``, side by side. With the up-projection absorbed into the query
(``q_lat[h] = q_nope[h] W_kvb^K[h]``) the scores of ALL heads are ``[q_lat |
q_pe] . row`` and the context is ``P @ row[:value_width]``, a latent that the
caller projects up per head afterwards: keys and values per head are never
made. The same row is key (its whole width) and value (its leading
``value_width`` columns), so a position is fetched ONCE and used by every
head: the kernel does ``2 * heads * (width + value_width)`` operations per
row of ``width`` cache numbers, which puts a v5e at its ridge with 128 heads
(``PERF.md``).

As ``paged_attention``: the kernel walks the slot's block-table row up to
``pos``, brings those pages of the stacked pool from HBM to VMEM by DMA
(double-buffered, several pages a step) and runs an online softmax over them
in float32; the pool is only read, the new token's row comes in as an operand
standing at position ``pos``, and the caller writes it afterwards. A slot's
output depends on the pages its table names below ``pos`` and on nothing
else: pages past ``cdiv(pos, page_size)`` are never fetched, rows of the last
page at or past ``pos`` are masked in the scores and zeroed as values.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

# cache positions brought to VMEM per DMA step (two buffers are resident)
_CHUNK_POSITIONS = 256
# query heads are padded to whole float32 sublane tiles
_HEAD_TILE = 8

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot_f32(a, b, dims):
    """``a`` (float32) contracted with ``b`` (a chunk in the pool's dtype),
    accumulated in float32. Against a bfloat16 chunk ``a`` goes as TWO
    bfloat16 terms, ``hi + lo`` (16 bits of mantissa; stacked, so the chunk
    is pushed to the MXU once): the precision the model's matrix products
    keep (``models.nemotron_h._dense``), at two thirds of the three-term
    cost ``paged_attention`` pays, in a kernel whose 128 heads make the MXU,
    not the DMA, the longer pole. A float32 chunk takes the MXU's
    full-precision passes."""
    if b.dtype != jnp.bfloat16:
        return lax.dot_general(a, b.astype(jnp.float32), dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    rows = a.shape[0]
    out = lax.dot_general(jnp.concatenate([hi, lo], axis=0), b, dims,
                          preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:]


def _mla_kernel(bt_ref, pos_ref, layer_ref, q_ref, new_ref, pool_hbm, o_ref,
                buf, sem, *, page_size, value_width):
    span = buf.shape[1]
    chunk = span // page_size
    slot = pl.program_id(0)
    pos = pos_ref[slot]
    layer = layer_ref[0]
    # pages holding rows below pos: the new row itself is an operand
    n_pages = (pos + page_size - 1) // page_size
    n_chunks = (n_pages + chunk - 1) // chunk

    def dma(c, b, fn):
        first = c * chunk

        def page(j, _):
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            fn(pltpu.make_async_copy(
                pool_hbm.at[layer, bt_ref[slot, first + j]], buf.at[b, dst],
                sem.at[b]))
            return 0

        lax.fori_loop(0, jnp.minimum(chunk, n_pages - first), page, 0)

    @pl.when(n_chunks > 0)
    def _():
        dma(0, 0, lambda d: d.start())

    q = q_ref[0].astype(jnp.float32)                # (heads, width)
    heads = q.shape[0]
    neg = jnp.finfo(jnp.float32).min
    at_lane = lax.broadcasted_iota(jnp.int32, (heads, span), 1)
    at_row = lax.broadcasted_iota(jnp.int32, (span, 1), 0)

    def step(c, carry):
        m, l, acc = carry
        b = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            dma(c + 1, 1 - b, lambda d: d.start())

        dma(c, b, lambda d: d.wait())
        left = pos - c * span                   # positions below pos here
        valid = at_lane < left
        rows = buf[b]
        s = jnp.where(valid, _dot_f32(q, rows, _NT), neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)       # (heads, span)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        values = rows[:, :value_width]
        values = jnp.where(at_row < left, values, jnp.zeros_like(values))
        return m_new, l, alpha * acc + _dot_f32(p, values, _NN)

    m, l, acc = lax.fori_loop(
        0, n_chunks, step,
        (jnp.full((heads, 1), neg, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, value_width), jnp.float32)))
    # the new token's own row, at position pos
    new = new_ref[0].astype(jnp.float32)            # (1, width)
    s_new = jnp.sum(q * new, axis=1, keepdims=True)
    m_new = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_new)
    p_new = jnp.exp(s_new - m_new)
    acc = alpha * acc + p_new * new[:, :value_width]
    o_ref[0] = (acc / (alpha * l + p_new)).astype(o_ref.dtype)


def mla_decode_attention(q, row_new, pool, block_tables, pos, layer, *,
                         value_width: int, interpret=None):
    """Attention of one absorbed query per head and slot over the slot's
    mapped pages of a latent pool.

    ``q`` ``(b, heads, width)`` float32, the softmax scale folded in, laid
    out as a cache row is (``[q_lat | q_pe | zeros to width]``); ``row_new``
    ``(b, width)``: the new token's row, attended to at position ``pos`` as
    if it were already written (it is rounded to the pool's dtype first, as a
    written row would be); ``pool`` ``[L, pages, page_size, width]``, the
    whole stacked pool, left in HBM and only read; ``block_tables`` ``(b,
    max_pages)`` int32; ``pos`` ``(b,)`` int32 (a slot with ``pos`` 0 reads
    no page); ``layer`` a scalar int32 (traced under the layer scan). Scores,
    softmax and the context accumulate in float32 with the mask ``s <= pos``;
    returns the latent context ``(b, heads, value_width)`` float32: ``softmax(q
    . rows) @ rows[:, :value_width]``.
    """
    b, heads, width = q.shape
    if pool.ndim != 4 or pool.shape[3] != width or row_new.shape != (b, width):
        raise ValueError(f"pool {pool.shape} / new row {row_new.shape} do not "
                         f"hold rows of the query's width {width}")
    if not 0 < value_width <= width:
        raise ValueError(f"value width {value_width} of a {width} row")
    page_size = pool.shape[2]
    chunk = max(1, min(_CHUNK_POSITIONS // page_size, block_tables.shape[1]))
    pad = -heads % _HEAD_TILE
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    q_spec = pl.BlockSpec((1, heads + pad, width), lambda i, *_: (i, 0, 0),
                          memory_space=pltpu.VMEM)
    with jax.named_scope("apex_mla_decode_fwd"):
        out = pl.pallas_call(
            functools.partial(_mla_kernel, page_size=page_size,
                              value_width=value_width),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b,),
                in_specs=[q_spec,
                          pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0),
                                       memory_space=pltpu.VMEM),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, heads + pad, value_width),
                                       lambda i, *_: (i, 0, 0),
                                       memory_space=pltpu.VMEM),
                scratch_shapes=[
                    pltpu.VMEM((2, chunk * page_size, width), pool.dtype),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((b, heads + pad, value_width),
                                           jnp.float32),
            interpret=pallas_interpret(interpret),
            name="apex_mla_decode_fwd",
        )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
          jnp.reshape(layer, (1,)).astype(jnp.int32), q.astype(jnp.float32),
          row_new.astype(pool.dtype)[:, None], pool)
    return out[:, :heads] if pad else out


def mla_decode_reference(q, row_new, pool, block_tables, pos, layer, *,
                         value_width: int):
    """What :func:`mla_decode_attention` computes, in plain XLA: every
    slot's pages gathered, float32 throughout."""
    b, heads, width = q.shape
    rows = pool[layer][block_tables].reshape(b, -1, width)
    rows = rows.astype(jnp.float32)
    at = jnp.arange(rows.shape[1])[None, :]
    rows = jnp.where((at < pos[:, None])[..., None], rows, 0.0)
    new = row_new.astype(pool.dtype).astype(jnp.float32)
    rows = jnp.concatenate([rows, new[:, None]], axis=1)
    valid = jnp.concatenate([at < pos[:, None], jnp.ones((b, 1), bool)], 1)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows,
                   precision=lax.Precision.HIGHEST)
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsv->bhv", p, rows[..., :value_width],
                      precision=lax.Precision.HIGHEST)
