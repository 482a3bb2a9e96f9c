"""Learned-sparse attention's selection over a paged pool: an INDEXER scores
the groups of positions a slot has cached, the best of them are picked, and
their rows are what the attention kernel reads (DeepSeek-V3.2's sparse
attention, with the indexer's keys pooled over ``pool`` positions).

The indexer's cache holds ONE key of ``width`` for every ``pool`` positions
(the mean of their keys): ``rows`` ``[L, num_pages, page_size / pool,
width]``, a page's keys under the page id of the latents they index, so the
slots' block tables address both. A query's score of group ``g`` is

    I[g] = sum_h w_h relu(q_h . key_g)

over the indexer's heads (the callers fold the scales into ``w``). Only a
WHOLE group has a key; a slot at position ``t`` has ``t // pool`` of them, and
the positions of its own group up to ``t`` (the tail) are attended always and
never scored.

``index_scores`` is the Pallas kernel ``apex_dsa_index_fwd``: a grid step a
slot, which walks the slot's block-table row up to its last whole group and
brings each page's keys (``page_size / pool * width`` numbers: 1 KB at pages
of 16, groups of 4 and 128 bfloat16 channels) from HBM to VMEM by DMA,
``_CHUNK_PAGES`` pages a wait, the next chunk on its way while this one is
scored (the products as ``mla_attention`` makes them: the query as two
bfloat16 terms against bfloat16 keys, float32 sums). What bounds it is those
bytes, 256 a group, and the page-sized fetches they come in.

``pick_groups`` is an exact ``lax.top_k`` (the program and its reference have
to pick the same groups; an approximate one would not), and
``gather_picked`` copies the picked groups' latent rows and the tail, valid
rows first, into a buffer that ``mla_decode_attention`` takes as a pool of its
own under an identity table: the plain way, whose copy a kernel that walks a
list of runs in the pool would save.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.transformer.functional.mla_attention import (
    _NT, _dot_f32, _operand,
)
from apex_tpu.utils.platform import pallas_interpret

# pages whose keys are fetched, waited for and scored at a time
_CHUNK_PAGES = 64
# page fetches started straight-line at a time
_START_UNROLL = 8

_NEG = float(jnp.finfo(jnp.float32).min)


def _index_kernel(bt_ref, groups_ref, layer_ref, q_ref, w_ref, rows_hbm, o_ref,
                  buf, sem):
    _, chunk, keys_a_page, width = buf.shape
    chunk_groups = chunk * keys_a_page
    slot = pl.program_id(0)
    groups = groups_ref[slot]
    layer = layer_ref[0]
    pages = lax.div(groups + (keys_a_page - 1), keys_a_page)
    chunks = lax.div(pages + (chunk - 1), chunk)

    def start(c, entry):
        """Every page of chunk ``c`` into ``buf[entry]``; past the slot's
        last page that page again, so that one wait of a chunk's bytes does
        (``mla_attention``'s rule)."""
        first = c * chunk

        def page(k, carry):
            at = bt_ref[slot, lax.min(first + k, pages - 1)]
            pltpu.make_async_copy(rows_hbm.at[layer, at], buf.at[entry, k],
                                  sem.at[entry]).start()
            return carry

        # (Mosaic unrolls a loop wholly or not at all: a run of starts
        # straight-line inside a rolled loop of runs)
        run = _START_UNROLL if chunk % _START_UNROLL == 0 else 1
        lax.fori_loop(0, chunk // run, lambda r, carry: lax.fori_loop(
            0, run, lambda j, carry: page(r * run + j, carry), carry,
            unroll=True), 0)

    q = _operand(q_ref[0].astype(jnp.float32), buf.dtype)
    w = w_ref[0].astype(jnp.float32)                    # (heads, 1)
    o_ref[0] = jnp.full(o_ref.shape[1:], _NEG, jnp.float32)

    @pl.when(chunks > 0)
    def _():
        start(0, 0)

    def step(c, carry):
        entry = lax.rem(c, 2)

        @pl.when(c + 1 < chunks)
        def _():
            start(c + 1, 1 - entry)

        at = buf.at[entry]
        pltpu.make_async_copy(at, at, sem.at[entry]).wait()
        keys = buf[entry].reshape(chunk_groups, width)
        s = _dot_f32(q, keys, _NT)                      # (heads, groups)
        score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        g = c * chunk_groups + lax.broadcasted_iota(
            jnp.int32, (1, chunk_groups), 1)
        o_ref[0, :, pl.ds(pl.multiple_of(c * chunk_groups, chunk_groups),
                          chunk_groups)] = jnp.where(g < groups, score, _NEG)
        return carry

    lax.fori_loop(0, chunks, step, 0)


def index_scores(q, w, rows, block_tables, groups, layer, *, interpret=None):
    """Every whole group of every slot scored by the slot's query.

    ``q`` ``(b, heads, width)`` and ``w`` ``(b, heads)`` float32, the
    indexer's queries and head weights (scales folded into ``w``); ``rows``
    ``[L, pages, keys a page, width]``, the indexer's cache, left in HBM and
    only read; ``block_tables`` ``(b, max_pages)`` int32; ``groups`` ``(b,)``
    int32, how many whole groups each slot holds (0: the slot reads nothing);
    ``layer`` a scalar int32. Returns ``(b, max_pages * keys a page)`` float32:
    group ``g``'s score at ``g`` below ``groups``, the lowest float32 from
    there on."""
    b, heads, width = q.shape
    if rows.ndim != 4 or rows.shape[3] != width or w.shape != (b, heads):
        raise ValueError(f"index rows {rows.shape} / weights {w.shape} do "
                         f"not fit queries {q.shape}")
    return _call(q, w, rows, block_tables, groups, layer,
                 interpret=pallas_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(q, w, rows, block_tables, groups, layer, *, interpret):
    b, heads, width = q.shape
    keys_a_page = rows.shape[2]
    max_pages = block_tables.shape[1]
    chunk = min(_CHUNK_PAGES, max_pages)
    out_groups = -(-max_pages // chunk) * chunk * keys_a_page
    with jax.named_scope("apex_dsa_index_fwd"):
        out = pl.pallas_call(
            _index_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b,),
                in_specs=[
                    pl.BlockSpec((1, heads, width), lambda i, *_: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, heads, 1), lambda i, *_: (i, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, 1, out_groups),
                                       lambda i, *_: (i, 0, 0),
                                       memory_space=pltpu.VMEM),
                scratch_shapes=[
                    pltpu.VMEM((2, chunk, keys_a_page, width), rows.dtype),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((b, 1, out_groups), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="apex_dsa_index_fwd",
        )(block_tables.astype(jnp.int32), groups.astype(jnp.int32),
          jnp.reshape(layer, (1,)).astype(jnp.int32), q.astype(jnp.float32),
          w.astype(jnp.float32)[..., None], rows)
    return out[:, 0, :max_pages * keys_a_page]


def index_scores_reference(q, w, rows, block_tables, groups, layer):
    """What :func:`index_scores` computes, in plain XLA: every slot's pages
    of keys gathered, float32 throughout."""
    b = q.shape[0]
    keys = rows[layer][block_tables].reshape(b, -1, rows.shape[3])
    s = jnp.einsum("bhd,bgd->bhg", q.astype(jnp.float32),
                   keys.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    score = jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None], 1)
    return jnp.where(jnp.arange(keys.shape[1])[None, :] < groups[:, None],
                     score, _NEG)


def pick_groups(scores, groups, top: int):
    """The ``top`` best-scored whole groups of every slot, exactly
    (``lax.top_k``; ties to the lower group): ``scores`` as
    :func:`index_scores` gives them. Returns ``(picked (b, k) int32, best
    first, count (b,) int32)`` with ``k = min(top, groups there are room
    for)``: a slot with fewer whole groups than ``k`` picks them all, and what
    stands in ``picked`` from ``count`` on is no pick."""
    k = min(top, scores.shape[1])
    picked = lax.top_k(scores, k)[1].astype(jnp.int32)
    return picked, jnp.minimum(groups, k).astype(jnp.int32)


def gather_picked(pool, layer, block_tables, picked, count, pos, group: int):
    """The rows a slot's query attends besides its own, copied out of the
    pool, valid rows first: the ``count`` picked groups' ``group`` rows each,
    then the rows of the query's own group before ``pos`` (``pos % group`` of
    them). ``pool`` ``[L, pages, page_size, width]`` with whole groups in a
    page. Returns ``(buffer [1, b * P, page_size, width], table (b, P) int32,
    length (b,) int32)``: what ``mla_decode_attention`` takes as pool, block
    table and ``pos``; rows at or past ``length`` are some row of the pool."""
    layers, pages, page_size, width = pool.shape
    if page_size % group:
        raise ValueError(f"pages of {page_size} rows do not hold whole "
                         f"groups of {group}")
    b, k = picked.shape
    a_page = page_size // group
    j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    groups = jnp.where(j < count[:, None],
                       jnp.pad(picked, ((0, 0), (0, 1))),
                       jnp.where(j == count[:, None], (pos // group)[:, None],
                                 0))
    logical = jnp.clip(groups // a_page, 0, block_tables.shape[1] - 1)
    page = jnp.take_along_axis(block_tables, logical, 1)
    first = ((layer * pages + page) * page_size + groups % a_page * group)
    rows = (first[..., None] + jnp.arange(group, dtype=jnp.int32)).reshape(
        b, -1)
    out_pages = -(-rows.shape[1] // page_size)
    rows = jnp.pad(rows, ((0, 0), (0, out_pages * page_size - rows.shape[1])))
    buffer = jnp.take(pool.reshape(-1, width), rows.reshape(-1), axis=0)
    table = jnp.arange(b * out_pages, dtype=jnp.int32).reshape(b, out_pages)
    return buffer.reshape(1, b * out_pages, page_size, width), table, \
        (count * group + pos % group).astype(jnp.int32)
