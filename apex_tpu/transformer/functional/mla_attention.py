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

As ``paged_attention``, the pool is only read in place: the kernel walks the
slots' block-table rows up to ``pos`` and brings those pages of the stacked
pool from HBM to VMEM by DMA; the new token's row comes in as an operand
standing at position ``pos``, and the caller writes it afterwards. Scores,
online softmax and context are float32.

The unit of everything is a BLOCK of ``_BLOCK_POSITIONS`` positions (256: 16
pages of 16 rows of 640 bfloat16 values, 320 KB): a block is fetched whole,
waited for ONCE (a DMA semaphore counts bytes: one descriptor of a block's
size) and attended over by one body. Blocks stand in a RING in VMEM
(``_RING_BYTES``: eight of them, 2.5 MiB; a table narrower than a block is
one block), and a fetch pointer (a slot and a block of it, two SMEM words)
runs seven blocks ahead of the block attended. Attention runs in ONE loop
over a slot's blocks with a DYNAMIC trip count, the blocks that hold a row
below ``pos``: nothing past them is attended, so the matrix unit does no
work on rows that do not count. In the loop's body stand the scores of block
``g + 1`` and the softmax and values of block ``g``, independent of each
other, so the matrix unit need not wait for the vector unit's chain; and the
16 descriptor starts of the block seven ahead, straight-line: starting a
descriptor (a table entry out of SMEM, two addresses) is scalar work, a
sixth of the kernel's time at 128 heads and a third at 32 where it stands in
a loop of its own, and beside the vector unit's work a part of it is hidden
(6% / 12% of the kernel: PR 45's probes). The query is split into its two
bfloat16 terms once a slot, not once a block.

The walk crosses slots. The grid runs the slots in order on one core; the
ring, its semaphores and the SMEM words outlive a grid step. The fetch
pointer moves from a slot's last block to block 0 of the next slot that
reads a page (``follows``, an SMEM word a slot, made by the call's first
slot: the table and ``pos`` are scalar-prefetched), so a slot begins by
waiting for pages that were asked for while earlier slots were attended
over, and only the call's first seven blocks are started with nothing to
hide them behind. Every block is fetched WHOLE: past a slot's last page that
page again, so that each wait is for a block's bytes and no count of a
partly filled block is kept. Behind the call's last block the pointer goes
round to its first; those seven blocks are never read, and the last slot
waits for them, so every descriptor of a call is waited for inside it.

Placement invariance: a slot's output depends on the pages its table names
below ``pos`` and on nothing else. No table entry at or past ``cdiv(pos,
page_size)`` is ever read, so NULL entries are never dereferenced. A slot
reads the ring entries of its own blocks only, counted from the SMEM word
that says how many blocks the call has attended over; what stands in the
other entries (blocks of the NEXT slots, on their way or arrived; blocks of
earlier ones) it never loads. Rows at or past ``pos`` (the rest of the last
page, and behind it that page again) can stand only in the slot's last
block, and there they are masked in the scores AND zeroed as values, by
selects, so what they hold (NaN) cannot reach the output.

The attention body (scores; softmax and values) stands in the kernel TWICE
and not once more: in the loop (every block but a slot's last, unmasked) and
around it (the first block's scores in front, the last block's masked
softmax and values behind); a block's descriptor starts twice (in the loop
and beside the last block) and once more rolled up (the call's first
blocks). A server pays for every copy at every start, in tracing, in
lowering Pallas to Mosaic and in loading the program, which no compile cache
saves (its key is the lowered text): a ``switch`` over live lengths or an
unrolled loop over blocks (``paged_attention``'s form, seven bodies at this
row width) cost the DeepSeek cell 5 s of a 46 s set-up (ledger, PR 44), in a
kernel its decode program holds twice, and PR 45's first form, which started
chunks of 48 pages at six places, 2 s. Hence also ``lax.div`` / ``lax.rem``
/ ``lax.select`` on the kernel's scalars and the page starts in a
``fori_loop(unroll=True)``: traced once, one operation each; and the call a
``jit`` of its own (``_call``), so that a program that calls the kernel twice
at the same shapes (DeepSeek's decode step) traces and lowers it once.
``tests/L0/test_aot_v5e.py`` holds the lowered module's products, descriptor
starts and bytes.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

# what is fetched, waited for and attended at a time: the positions of one
# block (whole pages; a table narrower than this is one block)
_BLOCK_POSITIONS = 256
# the ring holds as many blocks as fit in _RING_BYTES (1,280 B rows: 8 blocks
# of 16 pages of 16 rows, 2.5 MiB), the fetch running one less ahead; no fewer
# than _RING_BLOCKS[0] (the block attended, the block whose scores are made
# beside it, a fetch in flight) and no more than _RING_BLOCKS[1]
_RING_BYTES = 5 << 19
_RING_BLOCKS = (3, 8)
# query heads are padded to whole bfloat16 sublane tiles
_HEAD_TILE = 16

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _operand(a, dtype):
    """``a`` (float32) as ``_dot_f32`` takes it against a block of ``dtype``:
    against bfloat16 TWO bfloat16 terms, ``hi + lo`` (16 bits of mantissa),
    stacked, so that the block is pushed to the MXU once: the precision the
    model's matrix products keep (``models.nemotron_h._dense``), at two
    thirds of the three-term cost ``paged_attention`` pays, in a kernel whose
    128 heads make the MXU, not the DMA, the longer pole; else ``a``."""
    if dtype != jnp.bfloat16:
        return a
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, lo], axis=0)


def _dot_f32(a, b, dims):
    """``_operand(a)`` contracted with ``b`` (a block in the pool's dtype),
    accumulated in float32. A float32 block takes the MXU's full-precision
    passes."""
    if b.dtype != jnp.bfloat16:
        return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    rows = a.shape[0] // 2
    out = lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:]


def _mla_kernel(bt_ref, pos_ref, layer_ref, q_ref, new_ref, pool_hbm, o_ref,
                ring, sem, state, follows, *, page_size, value_width):
    entries, block = ring.shape[:2]
    block_pages = block // page_size
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    pos = pos_ref[slot]
    layer = layer_ref[0]
    # (scalars here are non-negative: lax.div / lax.rem, which lower to one
    # operation each where ``//`` and ``%`` lower to five)
    n_blocks = lax.div(pos + (block - 1), block)    # that hold a row below pos

    # state: [0] blocks attended so far in this call (block G of the call
    # stands in ring entry G % entries), [1] / [2] the slot and the block of
    # it that is fetched next. follows[s + 1]: the first slot after s that
    # reads a page (``slots`` if none does), follows[0] the call's first.

    def start_next(entry, unroll=True):
        """Start the descriptors of the block the fetch pointer names into
        ring entry ``entry`` and move the pointer on. A block is fetched
        WHOLE: past the slot's last page that page again (rows at or past
        ``pos``, which are masked), so that one descriptor of a block's size
        waits for it. ``unroll``: the page starts straight-line, which lets
        the compiler lay this scalar work beside the vector unit's."""
        fs, fb = state[1], state[2]
        reach = pos_ref[fs]
        last_page = lax.div(reach + (page_size - 1), page_size) - 1
        first_page = fb * block_pages

        def page(k, _):
            at = pl.ds(pl.multiple_of(k * page_size, page_size), page_size)
            pltpu.make_async_copy(
                pool_hbm.at[layer,
                            bt_ref[fs, lax.min(first_page + k, last_page)]],
                ring.at[entry, at], sem.at[entry]).start()
            return _

        lax.fori_loop(0, block_pages, page, 0, unroll=unroll)
        more = (fb + 1) * block < reach
        nxt = follows[fs + 1]
        # behind the call's last block its first one again: never read
        nxt = lax.select(nxt < slots, nxt, follows[0])
        state[1] = lax.select(more, fs, nxt)
        state[2] = lax.select(more, fb + 1, jnp.zeros_like(fb))

    def wait(entry):
        at = ring.at[entry]
        pltpu.make_async_copy(at, at, sem.at[entry]).wait()

    @pl.when(slot == 0)
    def _():
        def back(k, nxt):
            s = slots - 1 - k
            follows[s + 1] = nxt
            return lax.select(pos_ref[s] > 0, s, nxt)

        first = lax.fori_loop(0, slots, back, slots)
        follows[0] = first
        state[0] = 0
        state[1] = first
        state[2] = 0

        # only here is a fetch started with nothing to hide it behind
        @pl.when(first < slots)
        def _():
            def fill(k, _):
                start_next(k, unroll=False)
                return _

            lax.fori_loop(0, entries - 1, fill, 0)

    done = state[0]                     # this slot's block 0 is block `done`

    def entry_of(g):
        return lax.rem(done + g, entries)

    q = q_ref[0].astype(jnp.float32)                # (heads, width)
    q_op = _operand(q, ring.dtype)      # once a slot, not once a block
    heads = q.shape[0]
    neg = jnp.finfo(jnp.float32).min

    def scores(g):
        return _dot_f32(q_op, ring[entry_of(g)], _NT)       # (heads, block)

    def attend(g, s, carry, live=None):
        """Block ``g``, whose scores are ``s``, into the running softmax.
        ``live`` (where given: the one block of a slot that can hold rows at
        or past ``pos``) is how many of its rows lie below ``pos``."""
        m, l, acc = carry
        values = ring[entry_of(g), :, :value_width]
        if live is not None:            # at least 1, so exp(neg - m_new) is 0
            s = jnp.where(lax.broadcasted_iota(
                jnp.int32, (heads, block), 1) < live, s, neg)
            values = jnp.where(lax.broadcasted_iota(
                jnp.int32, (block, 1), 0) < live, values,
                jnp.zeros_like(values))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        return m_new, l, alpha * acc + _dot_f32(
            _operand(p, values.dtype), values, _NN)

    def walk(carry):
        """The fetch is ``entries - 1`` blocks ahead of the block attended,
        here and between slots: a step starts the block that far ahead into
        the entry of the block attended one step ago, waits for the next
        block and makes its scores beside the softmax and the values of
        this one (independent of each other: the matrix unit need not wait
        for the vector unit's chain). Behind the loop the slot's last block,
        masked; the start that belongs to it stands beside it."""
        wait(entry_of(0))

        def step(g, state_):
            s, carry = state_
            start_next(entry_of(g + (entries - 1)))
            wait(entry_of(g + 1))
            return scores(g + 1), attend(g, s, carry)

        s, carry = lax.fori_loop(0, n_blocks - 1, step, (scores(0), carry))
        start_next(entry_of(n_blocks + (entries - 2)))
        state[0] = done + n_blocks
        return attend(n_blocks - 1, s, carry,
                      live=pos - (n_blocks - 1) * block)

    m, l, acc = lax.cond(
        n_blocks > 0, walk, lambda carry: carry,
        (jnp.full((heads, 1), neg, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, value_width), jnp.float32)))

    @pl.when(jnp.logical_and(slot == slots - 1, follows[0] < slots))
    def _():
        # what was fetched ahead of the call's last block and is never read:
        # every descriptor of a call is waited for inside it
        end = state[0]

        def drain(k, _):
            wait(lax.rem(end + k, entries))
            return _

        lax.fori_loop(0, entries - 1, drain, 0)

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
    block_pages, entries = _ring_blocks(
        page_size, page_size * width * pool.dtype.itemsize,
        block_tables.shape[1])
    return _call(q, row_new, pool, block_tables, pos, layer,
                 value_width=value_width, block=block_pages * page_size,
                 entries=entries, interpret=pallas_interpret(interpret))


# a jit of its own: a program that calls the kernel twice at the same shapes
# (DeepSeek's decode step: the dense scan and the expert scan) traces and
# lowers it ONCE, as one function of the module called from both places; the
# compiled program holds both calls as before
@functools.partial(jax.jit, static_argnames=(
    "value_width", "block", "entries", "interpret"))
def _call(q, row_new, pool, block_tables, pos, layer, *, value_width, block,
          entries, interpret):
    b, heads, width = q.shape
    page_size = pool.shape[2]
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
                    pltpu.VMEM((entries, block, width), pool.dtype),
                    pltpu.SemaphoreType.DMA((entries,)),
                    pltpu.SMEM((3,), jnp.int32),
                    pltpu.SMEM((b + 1,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((b, heads + pad, value_width),
                                           jnp.float32),
            # the ring and its fetch pointer go from slot i to slot i + 1
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="apex_mla_decode_fwd",
        )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
          jnp.reshape(layer, (1,)).astype(jnp.int32), q.astype(jnp.float32),
          row_new.astype(pool.dtype)[:, None], pool)
    return out[:, :heads] if pad else out


def _ring_blocks(page_size, page_bytes, table_pages):
    """``(pages of a block, blocks of the ring)`` for pages of ``page_size``
    rows and ``page_bytes`` under a table ``table_pages`` wide."""
    block_pages = min(max(1, _BLOCK_POSITIONS // page_size), table_pages)
    low, high = _RING_BLOCKS
    return block_pages, max(low, min(
        high, _RING_BYTES // (block_pages * page_bytes)))


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
