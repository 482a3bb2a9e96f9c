"""Single-query attention straight out of the paged KV pool (Pallas).

The decode step's attention reads only what a slot has mapped: the kernel
walks the slot's block-table row up to ``pos``, brings those pages of the
stacked pool from HBM to VMEM by DMA (double-buffered, several pages a
chunk) and runs an online softmax over them in float32. The pool is never
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

The walk crosses slots. The grid runs the slots in order on one core, and
the two buffers, their DMA semaphores and one SMEM word outlive a grid step.
While slot ``i`` attends over its LAST chunk (or at once, when ``pos`` is 0
and it has none) it starts the descriptors of slot ``i + 1``'s first chunk
into the buffer that is free: the next table row and ``pos`` are
scalar-prefetched, so it can name them. Slot ``i + 1`` begins by waiting for
those pages, not by asking for them, and only slot 0 of a call pays a first
fetch with nothing to hide it behind. The SMEM word says which buffer holds
the running slot's first chunk; a slot without a chunk leaves it as it is
and hands the turn on; the last slot starts nothing, so every descriptor of
a call is waited for inside it. A chunk that is full is waited for once per
pool (a DMA semaphore counts bytes); a slot's last chunk, which may be
partly filled, in at most ``log2`` descriptors of whole pages.

Placement invariance: a slot's output depends on the pages its table names
below ``pos`` and on nothing else. Pages past ``cdiv(pos, page_size)`` are
never fetched, so NULL table entries are never dereferenced. A slot reads
only the buffer its own chunk was waited into, and of that buffer only rows
below ``pos`` count: rows at or past ``pos`` can stand only in a slot's last
chunk (the rest of a partly filled buffer with them: stale rows of an
earlier chunk, of another slot), and there they are masked in the scores AND
zeroed in the values, so what they hold (another slot's rows, NaN) cannot
reach the output. The next slot's pages, on their way into the OTHER buffer
meanwhile, are never read by this one.

A lower bound (``start``, a sliding window's first position): rows below
``start`` are masked in the scores and zeroed in the values exactly as rows
at or past ``pos`` are, in every chunk. The caller hands a table whose FIRST
page holds ``start`` (``serving.cache.WindowKVCache.window_view``: the live
pages of a slot's cyclic table in logical order, ``pos`` and ``start``
counted from the first of them), so no page is skipped and the walk is the
one above. The bounded call runs under its own kernel name,
``apex_paged_window_decode_fwd`` (one body, two names: the name says which
kind of layer ran).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

# a buffer (one of K and one of V; two of each are resident) holds as many
# whole pages as fit in _BUFFER_BYTES: the chunk follows the row's width
# (2 KB rows: 512 positions; 7.5 KB rows: 128)
_BUFFER_BYTES = 1 << 20
# a slot's last chunk is attended over its leading blocks of this many
# positions that hold a row below ``pos``, not over the whole buffer
_LIVE_POSITIONS = 128
# head rows of the score matrix are padded to whole bfloat16 sublane tiles
_ROW_TILE = 16

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _operand(a, dtype):
    """``a`` (float32) as ``_dot_f32`` takes it against a chunk of ``dtype``:
    against bfloat16 the three bfloat16 pieces whose sum is ``a``, stacked
    (so the chunk is pushed to the MXU once); else ``a`` itself."""
    if dtype != jnp.bfloat16:
        return a
    pieces = []
    for _ in range(3):
        piece = a.astype(jnp.bfloat16)
        pieces.append(piece)
        a = a - piece.astype(jnp.float32)
    return jnp.concatenate(pieces, axis=0)


def _dot_f32(a, b, dims):
    """``_operand(a)`` contracted with ``b`` (a chunk in the pool's dtype),
    products exact and accumulation in float32. A bfloat16 chunk goes to
    the MXU as it is, against the stacked pieces; a float32 chunk takes the
    MXU's full-precision passes."""
    if b.dtype != jnp.bfloat16:
        return lax.dot_general(a, b.astype(jnp.float32), dims,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    rows = a.shape[0] // 3
    out = lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _decode_kernel(bt_ref, pos_ref, layer_ref, *refs, heads, kv_heads,
                   page_size, bounded=False):
    # ``bounded``: one more scalar-prefetched row, the slots' lower bounds
    start_ref = refs[0] if bounded else None
    (q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
     turn) = refs[int(bounded):]
    span, width = kbuf.shape[1:]
    hd = width // kv_heads
    per = heads // kv_heads             # query heads that share a K/V head
    chunk = span // page_size
    rows = -(-heads // _ROW_TILE) * _ROW_TILE
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    pos = pos_ref[slot]
    layer = layer_ref[0]

    def pages_of(s):
        # pages holding rows below pos: the new row itself is an operand
        return (pos_ref[s] + page_size - 1) // page_size

    n_pages = pages_of(slot)
    n_chunks = (n_pages + chunk - 1) // chunk

    def fetch(s, c, buf):
        """Start the descriptors of chunk ``c`` of slot ``s`` into ``buf``."""
        first = c * chunk

        def page(j, _):
            src = bt_ref[s, first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            pltpu.make_async_copy(k_hbm.at[layer, src], kbuf.at[buf, dst],
                                  sem.at[0, buf]).start()
            pltpu.make_async_copy(v_hbm.at[layer, src], vbuf.at[buf, dst],
                                  sem.at[1, buf]).start()
            return 0

        lax.fori_loop(0, jnp.minimum(chunk, pages_of(s) - first), page, 0)

    def wait(buf, pages):
        """Wait for ``pages`` (static) pages' worth of bytes in ``buf``, K
        and V: one descriptor each, which names the buffer's leading rows
        only for their size."""
        for i, ref in enumerate((kbuf, vbuf)):
            at = ref.at[buf, pl.ds(0, pages * page_size)]
            pltpu.make_async_copy(at, at, sem.at[i, buf]).wait()

    def hand_on(buf):
        """Start the next slot's first chunk into ``buf``, which is free."""
        @pl.when(slot + 1 < slots)
        def _():
            fetch(slot + 1, 0, buf)

    @pl.when(slot == 0)
    def _():
        turn[0] = 0
        fetch(slot, 0, 0)

    base = turn[0]                      # the buffer of this slot's chunk 0
    turn[0] = (base + n_chunks) % 2     # and of the next slot's

    @pl.when(n_chunks == 0)
    def _():
        hand_on(base)

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
    q_op = _operand(q_blk, kbuf.dtype)  # once a slot, not once a chunk
    norm = math.sqrt(hd)
    neg = jnp.finfo(jnp.float32).min

    def step(c, carry, last):
        """Chunk ``c`` of this slot; ``last`` (static) says it is the slot's
        last one: the only one that may be partly filled and hold rows at
        or past ``pos``, and the one behind which the next slot's first
        fetch hides."""
        buf = (base + c) % 2
        if last:
            hand_on(1 - buf)
            filled = n_pages - c * chunk
            for bit in range(chunk.bit_length()):
                pl.when((filled >> bit) & 1 == 1)(
                    functools.partial(wait, buf, 1 << bit))
        else:
            fetch(slot, c + 1, 1 - buf)
            wait(buf, chunk)
        left = pos - c * span                   # positions below pos here
        if bounded:                             # and the first that counts
            lower = start_ref[slot] - c * span

        def attend(carry, live):
            """Over the buffer's first ``live`` (static) positions."""
            m, l, acc = carry
            at = lax.broadcasted_iota(jnp.int32, (rows, live), 1)
            valid = at < left
            if bounded:
                valid &= at >= lower
            k = kbuf[buf, pl.ds(0, live)]
            s = jnp.where(valid, _dot_f32(q_op, k, _NT) / norm, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)      # (rows, live)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            v = vbuf[buf, pl.ds(0, live)]
            if last:
                dead = lax.broadcasted_iota(jnp.int32, (live, 1), 0) >= left
                v = jnp.where(dead, jnp.zeros_like(v), v)
            if bounded:
                dead = lax.broadcasted_iota(jnp.int32, (live, 1), 0) < lower
                v = jnp.where(dead, jnp.zeros_like(v), v)
            return m_new, l, alpha * acc + _dot_f32(_operand(p, v.dtype), v,
                                                    _NN)

        if not last or span <= _LIVE_POSITIONS or span % _LIVE_POSITIONS:
            return attend(carry, span)
        # the blocks past the last live row hold nothing that counts:
        # dropping them changes no bit of the sums
        return lax.switch(
            (left - 1) // _LIVE_POSITIONS,
            [functools.partial(attend, live=(i + 1) * _LIVE_POSITIONS)
             for i in range(span // _LIVE_POSITIONS)], carry)

    carry = lax.fori_loop(
        0, n_chunks - 1, functools.partial(step, last=False),
        (jnp.full((rows, 1), neg, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, width), jnp.float32)))
    m, l, acc = lax.cond(
        n_chunks > 0,
        lambda carry: step(n_chunks - 1, carry, last=True),
        lambda carry: carry, carry)
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
                           pos, layer, *, heads, kv_heads=None, start=None,
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
    the context ``(b, 1, heads * hd)`` in ``q``'s dtype. ``start`` ``(b,)``
    int32, where given: the mask is ``start <= s <= pos`` (``start`` lies in
    the table's first page), and the call is ``apex_paged_window_decode_fwd``.
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
    page_bytes = page_size * width * k_pool.dtype.itemsize
    chunk = max(1, min(_BUFFER_BYTES // page_bytes, block_tables.shape[1]))
    row = pl.BlockSpec((1, 1, width), lambda i, *_: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    q_row = row
    if kv_heads != heads:       # a query head a row: (b, heads, hd)
        q = q.reshape(b, heads, q_width // heads)
        q_row = pl.BlockSpec((1,) + q.shape[1:], lambda i, *_: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    pool = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, chunk * page_size, width), k_pool.dtype)
    scalars = (block_tables.astype(jnp.int32), pos.astype(jnp.int32),
               jnp.reshape(layer, (1,)).astype(jnp.int32))
    operands = (q, k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype),
                k_pool, v_pool)
    kernel = functools.partial(_decode_kernel, heads=heads,
                               kv_heads=kv_heads, page_size=page_size)

    def call(scalar_rows):
        return dict(
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=scalar_rows, grid=(b,),
                in_specs=[q_row, row, row, pool, pool],
                out_specs=q_row,
                scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                                pltpu.SMEM((1,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            # the walk hands buffers from slot i to slot i + 1: in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=pallas_interpret(interpret))

    # one body, two call sites: a kernel's name is a literal where it is
    # called (tests/L0/run_utils/test_kernel_names.py)
    if start is None:
        with jax.named_scope("apex_paged_decode_fwd"):
            out = pl.pallas_call(kernel, name="apex_paged_decode_fwd",
                                 **call(3))(*scalars, *operands)
    else:
        with jax.named_scope("apex_paged_window_decode_fwd"):
            out = pl.pallas_call(
                functools.partial(kernel, bounded=True),
                name="apex_paged_window_decode_fwd", **call(4))(
                *scalars, start.astype(jnp.int32), *operands)
    return out.reshape(b, 1, q_width)
