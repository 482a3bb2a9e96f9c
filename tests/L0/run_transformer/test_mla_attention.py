"""``apex_mla_decode_fwd`` (interpret mode on the CPU) against its XLA form:
all heads' absorbed queries against ONE shared row a position, key its whole
width and value its leading columns, read out of mapped pages in place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import mla_attention
from apex_tpu.transformer.functional.mla_attention import (
    _ring_blocks, mla_decode_attention, mla_decode_reference,
)

PAGE, WIDTH, VALUE = 16, 128, 96


def block_of(max_pages, dtype=jnp.float32):
    """Positions of a block (what is fetched, waited for and attended at a
    time) under a table ``max_pages`` wide."""
    return PAGE * _ring_blocks(
        PAGE, PAGE * WIDTH * jnp.dtype(dtype).itemsize, max_pages)[0]


def case(seed, slots, heads, max_pages, lengths, dtype=jnp.float32,
         layers=2, dead=None):
    """A pool whose pages are handed out in a shuffled order, block tables
    NULL (0) past what each slot maps, and NaN in every row no slot maps;
    with ``dead`` also in the rows at or past ``pos`` of a slot's last page
    (``nan``), or that value."""
    rng = np.random.RandomState(seed)
    n_pages = 2 + slots * max_pages
    pool = np.full((layers, n_pages, PAGE, WIDTH), np.nan, np.float32)
    free = list(rng.permutation(np.arange(2, n_pages)))
    tables = np.zeros((slots, max_pages), np.int32)
    for i, n in enumerate(lengths):
        for j in range(-(-n // PAGE)):
            tables[i, j] = free.pop()
            pool[:, tables[i, j]] = rng.normal(size=(layers, PAGE, WIDTH))
        if dead is not None and n % PAGE:
            pool[:, tables[i, n // PAGE], n % PAGE:] = dead
    q = rng.normal(size=(slots, heads, WIDTH)).astype(np.float32) * 0.3
    new = rng.normal(size=(slots, WIDTH)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(new), jnp.asarray(pool, dtype),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("heads", [4, 16])
def test_kernel_matches_xla_on_ragged_lengths(heads):
    """Lengths that end inside a page, on a page boundary, past one block,
    and 0 (only the new row is attended)."""
    lengths = [37, 48, block_of(20) + 21, 0]
    q, new, pool, bt, pos = case(0, 4, heads, 20, lengths)
    for layer in (0, 1):
        got = mla_decode_attention(q, new, pool, bt, pos, jnp.int32(layer),
                                   value_width=VALUE)
        want = mla_decode_reference(q, new, pool, bt, pos, layer,
                                    value_width=VALUE)
        assert got.shape == (4, heads, VALUE) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # a slot with no page attends the new row alone: its value columns
    np.testing.assert_allclose(
        got[3], np.broadcast_to(np.asarray(new)[3, :VALUE], (heads, VALUE)),
        atol=1e-6)


# what the walk across slots can get wrong: (table pages, lengths from the
# block's positions), float32 pool; a table of 56 pages is three and a half
# blocks, the ring holds eight
WALKS = {
    # the fetch pointer passes slots that read nothing
    "empty_between_two_that_read":
        (56, lambda block: [3 * block + 21, 0, 0, block + 5, 0, 37]),
    "empty_first_and_last":
        (56, lambda block: [0, 3 * block + block // 2 - 3, 0]),
    "all_empty": (56, lambda block: [0, 0, 0]),
    # behind the call's one reader its first block again, drained by a slot
    # that reads nothing
    "one_reader_then_empties": (56, lambda block: [2 * block + 3, 0, 0, 0]),
    # a slot's last block exactly full: the masked block masks nothing
    "last_block_exactly_full":
        (56, lambda block: [3 * block, 40, 3 * block, 3 * block - PAGE]),
    "pos_on_block_boundaries":
        (56, lambda block: [block, 2 * block, block + 1, 1]),
    # a last block of one page: one row of it, and all of it; its other
    # pages are that page again
    "last_block_of_one_page":
        (56, lambda block: [3 * block + 1, 3 * block + PAGE, 9]),
    "pos_off_the_page_and_the_block":
        (56, lambda block: [block + 7, 2 * block - 1, 3,
                            3 * block + block // 4 + 3]),
    # the seven blocks in flight belong to several slots, and the ring
    # turns in the middle of one
    "fetch_ahead_over_many_short_slots":
        (56, lambda block: [5, 7, 9, block + 1, 3, 1, 16, 17, 2 * block, 4,
                            33, 2, 0, 3 * block + 2, 1]),
    # a table of more than one block and less than two
    "table_of_one_block_and_a_bit":
        (20, lambda block: [20 * PAGE, block, 17, 0, block + 44]),
    # a table narrower than a block: the block is the table
    "table_narrower_than_one_block":
        (6, lambda block: [block, 50, 0, 81, 1]),
    "one_slot": (56, lambda block: [3 * block + 70]),
    # a ring of three blocks under a table of seven and a half: the ring
    # turns twice inside a slot, the fetch two blocks ahead
    "ring_turns_inside_a_slot":
        (120, lambda block: [7 * block + 5, 2 * block, 0,
                             6 * block + 77, block - 1]),
}


@pytest.mark.parametrize("name", sorted(WALKS))
def test_walk_across_slots_matches_xla(name, monkeypatch):
    """Each slot against the XLA form, with NaN in every row no slot maps
    AND in every row at or past ``pos`` of a slot's last page: a row another
    slot left in the ring, or a dead one, would show."""
    max_pages, lengths = WALKS[name]
    if name == "ring_turns_inside_a_slot":
        monkeypatch.setattr(mla_attention, "_RING_BYTES",
                            3 * 16 * PAGE * WIDTH * 4)
    lengths = lengths(block_of(max_pages))
    assert max(lengths) <= max_pages * PAGE
    q, new, pool, bt, pos = case(11, len(lengths), 8, max_pages, lengths,
                                 dead=np.nan)
    got = mla_decode_attention(q, new, pool, bt, pos, jnp.int32(1),
                               value_width=VALUE)
    want = mla_decode_reference(q, new, pool, bt, pos, 1, value_width=VALUE)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [12, 32, 128])
def test_head_counts_and_pool_dtypes(heads, dtype):
    """32 and 128 heads (the two cells') and a count that is padded to whole
    tiles, against both pools, over more than one block."""
    block = block_of(56, dtype)
    lengths = [3 * block + 9, 0, block - 5]
    q, new, pool, bt, pos = case(12, 3, heads, 56, lengths, dtype,
                                 dead=np.nan)
    got = mla_decode_attention(q, new, pool, bt, pos, jnp.int32(0),
                               value_width=VALUE)
    want = mla_decode_reference(q, new, pool, bt, pos, 0, value_width=VALUE)
    assert got.shape == (3, heads, VALUE)
    tol = 3e-4 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_slot_does_not_depend_on_its_neighbours(dtype):
    """The ring outlives a slot: which entries a slot's blocks stand in, and
    what the entries beside them hold, is what the slots before it left.
    The same slots in another order, and each alone: bit-identical, whatever
    the dead rows hold."""
    block = block_of(56, dtype)
    lengths = [3 * block + 30, 3, 0, block + 100, 3 * block, 2 * block + 1]
    q, new, pool, bt, pos = case(13, 6, 8, 56, lengths, dtype, dead=np.nan)
    run = lambda pool, rows: np.asarray(mla_decode_attention(
        q[rows], new[rows], pool, bt[rows], pos[rows], jnp.int32(1),
        value_width=VALUE))
    a = run(pool, np.arange(6))
    assert np.isfinite(a).all()
    order = np.array([4, 2, 0, 5, 1, 3])
    np.testing.assert_array_equal(run(pool, order), a[order])
    for i in range(6):
        np.testing.assert_array_equal(run(pool, np.array([i])), a[[i]])
    other = jnp.where(jnp.isnan(pool), 7.0, pool).astype(pool.dtype)
    np.testing.assert_array_equal(run(other, np.arange(6)), a)


def test_rows_at_or_past_pos_and_unmapped_pages_cannot_reach_the_output():
    """The last page's rows past ``pos`` hold NaN here, as does every page no
    slot maps, and the output is finite; it does not move when they change."""
    q, new, pool, bt, pos = case(1, 2, 8, 6, [21, 70])
    stale = np.asarray(pool).copy()
    for i, n in enumerate([21, 70]):
        page = int(bt[i, n // PAGE])
        stale[:, page, n % PAGE:] = np.nan
    a = mla_decode_attention(q, new, jnp.asarray(stale), bt, pos,
                             jnp.int32(1), value_width=VALUE)
    assert np.isfinite(np.asarray(a)).all()
    other = np.where(np.isnan(stale), 7.0, stale)
    b = mla_decode_attention(q, new, jnp.asarray(other), bt, pos,
                             jnp.int32(1), value_width=VALUE)
    np.testing.assert_array_equal(a, b)


def test_placement_does_not_matter():
    """The same rows in other physical pages: bit-identical."""
    q, new, pool, bt, pos = case(2, 3, 8, 8, [100, 33, 64])
    perm = np.random.RandomState(3).permutation(pool.shape[1] - 2) + 2
    moved = np.asarray(pool).copy()
    moved[:, perm] = np.asarray(pool)[:, 2:]
    table = np.asarray(bt).copy()
    mapped = table > 0
    table[mapped] = perm[table[mapped] - 2]
    a = mla_decode_attention(q, new, pool, bt, pos, jnp.int32(0),
                             value_width=VALUE)
    b = mla_decode_attention(q, new, jnp.asarray(moved), jnp.asarray(table),
                             pos, jnp.int32(0), value_width=VALUE)
    np.testing.assert_array_equal(a, b)


def test_bfloat16_pool_two_terms_keep_float32_queries():
    """Against a bfloat16 pool the float32 queries and probabilities go in as
    two bfloat16 terms: the result is the float32 computation over the
    rounded rows to ~1e-4, where one term would leave ~1e-2."""
    q, new, pool, bt, pos = case(4, 2, 8, 12, [150, 90], jnp.bfloat16)
    got = mla_decode_attention(q, new, pool, bt, pos, jnp.int32(0),
                               value_width=VALUE)
    want = mla_decode_reference(q, new, pool, bt, pos, 0, value_width=VALUE)
    np.testing.assert_allclose(got, want, atol=3e-4)
    one_term = mla_decode_reference(
        q.astype(jnp.bfloat16).astype(jnp.float32), new, pool, bt, pos, 0,
        value_width=VALUE)
    assert float(jnp.abs(one_term - want).max()) > 10 * float(
        jnp.abs(got - want).max())


def test_shapes_that_do_not_fit_are_refused():
    q, new, pool, bt, pos = case(5, 2, 4, 4, [10, 20])
    with pytest.raises(ValueError, match="query's width"):
        mla_decode_attention(q[..., :64], new, pool, bt, pos, jnp.int32(0),
                             value_width=32)
    with pytest.raises(ValueError, match="value width"):
        mla_decode_attention(q, new, pool, bt, pos, jnp.int32(0),
                             value_width=WIDTH + 1)
