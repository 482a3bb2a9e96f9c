"""``apex_mla_decode_fwd`` (interpret mode on the CPU) against its XLA form:
all heads' absorbed queries against ONE shared row a position, key its whole
width and value its leading columns, read out of mapped pages in place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional.mla_attention import (
    _CHUNK_POSITIONS, mla_decode_attention, mla_decode_reference,
)

PAGE, WIDTH, VALUE = 16, 128, 96


def case(seed, slots, heads, max_pages, lengths, dtype=jnp.float32,
         layers=2):
    """A pool whose pages are handed out in a shuffled order, block tables
    NULL (0) past what each slot maps, and NaN in every row no slot maps."""
    rng = np.random.RandomState(seed)
    n_pages = 2 + slots * max_pages
    pool = np.full((layers, n_pages, PAGE, WIDTH), np.nan, np.float32)
    free = list(rng.permutation(np.arange(2, n_pages)))
    tables = np.zeros((slots, max_pages), np.int32)
    for i, n in enumerate(lengths):
        for j in range(-(-n // PAGE)):
            tables[i, j] = free.pop()
            pool[:, tables[i, j]] = rng.normal(size=(layers, PAGE, WIDTH))
    q = rng.normal(size=(slots, heads, WIDTH)).astype(np.float32) * 0.3
    new = rng.normal(size=(slots, WIDTH)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(new), jnp.asarray(pool, dtype),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("heads", [4, 16])
def test_kernel_matches_xla_on_ragged_lengths(heads):
    """Lengths that end inside a page, on a page boundary, past one DMA
    chunk, and 0 (only the new row is attended)."""
    lengths = [37, 48, _CHUNK_POSITIONS + 21, 0]
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
