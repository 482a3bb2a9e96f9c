"""The paged decode-attention kernel (``apex_paged_decode_fwd``) against the
plain gather path, which stays: ``_paged_verify_attention`` at ``k1 = 1``
writes the new row into one layer's pages, gathers the slot's whole table
row and runs the masked float32 softmax over it. The kernel reads the same
pool in place, takes the new row as an operand, and leaves the write to
its caller; outputs agree to float32 rounding (the online softmax sums in
another order), the pool and the tables come back bit for bit alike."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import (
    _paged_decode_attention, _paged_verify_attention, _rope_or_none,
    gpt_tiny, init_gpt,
)
from apex_tpu.serving.cache import NULL_PAGE, RESERVED_PAGES, init_paged_cache
from apex_tpu.serving.decode import make_paged_decode_fn, make_paged_verify_fn

from apex_tpu.transformer.functional import paged_attention
from apex_tpu.transformer.functional.paged_attention import (
    paged_decode_attention,
)

LAYERS, LAYER, MAX_PAGES = 3, 1, 5
# float32 rounding of a 20-term softmax in another summation order
TOL = dict(rtol=2e-5, atol=2e-6)


def _positions(page_size):
    """One slot per case of ``pos``: nothing cached yet; the middle of a
    page; the last row of a page; the last row of the whole table."""
    return {"pos0": 0, "mid_page": page_size + page_size // 2,
            "page_last_row": 2 * page_size - 1,
            "full_table": MAX_PAGES * page_size - 1}


def _cfg(heads, rope):
    base = gpt_tiny()
    return dataclasses.replace(
        base, num_heads=heads, hidden_size=heads * base.head_dim,
        use_rope=rope)


def _problem(page_size, dtype, heads, rope, seed=0, order=None, fill=None):
    """A pool whose pages hold random rows, a table that maps each slot's
    logical pages to shuffled physical ones (``order`` picks the shuffle),
    NULL past what ``pos`` needs, and the new token's fused projection.
    ``fill`` overwrites everything no slot may read: pages no table row
    names (NULL among them) and the rows at or past ``pos``."""
    cfg = _cfg(heads, rope)
    pos = np.asarray(list(_positions(page_size).values()), np.int32)
    slots = len(pos)
    width = heads * cfg.head_dim
    num_pages = RESERVED_PAGES + slots * MAX_PAGES + 3
    rng = np.random.RandomState(seed)
    logical = rng.standard_normal(
        (2, LAYERS, slots, MAX_PAGES, page_size, width)).astype(np.float32)
    physical = np.arange(RESERVED_PAGES, num_pages)
    np.random.RandomState(1 if order is None else order).shuffle(physical)
    table = np.full((slots, MAX_PAGES), NULL_PAGE, np.int32)
    pools = rng.standard_normal(
        (2, LAYERS, num_pages, page_size, width)).astype(np.float32)
    if fill is not None:
        pools[:] = fill
    for s in range(slots):
        for j in range(pos[s] // page_size + 1):
            page = physical[s * MAX_PAGES + j]
            table[s, j] = page
            rows = min(page_size, pos[s] - j * page_size)
            pools[:, :, page, :rows] = logical[:, :, s, j, :rows]
    qkv = rng.standard_normal((slots, 1, 3 * width)).astype(np.float32)
    freqs = _rope_or_none(cfg, MAX_PAGES * page_size)
    k_pool, v_pool = (jnp.asarray(p).astype(dtype) for p in pools)
    return (cfg, freqs, jnp.asarray(qkv), k_pool, v_pool,
            jnp.asarray(table), jnp.asarray(pos))


def _kernel(cfg, freqs, qkv, k_pool, v_pool, table, pos):
    """(context, pools after the caller's write of the new rows)."""
    ctx, k_row, v_row = _paged_decode_attention(
        qkv, k_pool, v_pool, jnp.int32(LAYER), table, pos, cfg, freqs)
    page_size = k_pool.shape[2]
    pages = table[jnp.arange(len(pos)), pos // page_size]
    return (ctx, k_pool.at[LAYER, pages, pos % page_size].set(k_row),
            v_pool.at[LAYER, pages, pos % page_size].set(v_row))


def _gather(cfg, freqs, qkv, k_pool, v_pool, table, pos):
    ctx, k_pages, v_pages = _paged_verify_attention(
        qkv, k_pool[LAYER], v_pool[LAYER], table, pos, cfg, freqs)
    return (ctx, k_pool.at[LAYER].set(k_pages),
            v_pool.at[LAYER].set(v_pages))


@functools.lru_cache(maxsize=None)
def _both(page_size, dtype, heads, rope):
    problem = _problem(page_size, jnp.dtype(dtype), heads, rope)
    return (jax.tree.map(np.asarray, _kernel(*problem)),
            jax.tree.map(np.asarray, _gather(*problem)))


@pytest.mark.parametrize("rope", [False, True], ids=["learned_pos", "rope"])
@pytest.mark.parametrize("heads", [1, 8], ids=["one_head", "all_heads"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(_positions(4)))
@pytest.mark.parametrize("page_size", [4, 16])
def test_kernel_matches_the_gather_path(page_size, case, dtype, heads, rope):
    (got, k_got, v_got), (want, k_want, v_want) = _both(
        page_size, dtype, heads, rope)
    slot = list(_positions(page_size)).index(case)
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_allclose(got[slot], want[slot], **TOL)
    # the pool comes back as the gather path leaves it, bit for bit
    np.testing.assert_array_equal(k_got, k_want)
    np.testing.assert_array_equal(v_got, v_want)


# -- the walk across slots ------------------------------------------------------
#
# The kernel fetches slot i + 1's first pages while it finishes slot i, so
# what one slot's program leaves in the buffers and on the semaphores is the
# next one's start. These layouts put that hand-over at its edges, with a
# chunk cut to TWO pages so that a table of seven holds up to four chunks.

WALK_PAGES = 7


def _walk_layouts(p):
    """``pos`` per slot, by what the hand-over meets (``p``: page size; a
    chunk is ``2 * p`` positions)."""
    return {
        "one_slot": [3 * p + 1],
        "pos0_first": [0, 3 * p + 1, p],
        "pos0_last": [3 * p + 1, p, 0],
        "pos0_between": [3 * p + 1, 0, 0, p],
        # chunk counts 3, 2, 0, 2, 1, 4, 1: odd / even / zero neighbours
        "buffer_parity": [5 * p, 4 * p, 0, 2 * p + 1, p, 6 * p + 2, 2],
        "last_chunk_full": [4 * p, 2 * p, 6 * p],
        "one_live_row_in_last_chunk": [2 * p + 1, 4 * p + 1, 1],
    }


def _walk_problem(pos, page_size, heads, kv_heads, hd, dtype, max_pages,
                  seed=0, order=0, keep=None, nan_slots=()):
    """Operands for the kernel called directly. ``order`` reseeds the
    physical placement of every slot but ``keep``; ``nan_slots`` get NaN in
    every row of their mapped pages, both pools."""
    pos = np.asarray(pos, np.int32)
    slots, width = len(pos), kv_heads * hd
    num_pages = RESERVED_PAGES + slots * max_pages + 3
    rng = np.random.RandomState(seed)
    logical = rng.standard_normal(
        (2, LAYERS, slots, max_pages, page_size, width)).astype(np.float32)
    q = rng.standard_normal((slots, 1, heads * hd)).astype(np.float32)
    new = rng.standard_normal((2, slots, 1, width)).astype(np.float32)
    physical = np.arange(RESERVED_PAGES, num_pages)
    np.random.RandomState(100).shuffle(physical)
    if order:
        kept = set() if keep is None else set(
            physical[keep * max_pages:(keep + 1) * max_pages])
        rest = np.asarray([x for x in physical if x not in kept])
        np.random.RandomState(100 + order).shuffle(rest)
        rest = iter(rest)
        physical = np.asarray([x if x in kept else next(rest)
                               for x in physical])
    table = np.full((slots, max_pages), NULL_PAGE, np.int32)
    pools = np.zeros((2, LAYERS, num_pages, page_size, width), np.float32)
    for s in range(slots):
        for j in range(pos[s] // page_size + 1):
            page = table[s, j] = physical[s * max_pages + j]
            pools[:, :, page] = np.nan if s in nan_slots else \
                logical[:, :, s, j]
    k_pool, v_pool = (jnp.asarray(t).astype(dtype) for t in pools)
    k_new, v_new = (jnp.asarray(t).astype(dtype) for t in new)
    return (jnp.asarray(q), k_new, v_new, k_pool, v_pool,
            jnp.asarray(table), jnp.asarray(pos), jnp.int32(LAYER))


def _einsum_reference(q, k_new, v_new, k_pool, v_pool, table, pos, layer,
                      heads, kv_heads):
    """The slot's pages gathered, the new row set at ``pos``, K and V
    repeated to the query heads, a masked float32 softmax."""
    table, pos = np.asarray(table), np.asarray(pos)
    page_size, width = k_pool.shape[2:]
    hd = width // kv_heads
    span = table.shape[1] * page_size
    out = np.zeros((len(pos), heads, hd), np.float32)
    for s in range(len(pos)):
        def rows(pool, new):
            t = np.asarray(pool[layer].astype(jnp.float32))[
                np.maximum(table[s], 0)].reshape(span, kv_heads, hd).copy()
            t[pos[s]] = np.asarray(new[s, 0].astype(jnp.float32)).reshape(
                kv_heads, hd)
            return np.repeat(t, heads // kv_heads, axis=1)   # (span, heads, hd)
        k, v = rows(k_pool, k_new), rows(v_pool, v_new)
        qs = np.asarray(q[s, 0]).reshape(heads, hd)
        scores = np.einsum("hd,shd->hs", qs, k[:pos[s] + 1]) / np.sqrt(hd)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        out[s] = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True),
                           v[:pos[s] + 1])
    return out


def _walk(monkeypatch, pos, dtype, page_size=4, heads=4, kv_heads=2, **kw):
    """The kernel's output over ``pos`` with chunks of two pages, a slot's
    last chunk attended over the one or two pages that hold a live row."""
    problem = _walk_problem(pos, page_size, heads, kv_heads, 16,
                            jnp.dtype(dtype), WALK_PAGES, **kw)
    monkeypatch.setattr(paged_attention, "_BUFFER_BYTES",
                        2 * problem[3][0, 0].nbytes)
    monkeypatch.setattr(paged_attention, "_LIVE_POSITIONS", page_size)
    return np.asarray(paged_decode_attention(
        *problem, heads=heads, kv_heads=kv_heads)), problem


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads, kv_heads", [(4, 4), (8, 2)],
                         ids=["1_per_kv_head", "4_per_kv_head"])
@pytest.mark.parametrize("layout", list(_walk_layouts(4)))
def test_walk_across_slots_matches_einsum(monkeypatch, layout, heads,
                                          kv_heads, dtype):
    got, problem = _walk(monkeypatch, _walk_layouts(4)[layout], dtype,
                         heads=heads, kv_heads=kv_heads)
    want = _einsum_reference(*problem, heads, kv_heads)
    np.testing.assert_allclose(got.reshape(want.shape), want,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("moved", ["every_slot", "the_other_slots"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_output_is_bit_identical_across_page_placements(monkeypatch, dtype,
                                                        moved):
    if moved == "every_slot":
        outs = [np.asarray(_kernel(*_problem(4, jnp.dtype(dtype), 8, True,
                                             order=order))[0])
                for order in (1, 2, 3)]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
        return
    # slot ``keep`` stays where it is and every other slot's pages move:
    # what its neighbours left in the buffers is not its business
    pos = _walk_layouts(4)["buffer_parity"]
    base, _ = _walk(monkeypatch, pos, dtype)
    for keep in (1, 3, 5):
        for order in (1, 2):
            out, _ = _walk(monkeypatch, pos, dtype, order=order, keep=keep)
            np.testing.assert_array_equal(out[keep], base[keep])
            np.testing.assert_array_equal(out, base)


@pytest.mark.parametrize("poison", ["unmapped_pages_and_dead_rows",
                                    "the_next_slot", "the_slot_before"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_nan_in_unmapped_pages_and_dead_rows_cannot_reach_the_output(
        monkeypatch, dtype, poison):
    """Stronger than the gather path's ``0 * v``: NULL, unmapped pages and
    the rows at or past ``pos`` hold NaN and every slot's context is finite
    and bit for bit what a pool of zeros there gives. And a slot's pages are
    its own: NaN in every page of slot ``i + 1``, which slot ``i``'s program
    fetches into the other buffer while it finishes, leaves slot ``i``'s
    context bit for bit; so does NaN in the slot before, whose rows the
    buffers still hold."""
    if poison == "unmapped_pages_and_dead_rows":
        clean = _kernel(*_problem(4, jnp.dtype(dtype), 8, True, fill=0.0))[0]
        dirty = _kernel(*_problem(4, jnp.dtype(dtype), 8, True,
                                  fill=np.nan))[0]
        assert np.isfinite(np.asarray(dirty)).all()
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
        return
    pos = _walk_layouts(4)["buffer_parity"]
    clean, _ = _walk(monkeypatch, pos, dtype)
    assert np.isfinite(clean).all()
    for bad in (1, 3, 4, 5):        # 2, 1, 2 and 4 chunks; slot 2 has none
        dirty, _ = _walk(monkeypatch, pos, dtype, nan_slots=(bad,))
        assert np.isnan(dirty[bad]).any()
        spared = bad - 1 if poison == "the_next_slot" else bad + 1
        np.testing.assert_array_equal(dirty[spared], clean[spared])
        others = [s for s in range(len(pos)) if s != bad]
        np.testing.assert_array_equal(dirty[others], clean[others])


def test_decode_program_scans_the_kernel_and_never_carries_the_pool():
    cfg = gpt_tiny()
    params = jax.eval_shape(lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(
        init_paged_cache, cfg, 2, 32, 6, 16))
    jaxpr = jax.make_jaxpr(make_paged_decode_fn(cfg))(
        params, cache, jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.bool_))
    (jit,) = jaxpr.eqns
    scans = [e for e in jit.params["jaxpr"].eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    (scan,) = scans
    assert scan.params["length"] == cfg.num_layers
    kernels = [e for e in scan.params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels] == ["apex_paged_decode_fwd"]
    # the pool goes in whole, as a constant of the loop, and is no output
    consts = scan.invars[:scan.params["num_consts"]]
    assert [v.aval.shape for v in consts].count(cache.k.shape) == 2
    assert all(v.aval.ndim < 4 for v in scan.outvars)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decode_step_leaves_pool_and_tables_as_the_verify_step_does(dtype):
    """The whole step: ``jit_decode`` (kernel + one scatter after the layer
    scan) against the verify step at ``k1 = 1`` (per-layer write + gather)
    from the same cache: logits agree, the pools hold the same rows in the
    same places, the block tables are equal bit for bit, and only decode
    advances the active slots' lengths."""
    cfg = dataclasses.replace(gpt_tiny(), hidden_dropout=0.0)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    slots, page_size, max_len = 3, 4, 16
    cache = init_paged_cache(cfg, slots, max_len, 14, page_size,
                             jnp.dtype(dtype))
    rng = np.random.RandomState(0)
    lengths = np.asarray([0, 6, 11], np.int32)
    table = np.full(cache.block_tables.shape, NULL_PAGE, np.int32)
    free = iter(rng.permutation(np.arange(RESERVED_PAGES, 14)))
    for s in range(slots):
        for j in range(lengths[s] // page_size + 1):
            table[s, j] = next(free)
    cache = cache._replace(
        k=jnp.asarray(rng.standard_normal(cache.k.shape), cache.k.dtype),
        v=jnp.asarray(rng.standard_normal(cache.v.shape), cache.v.dtype),
        lengths=jnp.asarray(lengths), block_tables=jnp.asarray(table))
    clone = jax.tree.map(jnp.copy, cache)
    tokens = jnp.asarray([5, 7, 11], jnp.int32)
    active = jnp.asarray([True, False, True])
    got, logits = make_paged_decode_fn(cfg)(params, cache, tokens, active)
    want, ref = make_paged_verify_fn(cfg)(params, clone, tokens[:, None])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, 0]),
                               rtol=2e-4, atol=2e-4)
    for ours, theirs in ((got.k, want.k), (got.v, want.v)):
        ours, theirs = (np.asarray(t, np.float32) for t in (ours, theirs))
        # layer 0 sees the same input on both paths; deeper layers differ
        # by the rounding of the attention before them (one bfloat16 ulp)
        np.testing.assert_array_equal(ours[0], theirs[0])
        np.testing.assert_allclose(ours, theirs, rtol=1e-2, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got.block_tables), table)
    np.testing.assert_array_equal(np.asarray(want.block_tables), table)
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  lengths + np.asarray([1, 0, 1]))
    np.testing.assert_array_equal(np.asarray(want.lengths), lengths)


# -- fewer K/V heads than query heads ------------------------------------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize(
    "heads, kv_heads, hd", [(8, 2, 16), (4, 1, 16), (2, 2, 16), (30, 30, 128)],
    ids=["4_per_kv_head", "one_kv_head", "1_per_kv_head", "row_of_3840_lanes"])
@pytest.mark.parametrize("page_size", [4, 16])
def test_grouped_kv_heads_match_gather_and_einsum(page_size, heads, kv_heads,
                                                  hd, dtype):
    """Query head ``h`` reads K/V head ``h // (heads / kv_heads)``; the pool
    row is ``kv_heads * head_dim``. Against the slot's pages gathered, K and V
    repeated to the query heads and a masked float32 softmax. The row of
    3,840 lanes is the hybrid configuration's; in float32 with pages of 16 a
    buffer holds four of them, so the table's five are two chunks."""
    pos = list(_positions(page_size).values())
    problem = _walk_problem(pos, page_size, heads, kv_heads, hd,
                            jnp.dtype(dtype), MAX_PAGES,
                            seed=heads + page_size)
    got = paged_decode_attention(*problem, heads=heads, kv_heads=kv_heads)
    assert got.shape == (len(pos), 1, heads * hd)
    assert got.dtype == jnp.float32
    want = _einsum_reference(*problem, heads, kv_heads)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                               rtol=2e-5, atol=2e-5)


def test_heads_that_are_no_multiple_of_the_kv_heads_are_refused():
    pool = jnp.zeros((1, 6, 4, 32))
    with pytest.raises(ValueError, match="query heads"):
        paged_decode_attention(
            jnp.zeros((2, 1, 48)), jnp.zeros((2, 1, 32)), jnp.zeros((2, 1, 32)),
            pool, pool, jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.int32(0), heads=3, kv_heads=2)
    with pytest.raises(ValueError, match="does not hold"):
        paged_decode_attention(
            jnp.zeros((2, 1, 64)), jnp.zeros((2, 1, 16)), jnp.zeros((2, 1, 16)),
            pool, pool, jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.int32(0), heads=4, kv_heads=1)


# ---------------------------------------------------------------------------
# the lower bound: a sliding layer's call over a slot's cycle of pages
# ---------------------------------------------------------------------------

W_HEADS, W_KV, W_HD = 4, 2, 16


def _window_problem(page_size, window, pos, dtype, seed=0, poison=None):
    """A window pool (``serving.cache.WindowKVCache``) holding, for every
    slot, the rows of positions ``0 .. pos - 1`` of a random history laid
    into the slot's cycle as decode would have written them (a later row
    overwrites the one ``ring * page_size`` positions before it), and the
    new token's rows. ``poison`` overwrites every row no slot may read: the
    reserved pages and each slot's rows outside ``[start, pos)``."""
    from apex_tpu.serving.cache import WindowKVCache, ring_page, ring_pages

    pos = np.asarray(pos, np.int32)
    slots, ring = len(pos), ring_pages(window, page_size)
    width = W_KV * W_HD
    rng = np.random.RandomState(seed)
    history = rng.standard_normal(
        (2, LAYERS, slots, int(pos.max()) + 1, width)).astype(np.float32)
    pools = rng.standard_normal(
        (2, LAYERS, RESERVED_PAGES + slots * ring, page_size,
         width)).astype(np.float32)
    if poison is not None:
        pools[:] = poison
    start = np.maximum(pos - (window - 1), 0)
    for s in range(slots):
        for p in range(0 if poison is None else start[s], pos[s]):
            page = int(ring_page(s, p // page_size, ring))
            pools[:, :, page, p % page_size] = history[:, :, s, p]
    q = rng.standard_normal((slots, 1, W_HEADS * W_HD)).astype(np.float32)
    new = rng.standard_normal((2, slots, 1, width)).astype(np.float32)
    wk, wv = (jnp.asarray(p).astype(dtype) for p in pools)
    cache = WindowKVCache(k=wk[:, :1], v=wv[:, :1], lengths=jnp.asarray(pos),
                          block_tables=jnp.zeros((slots, 1), jnp.int32),
                          wk=wk, wv=wv)
    history = jnp.asarray(history).astype(dtype).astype(jnp.float32)
    new = jnp.asarray(new).astype(dtype).astype(jnp.float32)
    return cache, jnp.asarray(q), new, history, start


def _band_reference(q, new, history, pos, start):
    """Gather + einsum with the band mask, float32: each slot's query heads
    over the history rows ``start .. pos - 1`` and the new row at ``pos``."""
    out = []
    per = W_HEADS // W_KV
    for s in range(q.shape[0]):
        k = jnp.concatenate([history[0, LAYER, s, start[s]:pos[s]],
                             new[0, s]]).reshape(-1, W_KV, W_HD)
        v = jnp.concatenate([history[1, LAYER, s, start[s]:pos[s]],
                             new[1, s]]).reshape(-1, W_KV, W_HD)
        qs = q[s, 0].reshape(W_KV, per, W_HD)
        scores = jnp.einsum("gpd,sgd->gps", qs, k,
                            precision="highest") / np.sqrt(W_HD)
        out.append(jnp.einsum("gps,sgd->gpd", jax.nn.softmax(scores, -1), v,
                              precision="highest").reshape(-1))
    return jnp.stack(out)[:, None]


def _bounded_call(cache, q, new, window):
    table, pos, start = cache.window_view(window)
    return paged_decode_attention(
        q, new[0], new[1], cache.wk, cache.wv, table, pos, jnp.int32(LAYER),
        heads=W_HEADS, kv_heads=W_KV, start=start)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("page_size, window, pos", [
    (4, 8, (0, 3, 7, 8, 9, 100)),           # nothing cached .. far past it
    (4, 8, (10, 11, 12, 13, 14, 15)),       # the window begins mid-page
    (16, 128, (0, 127, 128, 129, 1000, 5000)),
    (16, 24, (40, 41, 55, 56, 57, 2000)),   # a window of no whole pages
], ids=["w8_ragged", "w8_mid_page", "w128_published", "w24_odd"])
def test_bounded_call_matches_gather_and_einsum_under_the_band(
        page_size, window, pos, dtype):
    cache, q, new, history, start = _window_problem(page_size, window, pos,
                                                    dtype)
    got = _bounded_call(cache, q, new, window)
    want = _band_reference(q, new, history, np.asarray(pos), start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_nan_outside_the_window_and_in_unmapped_pages_changes_no_bit(dtype):
    pos = (0, 3, 9, 14, 100, 37)
    clean = _window_problem(4, 8, pos, dtype)
    dirty = _window_problem(4, 8, pos, dtype, poison=np.nan)
    got = np.asarray(_bounded_call(dirty[0], dirty[1], dirty[2], 8))
    assert not np.isnan(got).any()
    np.testing.assert_array_equal(
        got, np.asarray(_bounded_call(clean[0], clean[1], clean[2], 8)))


def test_cyclic_table_walked_over_three_wraps_matches_the_band():
    """Decode by decode from position 0 past three turns of the cycle: the
    new row is written into the slot's cycle where ``_write_window_rows``
    puts it, and every step's bounded call equals the band over the whole
    history. Two slots out of step with each other."""
    from apex_tpu.serving.cache import WindowKVCache, ring_pages
    from apex_tpu.serving.decode import _write_window_rows

    page_size, window, slots = 4, 8, 2
    ring = ring_pages(window, page_size)
    steps = 3 * ring * page_size + 5
    width = W_KV * W_HD
    rng = np.random.RandomState(5)
    rows = jnp.asarray(rng.standard_normal(
        (2, LAYERS, slots, steps + 7, width)).astype(np.float32))
    qs = jnp.asarray(rng.standard_normal(
        (steps, slots, 1, W_HEADS * W_HD)).astype(np.float32))
    pool = jnp.zeros((LAYERS, RESERVED_PAGES + slots * ring, page_size,
                      width), jnp.float32)
    cache = WindowKVCache(k=pool[:, :1], v=pool[:, :1],
                          lengths=jnp.asarray([0, 7], jnp.int32),
                          block_tables=jnp.zeros((slots, 1), jnp.int32),
                          wk=pool, wv=pool)
    # slot 1 is seven positions ahead: its first seven rows are history
    for p in range(7):
        ahead = cache._replace(lengths=jnp.asarray([0, p], jnp.int32))
        wk, wv = _write_window_rows(ahead, rows[0, :, :, p], rows[1, :, :, p])
        cache = cache._replace(wk=wk.at[:, RESERVED_PAGES:RESERVED_PAGES
                                        + ring].set(0.0), wv=wv)
    for t in range(steps):
        pos = np.asarray(cache.lengths)
        new = jnp.stack([jnp.stack([rows[i, LAYER, s, pos[s]]
                                    for s in range(slots)])[:, None]
                         for i in range(2)])
        got = _bounded_call(cache, qs[t], new, window)
        start = np.maximum(pos - (window - 1), 0)
        want = _band_reference(qs[t], new, rows, pos, start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
        now = jnp.stack([jnp.stack([rows[i, :, s, pos[s]]
                                    for s in range(slots)], 1)
                         for i in range(2)])
        wk, wv = _write_window_rows(cache, now[0], now[1])
        cache = cache._replace(wk=wk, wv=wv, lengths=cache.lengths + 1)


def test_window_view_names_the_live_pages_of_the_cycle_in_order():
    from apex_tpu.serving.cache import (WindowKVCache, ring_page,
                                        ring_pages)

    assert ring_pages(128, 16) == 9 and ring_pages(8, 4) == 3
    pool = jnp.zeros((1, RESERVED_PAGES + 3 * 9, 16, 8))
    cache = WindowKVCache(k=pool, v=pool, lengths=jnp.asarray([0, 130, 5000]),
                          block_tables=jnp.zeros((3, 1), jnp.int32), wk=pool,
                          wv=pool)
    assert cache.ring == 9
    table, pos, start = (np.asarray(t) for t in cache.window_view(128))
    # slot 1 at 130: the window is 3 .. 130, which begins in logical page 0
    assert pos.tolist() == [0, 130, 5000 - 304 * 16]
    assert start.tolist() == [0, 3, (5000 - 127) % 16]
    assert table[1].tolist() == [2 + 9 + j % 9 for j in range(9)]
    assert table[2].tolist() == [2 + 18 + (304 + j) % 9 for j in range(9)]
    # the pages pos - 127 .. pos touch never alias: nine logical pages, nine
    # physical ones
    assert all(len(set(row)) == 9 for row in table.tolist())
    assert int(ring_page(2, 304, 9)) == table[2, 0]
    assert (start < 16).all() and (pos <= 9 * 16).all()


def test_without_a_bound_the_call_is_the_kernel_it_was():
    """``start=None`` adds no operand and no operation: the program's text
    is what the call without the argument lowers to, under the old name; the
    bounded call has one more scalar row and its own name."""
    cache, q, new, _, _ = _window_problem(4, 8, (3, 9), "float32")
    table, pos, start = cache.window_view(8)
    args = (q, new[0], new[1], cache.wk, cache.wv, table, pos,
            jnp.int32(LAYER))
    call = functools.partial(paged_decode_attention, heads=W_HEADS,
                             kv_heads=W_KV)

    def step(*a):
        return call(*a)

    def step_none(*a):
        return call(*a, start=None)

    def step_bounded(*a):
        return call(*a[:-1], start=a[-1])

    step_none.__name__ = step.__name__
    plain, none = (jax.jit(f).lower(*args) for f in (step, step_none))
    assert plain.as_text() == none.as_text()
    bounded = jax.jit(step_bounded).lower(*args, start)
    assert bounded.as_text() != plain.as_text()
    plain, bounded = (t.as_text(debug_info=True) for t in (plain, bounded))
    assert "apex_paged_decode_fwd" in plain
    assert "apex_paged_window_decode_fwd" not in plain
    assert "apex_paged_window_decode_fwd" in bounded
    assert "apex_paged_decode_fwd" not in bounded
