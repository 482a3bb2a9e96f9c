"""Page pool + prefix-sharing contracts: host-side allocator invariants
(alloc/free/refcount, LRU eviction, out-of-pages behavior), stored-once
prefix sharing, and the copy-on-write acceptance contract — a slot
appending into a shared page must never perturb the other request's
logits (bit-identity, not tolerance)."""

import dataclasses
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (
    PagePool, PagedDecodeEngine, PoolExhausted, prefix_page_keys,
)
from apex_tpu.serving.cache import RESERVED_PAGES, SCRATCH_PAGE

S_MAX = 32


def _cfg():
    return dataclasses.replace(gpt_tiny(), use_rope=True,
                               hidden_dropout=0.0)


def _engine(params, cfg, num_pages, page_size=4, **kw):
    return PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                             num_pages=num_pages, page_size=page_size,
                             cache_dtype=jnp.float32, buckets=(16, 32),
                             **kw)


# -- prefix keys ------------------------------------------------------------

def test_prefix_page_keys_chain():
    """Key i commits to every token of pages 0..i: a longer prompt's
    keys extend a shorter one's, and any token change invalidates all
    keys from its page onward (including a partial last page)."""
    a = prefix_page_keys([1, 2, 3, 4, 5, 6], 4)
    b = prefix_page_keys([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    assert len(a) == 2 and len(b) == 3
    assert b[0] == a[0]
    assert b[1] != a[1]  # partial page (5, 6) vs full (5, 6, 7, 8)
    c = prefix_page_keys([1, 2, 9, 4, 5, 6], 4)
    assert c[0] != a[0] and c[1] != a[1]
    with pytest.raises(ValueError, match="positive"):
        prefix_page_keys([1], 0)


def test_prefix_page_key_encoding_is_pinned():
    """The canonical byte layout under the chain hash — ``<II{n}i``
    little-endian (version, count, tokens) — pinned by exact hex. The
    chained digests are a CROSS-REPLICA wire format (prefix-cache
    dedup, transfer checksums in the disaggregated tier), so any
    drift here silently severs every cached prefix and quarantines
    every in-flight handoff: a layout change must bump
    ``PAGE_KEY_VERSION``, not mutate these vectors."""
    from apex_tpu.serving.paging import PAGE_KEY_VERSION, _encode_page

    assert PAGE_KEY_VERSION == 1
    assert _encode_page((1, 2, 3, 4)).hex() == \
        "010000000400000001000000020000000300000004000000"
    assert [k.hex() for k in prefix_page_keys([1, 2, 3, 4, 5, 6, 7], 4)] \
        == ["79e1a907696f5ad880df64ad64b10044647381ac2788c8f53e33ce"
            "66f9f9a025",
            "384380725a66cc2f73081861c743d7c658bc5bc5c3a40dbbed2e1e2"
            "27c2ff961"]
    # a partial page commits to its count: [0] under page_size 4 must
    # not alias [0, 0] or a zero-padded full page
    assert prefix_page_keys([0], 4)[0].hex() == \
        "7d450465ceb49083708a6970827f0e0b116ed285072a95b451e55f583f56da8d"
    assert prefix_page_keys(list(range(8)), 2)[-1].hex() == \
        "68885af65c19be66af637a6cf362f02b6dc9c2c6ab3423a08c7600a81ccd0e86"
    # int32 wire range is enforced, never truncated
    with pytest.raises(struct.error):
        _encode_page((2**31,))


def test_spill_header_encoding_is_pinned():
    """The host-tier spill payload header — ``<IIIIII`` little-endian
    (version, layers, heads, page_size, head_dim, dtype_tag) followed
    by the 32-byte chain key — pinned by exact hex. Spill records
    outlive engines (the registry is shared across replicas), so any
    drift silently quarantines every resident record at its next
    promotion: a layout change must bump ``PAGE_KEY_VERSION``."""
    from apex_tpu.serving.paging import (
        SPILL_DTYPE_TAGS, SPILL_HEADER_BYTES, decode_spill_header,
        encode_spill_header, spill_checksum,
    )

    assert SPILL_HEADER_BYTES == 56
    assert SPILL_DTYPE_TAGS == {"bfloat16": 1, "float32": 2,
                                "float16": 3, "int8": 4}
    key = bytes(range(32))
    header = encode_spill_header(key, 2, 2, 4, 8, 1)
    assert header.hex() == (
        "010000000200000002000000040000000800000001000000"
        "000102030405060708090a0b0c0d0e0f"
        "101112131415161718191a1b1c1d1e1f")
    assert decode_spill_header(header) == {
        "version": 1, "num_layers": 2, "num_heads": 2, "page_size": 4,
        "head_dim": 8, "dtype_tag": 1, "key": key}
    with pytest.raises(ValueError, match="32-byte"):
        encode_spill_header(b"short", 2, 2, 4, 8, 1)
    with pytest.raises(ValueError, match="56 bytes"):
        decode_spill_header(header[:-1])
    # the checksum binds header AND payload (scale planes included)
    k = np.arange(8, dtype=np.float32).reshape(1, 1, 1, 2, 4)
    v = k + 8
    d = spill_checksum(header, k, v)
    assert d == spill_checksum(header, k.copy(), v.copy())
    assert d != spill_checksum(header, k + 1, v)
    assert d != spill_checksum(header, k, v, k[..., 0, 0], v[..., 0, 0])


# -- PagePool ---------------------------------------------------------------

def test_pool_alloc_free_refcount():
    pool = PagePool(6, 4)
    assert pool.num_free == 6 - RESERVED_PAGES
    a, b = pool.alloc(), pool.alloc()
    assert a != b and a >= RESERVED_PAGES and b >= RESERVED_PAGES
    assert pool.refcount(a) == 1 and not pool.needs_copy(a)
    pool.retain(a)
    assert pool.refcount(a) == 2 and pool.needs_copy(a)
    pool.release(a)
    assert pool.refcount(a) == 1 and not pool.needs_copy(a)
    pool.release(a)
    assert pool.refcount(a) == 0 and pool.num_free == 3
    with pytest.raises(ValueError, match="free/reserved"):
        pool.release(a)  # double free
    with pytest.raises(ValueError, match="free/reserved"):
        pool.release(SCRATCH_PAGE)
    with pytest.raises(ValueError, match="free/reserved"):
        pool.retain(a)


def test_pool_free_order_is_validated_permutation():
    with pytest.raises(ValueError, match="permutation"):
        PagePool(6, 4, free_order=[3, 4, 5])  # misses 2
    with pytest.raises(ValueError, match="permutation"):
        PagePool(6, 4, free_order=[0, 1, 2, 3])  # includes reserved
    pool = PagePool(6, 4, free_order=[5, 3, 4, 2])
    assert pool.alloc() == 5 and pool.alloc() == 3


def test_pool_lru_eviction_and_exhaustion():
    pool = PagePool(RESERVED_PAGES + 3, 4)
    pages = [pool.alloc() for _ in range(3)]
    assert pool.alloc() is None  # dry, nothing cached to evict
    k1 = prefix_page_keys([1, 2, 3, 4], 4)
    k2 = prefix_page_keys([5, 6, 7, 8], 4)
    pool.register_prefix(k1, pages[:1])
    pool.register_prefix(k2, pages[1:2])
    for p in pages:
        pool.release(p)
    assert pool.num_free == 1 and pool.num_cached == 2
    # a hit refreshes recency: k1 becomes most-recent, so the first
    # eviction under pressure drops k2, not k1
    hit = pool.match_prefix(k1)
    assert hit == pages[:1]
    pool.release(hit[0])
    got = {pool.alloc(), pool.alloc()}  # free page + evict k2
    assert got == {pages[1], pages[2]}
    assert pool.match_prefix(k2) == []      # evicted
    assert pool.match_prefix(k1) != []      # survived (refreshed)
    pool.release(pool._prefix[k1[0]])
    assert pool.alloc() is not None  # evicts k1, the last entry
    assert pool.num_cached == 0 and pool.alloc() is None


# -- engine: stored-once sharing, COW, out-of-pages -------------------------

def test_prefix_shared_pages_stored_once():
    """Two requests with the same prompt hold the SAME physical pages:
    the second admission allocates nothing and its prefill logits are
    bit-identical (the rows are literally the same memory)."""
    cfg = _cfg()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    eng = _engine(params, cfg, num_pages=10)
    prompt = [5, 7, 11, 13, 17, 19, 23, 29]  # 2 full pages of 4
    l0 = eng.prefill(0, prompt)
    free_before = eng.pool.num_free
    l1 = eng.prefill(1, prompt)
    assert eng._slot_pages[0] == eng._slot_pages[1]
    assert eng.pool.num_free == free_before  # zero new allocations
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    for p in eng._slot_pages[0]:
        assert eng.pool.refcount(p) == 3  # 2 slots + registry


def test_cow_does_not_perturb_sharing_request():
    """The acceptance contract: two requests share a partial last
    prompt page; both then append (triggering copy-on-write). The
    logits of each must be BIT-IDENTICAL to a run where it decodes
    alone — COW never mutates the shared original, and the registry's
    cached copy survives at refcount 1."""
    cfg = _cfg()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prompt = [5, 7, 11, 13, 17, 19]  # 1.5 pages of 4: partial page shared
    div_a, div_b = 31, 37            # divergent appended tokens

    def alone(slot, token):
        eng = _engine(params, cfg, num_pages=12)
        logits = eng.prefill(slot, prompt)
        assert eng.prepare_decode({slot: len(prompt)}) == []
        toks = [0, 0]
        toks[slot] = token
        active = jnp.asarray([i == slot for i in range(2)])
        step = eng.decode(jnp.asarray(toks, jnp.int32), active)
        return np.asarray(logits), np.asarray(step[slot])

    ref_pre_a, ref_a = alone(0, div_a)
    ref_pre_b, ref_b = alone(1, div_b)

    eng = _engine(params, cfg, num_pages=12)
    pre_a = eng.prefill(0, prompt)
    pre_b = eng.prefill(1, prompt)
    shared = eng._slot_pages[0][1]
    assert eng.pool.refcount(shared) == 3  # 2 slots + registry
    assert eng.prepare_decode({0: len(prompt), 1: len(prompt)}) == []
    # both slots COW'd the partial page to distinct private copies; the
    # registry keeps the pristine original
    assert eng._slot_pages[0][1] != shared
    assert eng._slot_pages[1][1] != shared
    assert eng._slot_pages[0][1] != eng._slot_pages[1][1]
    assert eng.pool.refcount(shared) == 1
    step = eng.decode(jnp.asarray([div_a, div_b], jnp.int32),
                      jnp.asarray([True, True]))
    np.testing.assert_array_equal(np.asarray(pre_a), ref_pre_a)
    np.testing.assert_array_equal(np.asarray(pre_b), ref_pre_b)
    np.testing.assert_array_equal(np.asarray(step[0]), ref_a)
    np.testing.assert_array_equal(np.asarray(step[1]), ref_b)
    # the cached prefix is still shareable after both divergences
    eng2_pages = eng.pool.match_prefix(
        prefix_page_keys(prompt, eng.page_size))
    assert len(eng2_pages) == 2 and eng2_pages[1] == shared


def test_prefill_raises_pool_exhausted_when_out_of_pages():
    """An admission the pool can't cover (even after LRU eviction)
    raises typed ``PoolExhausted`` — carrying need/free/cached — and
    leaks nothing: every transient reference is rolled back so the
    request can be retried after evictions."""
    cfg = _cfg()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    eng = _engine(params, cfg, num_pages=RESERVED_PAGES + 3,
                  prefix_sharing=False)
    assert eng.prefill(0, [5, 7, 11, 13, 17, 19, 23, 29]) is not None
    free_before = eng.pool.num_free
    with pytest.raises(PoolExhausted) as exc:
        eng.prefill(1, [2, 3, 4, 6, 8, 9, 10, 12])
    assert exc.value.need == 2
    assert exc.value.free == free_before
    assert exc.value.cached == 0
    assert eng.pool.num_free == free_before  # rollback, no leak
    eng.check_invariants()                   # books balance post-rollback
    # the retry is typed too — and still leak-free
    with pytest.raises(PoolExhausted):
        eng.prefill(1, [2, 3, 4, 6, 8, 9, 10, 12])
    assert eng.pool.num_free == free_before
    eng.free_slot(0)
    assert eng.pool.num_free == 3
    eng.check_invariants()


def test_page_demand_rejects_oversized_requests():
    cfg = _cfg()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    eng = _engine(params, cfg, num_pages=RESERVED_PAGES + 3)
    eng.page_demand(12)  # 3 pages: fits
    with pytest.raises(ValueError, match="pages"):
        eng.page_demand(13)  # 4 pages > 3 usable


def test_full_pool_pages_and_trace_programs():
    """``full_pool_pages`` is the pool in which every slot fits whole, and
    ``trace_programs`` hands out the engine's OWN prefill/decode jits at
    the shapes it runs them with — tracing runs nothing and leaves the
    cache alone."""
    cfg = _cfg()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    n = PagedDecodeEngine.full_pool_pages(2, S_MAX, 4)
    assert n == 2 * (S_MAX // 4) + RESERVED_PAGES
    eng = _engine(params, cfg, num_pages=n)
    eng.page_demand(S_MAX)
    cache = eng.cache
    traced = eng.trace_programs()
    assert set(traced) == {"prefill_32", "decode"}
    assert eng.cache is cache and eng.pool.num_free == n - RESERVED_PAGES
    logits = traced["decode"].out_info[1]
    assert logits.shape == (2, cfg.vocab_size)
    assert traced["prefill_32"].out_info[1].shape == (1, cfg.vocab_size)
