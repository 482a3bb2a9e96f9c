"""The block table is the host's (``PagedDecodeEngine``, class docstring).

``_slot_pages`` decides what a slot maps, ``_table`` is that decision in the
device leaf's layout, and ``cache.block_tables`` is a copy that is brought up
to date by ONE transfer before a decode, verify or tree-verify program is
launched. Held here:

- ``prepare_decode`` and ``free_slot`` leave the device leaf alone (no
  program, no transfer) and the next step's launch uploads once;
- a freed slot's row is on scratch by the time the decode program, which
  writes a row for every slot, goes through it: pages handed on to another
  request keep that request's rows;
- a scheduler that audits the device table against ``_slot_pages`` after
  every tick runs clean through preemption for want of pages, a copy-on-write
  clone of a registered partial last page, a finish and a re-admission into
  the freed slot in one tick, and a slot parked in the middle of a chunked
  prefill, and its streams are those of a pool in which none of that happens;
- without the audit the table costs at most one upload a tick.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (ContinuousBatchingScheduler, PagedDecodeEngine,
                              Request)
from apex_tpu.serving.cache import (NULL_PAGE, RESERVED_PAGES, SCRATCH_PAGE,
                                    audit_block_tables)

EOS = -1
MAX_LEN = 32
PAGE = 4


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True, hidden_dropout=0.0)
    return cfg, init_gpt(jax.random.PRNGKey(0), cfg)


def _engine(model, num_slots=2, num_pages=None, **kw):
    cfg, params = model
    if num_pages is None:
        num_pages = PagedDecodeEngine.full_pool_pages(num_slots, MAX_LEN,
                                                      PAGE)
    return PagedDecodeEngine(params, cfg, num_slots=num_slots,
                             max_len=MAX_LEN, num_pages=num_pages,
                             page_size=PAGE, cache_dtype=jnp.float32,
                             buckets=(16, 32), **kw)


def _device_rows(eng):
    return np.asarray(eng.cache.block_tables)


def test_host_writes_reach_the_device_in_one_upload_at_launch(model):
    eng = _engine(model)
    prompt = [5, 7, 11, 13, 17, 19, 23, 29]          # two whole pages
    eng.prefill(0, prompt)
    eng.prefill(1, prompt[:6])
    # a prefill program wrote its row on the device and the host mirrored it
    assert not eng._table_dirty
    np.testing.assert_array_equal(_device_rows(eng), eng._table)
    before = _device_rows(eng)
    assert eng.prepare_decode({0: 8, 1: 6}) == []
    leaf = eng.cache.block_tables   # the clone is a program: it copies a page
    eng.free_slot(1)
    # slot 0 crossed a boundary, slot 1 cloned its registered partial page
    # and was freed: three host writes, and the device table is as it was
    assert eng.stats.page_boundaries == 1 and eng.stats.cow_copies == 1
    assert eng.cache.block_tables is leaf
    np.testing.assert_array_equal(_device_rows(eng), before)
    assert eng._table_dirty and eng.stats.block_table_uploads == 0
    assert eng._table[0, 2] == eng._slot_pages[0][2]
    assert (eng._table[1] == SCRATCH_PAGE).all()
    eng.decode(jnp.asarray([3, 0], jnp.int32), jnp.asarray([True, False]))
    assert eng.stats.block_table_uploads == 1 and not eng._table_dirty
    np.testing.assert_array_equal(_device_rows(eng), eng._table)
    # nothing changed on the host: the next launch uploads nothing
    assert eng.prepare_decode({0: 9}) == []
    eng.decode(jnp.asarray([4, 0], jnp.int32), jnp.asarray([True, False]))
    assert eng.stats.block_table_uploads == 1
    eng.check_invariants()
    # freeing a slot that maps nothing is no write
    eng.free_slot(1)
    assert not eng._table_dirty


def test_freed_row_is_on_scratch_when_the_decode_program_writes(model):
    """Slot 1's request ends and all its pages go to slot 0's next prompt.
    The decode program then writes a row for slot 1 too, at the length the
    freed request had, through slot 1's table row: were that row still the
    freed one on the device, the write would land in a page that is now
    slot 0's, and slot 0's next step would read it."""
    prompt = list(range(40, 40 + 3 * PAGE + 2))     # 4 pages, 2 rows to go

    def two_steps(reuse):
        eng = _engine(model, num_pages=RESERVED_PAGES + 4,
                      prefix_sharing=False)
        if reuse:
            eng.prefill(1, list(range(2, 2 + 3 * PAGE + 1)))
            eng.free_slot(1)
        eng.prefill(0, prompt)                      # every usable page
        steps = []
        for t in range(2):                          # no boundary crossed
            assert eng.prepare_decode({0: len(prompt) + t}) == []
            logits = eng.decode(jnp.asarray([7 + t, 9], jnp.int32),
                                jnp.asarray([True, False]))
            steps.append(np.asarray(logits[0]))
        eng.check_invariants()
        return steps, eng

    want, fresh = two_steps(reuse=False)
    got, eng = two_steps(reuse=True)
    assert fresh.stats.block_table_uploads == 0
    assert eng.stats.block_table_uploads == 1       # the freed row, once
    assert int(eng.cache.lengths[1]) == 3 * PAGE + 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _reqs(n, prompt_len, max_new, shared=False):
    return [Request(prompt=tuple(range(3, 3 + prompt_len)) if shared
                    else tuple(range(3 + 7 * i, 3 + 7 * i + prompt_len)),
                    max_new_tokens=max_new,
                    temperature=0.0 if i % 2 else 0.8, seed=i)
            for i in range(n)]


def _run(model, reqs, audit, chunk_tokens=None, **engine_kw):
    eng = _engine(model, **engine_kw)
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS, audit=audit,
                                        chunk_tokens=chunk_tokens)
    for r in reqs:
        sched.submit(r)
    return sched, sched.run()


SCENARIOS = {
    # 5-token prompts + 8 new tokens want 4 pages each; the pool has 5
    "preempted_for_want_of_pages": dict(
        reqs=_reqs(2, 5, 8), engine=dict(num_pages=RESERVED_PAGES + 5),
        happened=lambda st: st.preemptions > 0),
    # the same 6-token prompt twice: the partial second page is registered
    # and shared, and each slot's first append clones it
    "cow_on_registered_partial_page": dict(
        reqs=_reqs(3, 6, 5, shared=True), engine={},
        happened=lambda st: st.cow_copies >= 2),
    # five requests over two slots: a slot finishes in `commit` and the next
    # tick's `admit` prefills into it before any decode has uploaded
    "finish_and_readmit_into_the_freed_slot": dict(
        reqs=_reqs(5, 5, 5), engine={},
        happened=lambda st: st.evictions == 5),
    # 13-token prompts in chunks of 4: three ticks with the slot's row parked
    # on scratch while the other slot decodes
    "parked_mid_chunked_prefill": dict(
        reqs=_reqs(3, 13, 6), engine={}, chunk_tokens=4,
        happened=lambda st: st.prefill_chunks >= 9),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_audited_run_keeps_device_table_equal_to_slot_pages(model, name):
    sc = SCENARIOS[name]
    chunk = sc.get("chunk_tokens")
    want = _run(model, sc["reqs"], audit=False, chunk_tokens=chunk,
                num_slots=len(sc["reqs"]), prefix_sharing=False)[1]
    sched, got = _run(model, sc["reqs"], audit=True, chunk_tokens=chunk,
                      **sc["engine"])
    assert sc["happened"](sched.stats), sched.stats
    assert got == want
    eng = sched.engine
    assert all(not p for p in eng._slot_pages)
    assert (eng._table == SCRATCH_PAGE).all()
    # the same run without the audit (whose own upload hides a late one)
    # gives the same streams, and the table costs at most one upload a tick
    plain, streams = _run(model, sc["reqs"], audit=False, chunk_tokens=chunk,
                          **sc["engine"])
    assert streams == want
    st = plain.stats
    assert 0 < st.block_table_uploads <= st.plain_ticks + st.spec_ticks
    assert st.page_boundaries > 0
    plain.engine.check_invariants()


def test_parked_row_stays_on_scratch_on_host_and_device(model):
    """Between ``begin_chunk_prefill`` and the final chunk the slot holds
    pages and its row maps none of them, on either side."""
    eng = _engine(model)
    prompt = list(range(3, 16))
    state = eng.begin_chunk_prefill(0, prompt)
    assert eng._slot_pages[0] and (eng._table[0] == SCRATCH_PAGE).all()
    eng.chunk_prefill(0, prompt[:4], 0, state, 4, final=False)
    assert (eng._table[0] == SCRATCH_PAGE).all() and not eng._table_dirty
    eng.check_invariants()
    eng.chunk_prefill(0, prompt[4:8], 4, state, 4, final=False)
    eng.chunk_prefill(0, prompt[8:12], 8, state, 4, final=False)
    eng.chunk_prefill(0, prompt[12:], 12, state, 4, final=True)
    eng.finish_chunk_prefill(0, state)
    assert not eng._table_dirty
    n = len(eng._slot_pages[0])
    assert eng._table[0, :n].tolist() == eng._slot_pages[0]
    assert (eng._table[0, n:] == NULL_PAGE).all()
    audit_block_tables(eng.cache.block_tables, eng._slot_pages)


def test_upload_keeps_the_leafs_placement(model):
    """The uploaded leaf is placed as the one it replaces, so the step
    program sees the arguments it was compiled for: one executable before
    and after the first upload."""
    eng = _engine(model, prefix_sharing=False)
    eng.prefill(0, [5, 7, 11])
    eng.prepare_decode({0: 3})
    assert not eng._table_dirty                 # row 3 is in the first page
    eng.decode(jnp.asarray([3, 0], jnp.int32), jnp.asarray([True, False]))
    committed = eng.cache.block_tables.committed
    compiled = eng._decode._cache_size()
    eng.prepare_decode({0: 4})                  # a boundary
    eng.decode(jnp.asarray([4, 0], jnp.int32), jnp.asarray([True, False]))
    assert eng.stats.block_table_uploads == 1
    assert eng.cache.block_tables.committed == committed
    assert eng._decode._cache_size() == compiled
