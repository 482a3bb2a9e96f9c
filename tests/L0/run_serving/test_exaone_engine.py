"""The ``exaone_moe`` family through ``PagedDecodeEngine`` and
``ContinuousBatchingScheduler``: the full layer's page pool walked by the
host's block table, the sliding layers' ``window`` rows a slot in a cyclic
page table that no host code touches, both read in place by the paged decode
kernel, against the benchmark's plain reference (every layer keeps every
position, the window a band mask); the host's features over the full pool
(prefix sharing, copy-on-write, preemption by requeue); and the seam: what
needs a core the model does not bring is refused by name and by what is
missing for THESE pools."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import exaone_moe
from apex_tpu.serving import (ContinuousBatchingScheduler,
                              DisaggregatedRouter, PagedDecodeEngine, Request,
                              Tracer)
from apex_tpu.serving.cache import (RESERVED_PAGES, WindowKVCache,
                                    audit_block_tables, ring_page)
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
SLOTS, MAX_LEN, PAGE = 3, 128, 4
RING = 3                    # cdiv(8 + 4 - 1, 4): a window of 8, pages of 4
ROW = 2 * 16                # two K/V heads of 16


@pytest.fixture(scope="module")
def tiny():
    cfg = exaone_moe.exaone_moe_tiny()
    return cfg, exaone_moe.init(jax.random.PRNGKey(3), cfg)


def engine(cfg, params, slots=SLOTS, num_pages=None, **kw):
    kw.setdefault("buckets", (16, 32, 64, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    if num_pages is None:
        num_pages = PagedDecodeEngine.full_pool_pages(slots, MAX_LEN, PAGE)
    return PagedDecodeEngine(params, cfg, num_slots=slots, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=PAGE, **kw)


def teacher_forced(eng, slot, prompt, cont):
    rows = [np.asarray(eng.prefill(slot, prompt))[0]]
    active = jnp.arange(eng.num_slots) == slot
    for i, t in enumerate(cont):
        assert eng.prepare_decode({slot: len(prompt) + i}) == []
        tokens = jnp.zeros((eng.num_slots,), jnp.int32).at[slot].set(int(t))
        rows.append(np.asarray(eng.decode(tokens, active))[slot])
    return np.stack(rows)


def draw(seed, *lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 512, n) for n in lengths]


def run(eng, requests, **kw):
    sched = ContinuousBatchingScheduler(eng, eos_id=-1, **kw)
    rids = [sched.submit(r) for r in requests]
    sched.run()
    assert all(sched.outcomes[r].error is None for r in rids)
    return [list(sched.outcomes[r].tokens) for r in rids], sched


def streams(eng, requests, **kw):
    return run(eng, requests, **kw)[0]


def test_prefill_then_three_windows_of_decode_match_the_reference(tiny):
    """The headline: logits of prefill and of 3 x ``window`` decode steps
    through both pools against the plain reference's full forward (every
    ring row overwritten more than once: 24 steps, a cycle of 12 rows)."""
    from tests.L0.run_models.test_exaone_moe import sizes_of

    ref = harness.load_module("reference", "k_exaone_236b_a23b", BENCH)
    cfg, params = tiny
    prompt, cont = draw(0, 37, 3 * cfg.window)
    eng = engine(cfg, params)
    assert isinstance(eng.cache, WindowKVCache) and not eng.recurrent
    # TWO pools: the one full layer's, and the four sliding layers' cycles
    assert eng.cache.k.shape == eng.cache.v.shape == (
        1, eng.pool.num_pages, PAGE, ROW)
    assert eng.cache.wk.shape == eng.cache.wv.shape == (
        4, RESERVED_PAGES + SLOTS * RING, PAGE, ROW)
    ids = jnp.asarray(np.concatenate([prompt, cont]))
    with jax.default_matmul_precision("highest"):
        got = teacher_forced(eng, 1, prompt, cont)
        want = ref.logits_at(params, sizes_of(cfg), ids,
                             jnp.arange(len(prompt) - 1, len(ids)))
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert eng.check_invariants()
    # the full pool: the prompt's and the decoded tokens' rows, nothing else
    pages = eng._slot_pages[1]
    rows = np.asarray(eng.cache.k[:, pages]).reshape(1, -1, ROW)
    assert np.all(np.any(rows[:, :61] != 0, -1)) and not np.any(rows[:, 61:])
    audit_block_tables(eng.cache.block_tables, eng._slot_pages)


def test_the_cycle_is_the_formula_and_holds_the_last_rows_only(tiny):
    """After a prompt of 37 and 24 decode steps (61 positions): logical page
    ``j`` of slot 1 stands in physical page ``2 + 1 * 3 + j % 3``, the cycle
    holds positions 49 .. 60 (the last three logical pages; the window reads
    the last 8 of them), rows of the full pool's K at the same positions in
    every sliding layer's own projection, and of the other slots' cycles only
    the row AT their length (0) was ever written (the step parks an inactive
    slot's write there, in its own cycle, where no mask admits it)."""
    cfg, params = tiny
    prompt, cont = draw(0, 37, 24)
    eng = engine(cfg, params)
    with jax.default_matmul_precision("highest"):
        teacher_forced(eng, 1, prompt, cont)
        ids = jnp.asarray(np.concatenate([prompt, cont]))
        _, _, (wk, wv), _ = exaone_moe.prefill_layers(
            params, cfg, exaone_moe.embed(params, ids),
            jnp.ones(ids.shape, jnp.int32))
    ring = np.asarray(eng.cache.wk)
    assert not ring[:, :RESERVED_PAGES].any()       # null and scratch
    for other in (0, 2):
        mine = ring[:, RESERVED_PAGES + other * RING:][:, :RING].reshape(
            4, RING * PAGE, ROW)
        assert mine[:, 0].any() and not mine[:, 1:].any()
    for logical in (12, 13, 14, 15):        # positions 48 .. 63
        page = int(ring_page(1, logical, RING))
        assert page == RESERVED_PAGES + RING + logical % RING
    for pos in range(49, 61):       # page 12's rows were page 15's turn
        page = RESERVED_PAGES + RING + (pos // PAGE) % RING
        np.testing.assert_allclose(ring[:, page, pos % PAGE],
                                   np.asarray(wk)[:, pos], atol=2e-5)
    # what a window layer holds of a slot does not grow with the context
    per_slot = RING * PAGE * ROW * 4
    assert eng.cache.wk[0].nbytes == (RESERVED_PAGES + SLOTS * RING) \
        * PAGE * ROW * 4
    assert eng._window_bytes == 2 * 4 * per_slot


def test_a_prompt_shorter_than_the_cycle_and_one_that_wraps_it(tiny):
    """Prompts of 3 (less than a page), 12 (the cycle exactly) and 50 (four
    turns and a bit): each followed by decode across a page boundary."""
    cfg, params = tiny
    for n in (3, 12, 50):
        prompt, cont = draw(n, n, 10)
        eng = engine(cfg, params)
        with jax.default_matmul_precision("highest"):
            got = teacher_forced(eng, 0, prompt, cont)
            want = np.asarray(exaone_moe.apply(params, cfg, jnp.asarray(
                np.concatenate([prompt, cont]))))[n - 1:]
        np.testing.assert_allclose(got, want, atol=3e-4)


def test_a_reused_slot_reads_nothing_of_its_predecessors_cycle(tiny):
    """A slot is freed and prefilled again with a SHORTER prompt: the cycle
    still holds the predecessor's rows where the new prompt wrote none, and
    NaN poured over every row outside the new stream's own changes no bit."""
    cfg, params = tiny
    old, new, cont = draw(1, 61, 6, 9)
    fresh = teacher_forced(engine(cfg, params), 2, new, cont)
    eng = engine(cfg, params)
    teacher_forced(eng, 2, old, cont)
    eng.free_slot(2)
    np.testing.assert_array_equal(teacher_forced(eng, 2, new, cont), fresh)
    # the same with the predecessor's rows turned to NaN before the reuse
    eng = engine(cfg, params)
    teacher_forced(eng, 2, old, cont)
    eng.free_slot(2)
    eng.cache = eng.cache._replace(
        wk=jnp.full_like(eng.cache.wk, jnp.nan),
        wv=jnp.full_like(eng.cache.wv, jnp.nan))
    np.testing.assert_array_equal(teacher_forced(eng, 2, new, cont), fresh)


def test_an_inactive_slot_is_untouched_while_another_decodes(tiny):
    cfg, params = tiny
    a, b, cont = draw(3, 20, 33, 14)
    eng = engine(cfg, params)
    first = teacher_forced(eng, 0, a, cont[:1])[1]
    eng.free_slot(0)
    eng.prefill(0, a)
    teacher_forced(eng, 2, b, cont)         # slot 0 inactive all along
    assert int(eng.cache.lengths[0]) == 20
    assert eng.prepare_decode({0: 20}) == []
    tokens = jnp.zeros((SLOTS,), jnp.int32).at[0].set(int(cont[0]))
    again = np.asarray(eng.decode(tokens, jnp.arange(SLOTS) == 0))[0]
    np.testing.assert_allclose(again, first, atol=2e-5)


def test_the_host_never_writes_or_uploads_a_window_table(tiny):
    """A steady tick is ``jit_decode`` + the checked sampler and ONE
    read-back; the only table that goes up is the full pool's, at most once
    a tick; the window pool's table is no array at all."""
    cfg, params = tiny
    eng = engine(cfg, params)
    assert set(eng.cache._fields) == {"k", "v", "lengths", "block_tables",
                                      "wk", "wv", "counters"}
    reqs = [Request(prompt=tuple(int(t) for t in p), max_new_tokens=30,
                    temperature=(0.0, 0.8)[i % 2], seed=i)
            for i, p in enumerate(draw(8, 9, 22, 17))]
    _, sched = run(eng, reqs, audit=True)
    ticks = sched.stats.plain_ticks
    assert sched.stats.spec_ticks == 0
    assert 0 < eng.stats.block_table_uploads <= ticks
    assert eng.stats.page_boundaries > 0
    assert sched.stats.sampler_waits == ticks + len(reqs)
    assert eng.read_counters()["moe_steps"].tolist() == [ticks]


# -- the host's features over the full pool --------------------------------------

def shared_prefix_requests():
    head = tuple(int(t) for t in draw(5, 22)[0])
    tails = [tuple(int(t) for t in d) for d in draw(6, 9, 17, 3)]
    return [Request(prompt=head + tail, max_new_tokens=12,
                    temperature=(0.0, 0.8, 0.0)[i], seed=i)
            for i, tail in enumerate(tails)] + [
        Request(prompt=head + tails[0], max_new_tokens=9, temperature=0.0,
                seed=9)]            # the first prompt again: a partial page


def test_prefix_sharing_over_the_full_pool_falls_out_of_a_whole_prefill(tiny):
    """Requests that open with the same 22 tokens share its five full pages
    of the FULL layer; every prefill still runs the whole prompt, so each
    slot's cycle is rebuilt from its own prompt and no ring page is ever
    shared or copied. The streams are those of an engine that shares
    nothing."""
    cfg, params = tiny
    reqs = shared_prefix_requests()
    plain = streams(engine(cfg, params, slots=2, prefix_sharing=False), reqs)
    eng = engine(cfg, params, slots=2)
    assert eng.prefix_sharing
    assert streams(eng, reqs, audit=True) == plain
    assert eng.pool.num_cached > 0 and eng.stats.cow_copies > 0
    assert eng.check_invariants()
    # the page copy clones pages of the pool the table walks and leaves the
    # cycles alone
    before = np.asarray(eng.cache.wk)
    eng.cache = eng._copy(eng.cache, jnp.int32(5), jnp.int32(6))
    np.testing.assert_array_equal(np.asarray(eng.cache.wk), before)
    np.testing.assert_array_equal(np.asarray(eng.cache.k[:, 6]),
                                  np.asarray(eng.cache.k[:, 5]))


def test_preemption_by_requeue_over_a_small_full_pool(tiny):
    """A full pool too small for both requests to finish side by side: one
    is preempted, its pages released, requeued and prefilled again (its cycle
    with it); the streams are a roomy pool's."""
    cfg, params = tiny
    reqs = [Request(prompt=tuple(int(t) for t in p), max_new_tokens=40,
                    temperature=0.0, seed=i)
            for i, p in enumerate(draw(7, 30, 28))]
    roomy = streams(engine(cfg, params, slots=2), reqs)
    small = engine(cfg, params, slots=2, num_pages=2 + 26)
    assert streams(small, reqs, audit=True) == roomy
    assert small.stats.preemptions > 0
    assert small.check_invariants()


def test_streams_do_not_depend_on_which_slot_or_pages_serve_them(tiny):
    cfg, params = tiny
    reqs = [Request(prompt=tuple(int(t) for t in p), max_new_tokens=20,
                    temperature=(0.0, 0.8)[i % 2], seed=i)
            for i, p in enumerate(draw(11, 14, 35, 9, 21))]
    a = streams(engine(cfg, params, slots=3), reqs)
    order = list(range(RESERVED_PAGES, PagedDecodeEngine.full_pool_pages(
        2, MAX_LEN, PAGE)))[::-1]
    b = streams(engine(cfg, params, slots=2, free_order=order), reqs)
    assert a == b


# -- the seam ----------------------------------------------------------------------

def test_the_config_states_the_seam_and_the_engine_names_no_family(tiny):
    from apex_tpu.serving import scheduler
    from apex_tpu.serving.decode import model_cores

    cfg, params = tiny
    assert model_cores(cfg) and not cfg.recurrent and not cfg.latent
    assert (cfg.window, cfg.window_layers, cfg.kv_layers) == (8, 4, 1)
    assert not hasattr(cfg, "state_shapes")
    eng = engine(cfg, params)
    assert eng.model_cores and eng._exec_stats() == {}
    assert "exaone" not in open(scheduler.__file__).read()
    from apex_tpu.serving.cache import MODEL_POOLS, init_window_cache
    assert MODEL_POOLS[cfg.pools] == (init_window_cache,
                                      "a full pool and a window pool")


REFUSED = [
    ("spec_k", dict(spec_k=2), "roll a rejected draft back"),
    ("tree_spec", dict(spec_k=2, tree_spec=True), "an ancestor mask"),
    ("int8 pool", dict(cache_dtype=jnp.int8), "no per-page scale"),
    ("host tier", dict(host_tier="a registry"), "chunked-prefill core"),
    ("compute_dtype", dict(compute_dtype=jnp.bfloat16), "fix their precision"),
]


@pytest.mark.parametrize("name, kw, needs", REFUSED,
                         ids=[n for n, _, _ in REFUSED])
def test_engine_refuses_by_name_what_needs_a_core_it_lacks(tiny, name, kw,
                                                          needs):
    cfg, params = tiny
    with pytest.raises(ValueError,
                       match="over a full pool and a window pool") as e:
        engine(cfg, params, **kw)
    assert name.split()[0] in str(e.value) and needs in str(e.value)
    assert "ExaoneMoeConfig" in str(e.value)
    assert "latent" not in str(e.value)


def test_the_rest_is_refused_where_it_is_asked_for(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match=r"chunked prefill \(chunk_tokens=\)"
                       r".*window pool.*out of its pools at prompt length"):
        ContinuousBatchingScheduler(eng, eos_id=-1, chunk_tokens=16)
    quantized = {**params, "embedding": {"word": {
        **params["embedding"]["word"], "scale": jnp.ones((8,))}}}
    with pytest.raises(ValueError, match="weight-only int8.*window pool"):
        engine(cfg, quantized)
    with pytest.raises(ValueError, match="page transfer is not offered over "
                       "a full pool and a window pool.*ExaoneMoeConfig"):
        DisaggregatedRouter(engine(cfg, params), engine(cfg, params),
                            eos_id=-1)


def test_a_latent_pool_is_refused_in_its_own_words():
    """The refusals name the pools of the model asked about, in the words of
    ``serving.cache.MODEL_POOLS``; what a feature would need is one text."""
    from apex_tpu.models import deepseek

    cfg = deepseek.deepseek_tiny()
    params = deepseek.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="spec_k is not offered over a "
                       "latent pool.*k\\+1 rows a slot in each of its pools"):
        PagedDecodeEngine(params, cfg, num_slots=2, max_len=32, num_pages=8,
                          page_size=16, cache_dtype=jnp.float32, spec_k=2)


def test_the_prefill_span_says_what_it_wrote(tiny):
    cfg, params = tiny
    eng = engine(cfg, params, tracer=Tracer())
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    for n in (20, 37):
        sched.submit(Request(prompt=tuple(range(2, 2 + n)), max_new_tokens=3,
                             temperature=0.0, seed=n))
    sched.run()
    said = [dict(e.args) for e in eng.tracer.events if e.name == "prefill"]
    # the first prompt's five full pages (tokens 2..21) are the second's
    assert [p["shared_pages"] for p in said] == [0, 5]
    # a slot's cycle: 3 pages of 4 rows x 32 float32, K and V, 4 layers,
    # whatever the prompt's length and whatever was shared
    assert [p["window_bytes"] for p in said] == [2 * 4 * 3 * 4 * 32 * 4] * 2
    assert all("state_bytes" not in p and "latent_bytes" not in p
               for p in said)
