"""A model whose attention PICKS the rows it reads (``models.glm_next``: an
indexer's cache beside ONE latent pool, under the pool's own block table,
beside Kimi Delta Attention's state a slot and four residual streams) through
``PagedDecodeEngine`` and ``ContinuousBatchingScheduler``: against the
benchmark's plain reference; what a slot's prefill resets (state, pages, index
keys, index tail); preemption by requeue; each refusal by name; three byte
counts on one span; and the caches of the families that state fewer facts,
built as before."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import (ContinuousBatchingScheduler, PagedDecodeEngine,
                              Request, Tracer)
from apex_tpu.serving.cache import (HybridKVCache, LatentKVCache,
                                    audit_block_tables)
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
SLOTS, MAX_LEN, PAGE = 3, 128, 4


@pytest.fixture(scope="module")
def tiny():
    """(reference, sizes, config object, float32 weights the reference made)
    from the configuration file's rehearsal sizes, through the runner's own
    ``model_config``: float32 so that engine and reference agree to
    rounding."""
    ref = harness.load_module("reference", "glm_5_3_flash", BENCH)
    runner = harness.load_module("runners", "glm_serve", BENCH)
    config = harness.rehearsal_view(harness.load_json(
        BENCH, "configs", "glm_5_3_flash.json"))
    sz = {**ref.sizes_of(config), "cache_dtype": "float32",
          "positions": MAX_LEN}
    cfg = runner.model_config(config, sz)
    served = jax.jit(lambda key: ref.make_weights(sz, key))(ref.seed_key(3))
    return ref, sz, cfg, jax.tree.map(
        lambda a: a.astype(jnp.float32), served)


def engine(cfg, params, slots=SLOTS, num_pages=None, page=PAGE, **kw):
    kw.setdefault("buckets", (16, 32, 64, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("prefix_sharing", False)
    if num_pages is None:
        num_pages = PagedDecodeEngine.full_pool_pages(slots, MAX_LEN, page)
    return PagedDecodeEngine(params, cfg, num_slots=slots, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=page, **kw)


def teacher_forced(eng, slot, prompt, cont):
    """Prefill ``prompt`` into ``slot``, then decode ``cont`` token by
    token: the logits rows that predict cont[0], cont[1], ..., and one
    more."""
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
    return [list(sched.outcomes[r].tokens) for r in rids]


def test_prefill_then_forty_decode_steps_match_the_references_forward(tiny):
    ref, sz, cfg, params = tiny
    prompt, cont = draw(0, 37, 40)
    eng = engine(cfg, params)
    assert eng.recurrent and eng.model_cores and eng._latent
    cache = eng.cache
    assert isinstance(cache, HybridKVCache) and cache.v is None
    assert cache.k.shape == (1, eng.pool.num_pages, PAGE, 128)
    assert cache.state.shape == (4, SLOTS, 4, 16, 16)
    assert cache.conv.shape == (4, SLOTS, 3, 192)
    # ONE key a page of 4 under the pool's page ids, and a tail a slot
    assert cache.index["rows"].shape == (1, eng.pool.num_pages, 1, 16)
    assert cache.index["tail"].shape == (1, SLOTS, 3, 16)
    assert cache.index["tail"].dtype == cache.state.dtype == jnp.float32
    assert len(jax.tree.leaves(cache)) == 5 + 2 + 5
    got = teacher_forced(eng, 1, prompt, cont)
    ids = jnp.asarray(np.concatenate([prompt, cont]))
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(params, sz, ids,
                             jnp.arange(len(prompt) - 1, len(ids)))
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert eng.check_invariants()
    eng.sync_table()
    assert audit_block_tables(eng.cache.block_tables, eng._slot_pages)
    assert len(eng._slot_pages[1]) == -(-(37 + 40) // PAGE)
    # the other slots' state, index keys and tails were never written
    assert not np.any(np.asarray(eng.cache.state[:, 0]))
    assert not np.any(np.asarray(eng.cache.index["tail"][:, 2]))
    mine = set(eng._slot_pages[1]) | {1}            # and the scratch page
    rows = np.asarray(eng.cache.index["rows"][0])
    assert all(p in mine for p in range(rows.shape[0]) if rows[p].any())
    assert 0 < eng.stats.block_table_uploads <= 41
    counters = eng.read_counters()
    assert counters["moe_steps"].tolist() == [40]
    # every step past the top-k of 16 positions attends 16 + its tail + 1
    assert counters["dsa_rows_mapped"].tolist() == [sum(range(38, 78))]
    assert counters["dsa_rows_read"].tolist() == [
        sum(16 + t % 4 + 1 for t in range(37, 77))]


def test_a_freed_slot_reads_nothing_of_its_predecessor(tiny):
    """A slot's state, tails and index tail are written whole by its prefill,
    and its pages and their index keys are its own: after another request has
    lived in the slot, a second one gives what a fresh engine gives, bit for
    bit."""
    _, _, cfg, params = tiny
    first, second = draw(1, 50, 6), draw(2, 21, 30)
    used = engine(cfg, params)
    teacher_forced(used, 0, *first)
    used.free_slot(0)
    assert used._slot_pages[0] == [] and used.check_invariants()
    got = teacher_forced(used, 0, *second)
    want = teacher_forced(engine(cfg, params), 0, *second)
    np.testing.assert_array_equal(got, want)


def test_an_inactive_slot_keeps_all_it_holds_while_another_decodes(tiny):
    _, _, cfg, params = tiny
    a, b = draw(3, 30, 25)
    eng = engine(cfg, params)
    eng.prefill(0, a)
    held = lambda: jax.tree.map(np.asarray, (
        eng.cache.state[:, 0], eng.cache.conv[:, 0],
        eng.cache.index["tail"][:, 0],
        eng.cache.index["rows"][:, np.asarray(eng._slot_pages[0])],
        eng.cache.k[:, np.asarray(eng._slot_pages[0])].reshape(
            -1, 128)[:30]))
    before = held()
    teacher_forced(eng, 2, b, draw(4, 9)[0])
    for x, y in zip(before, held()):
        np.testing.assert_array_equal(x, y)
    assert int(eng.cache.lengths[0]) == 30


def test_the_same_prompt_through_two_bucket_sets_gives_the_same_logits(tiny):
    """37 tokens padded to 64 and to 128: the pad is never attended, picked
    or pooled into a key that counts."""
    _, _, cfg, params = tiny
    prompt, cont = draw(5, 37, 9)
    a = teacher_forced(engine(cfg, params, buckets=(64, 128)), 2, prompt,
                       cont)
    b = teacher_forced(engine(cfg, params, buckets=(128,)), 2, prompt, cont)
    np.testing.assert_allclose(a, b, atol=2e-5)


def test_scheduler_streams_are_the_references_greedy_tokens(tiny):
    """Five requests over three slots (slots turn over, admissions run
    beside decode): every greedy stream is the reference's argmax where its
    margin is not a tie; the seeded samplers run beside them."""
    ref, sz, cfg, params = tiny
    eng = engine(cfg, params)
    sched = ContinuousBatchingScheduler(eng, eos_id=-1, audit=True)
    prompts = [tuple(int(t) for t in p) for p in draw(6, 9, 40, 17, 70, 25)]
    rids = [sched.submit(Request(prompt=p, max_new_tokens=6,
                                 temperature=(0.0, 0.8)[i % 2], seed=i))
            for i, p in enumerate(prompts)]
    sched.run()
    scorer = ref.Scorer(sz, 3)
    scorer.params = params
    for i, (rid, prompt) in enumerate(zip(rids, prompts)):
        out = sched.outcomes[rid]
        assert out.error is None and len(out.tokens) == 6
        if i % 2 == 0:
            gaps, _ = scorer.gaps(prompt, list(out.tokens))
            assert float(gaps.max()) < 1e-3
    assert eng.check_invariants()


def test_preemption_by_requeue_over_a_small_pool(tiny):
    """A pool too small for both requests to finish side by side: one is
    preempted, its pages (and their index keys with them) released, requeued
    and prefilled again; the streams are a roomy pool's."""
    _, _, cfg, params = tiny
    reqs = [Request(prompt=tuple(int(t) for t in p), max_new_tokens=24,
                    temperature=0.0, seed=i)
            for i, p in enumerate(draw(7, 14, 12))]
    roomy = run(engine(cfg, params, slots=2), reqs)
    small = engine(cfg, params, slots=2, num_pages=2 + 13)
    assert run(small, reqs, audit=True) == roomy
    assert small.stats.preemptions > 0
    assert small.check_invariants()


def test_the_config_states_three_facts_and_the_engine_names_no_family(tiny):
    from apex_tpu.serving import cache, scheduler
    from apex_tpu.serving.decode import model_cores

    _, _, cfg, params = tiny
    assert model_cores(cfg) and cfg.recurrent and cfg.latent and cfg.indexed
    assert not hasattr(cfg, "pools")
    assert cfg.state_shapes(5) == ((4, 5, 4, 16, 16), (4, 5, 3, 192))
    assert cfg.index_shapes(5, 40, 8) == ((1, 40, 2, 16), (1, 5, 3, 16))
    assert cfg.index_bytes_per_page(8, 2) == 2 * 16 * 2
    assert cfg.state_bytes_per_slot() == 4 * (
        4 * (4 * 16 * 16 + 3 * 192) + 3 * 16)
    for module in (scheduler, cache):
        text = open(module.__file__).read()
        assert "glm" not in text.replace("apex_tpu.models.glm_next", "")


REFUSED = [
    ("prefix_sharing", dict(prefix_sharing=True), "an indexed pool"),
    ("spec_k", dict(spec_k=2), "recurrent layers"),
    ("tree_spec", dict(spec_k=2, tree_spec=True), "recurrent layers"),
    ("int8 pool", dict(cache_dtype=jnp.int8), "recurrent layers"),
    ("host tier", dict(host_tier="a registry"), "recurrent layers"),
    ("compute_dtype", dict(compute_dtype=jnp.bfloat16), "recurrent layers"),
]


@pytest.mark.parametrize("name, kw, over", REFUSED,
                         ids=[n for n, _, _ in REFUSED])
def test_engine_refuses_by_name(tiny, name, kw, over):
    """Sharing a page (and so copying it on the first write) is refused over
    the indexed pool, by that name, before any other refusal speaks; the rest
    as for any model with recurrent layers."""
    _, _, cfg, params = tiny
    with pytest.raises(ValueError, match=over) as e:
        engine(cfg, params, **kw)
    assert name.split()[0] in str(e.value)
    assert "GlmNextConfig" in str(e.value)


def test_the_rest_is_refused_where_it_is_asked_for(tiny):
    from apex_tpu.serving import DisaggregatedRouter

    _, _, cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match=r"chunked prefill \(chunk_tokens=\)"
                       r".*recurrent layers"):
        ContinuousBatchingScheduler(eng, eos_id=-1, chunk_tokens=16)
    with pytest.raises(ValueError, match="page transfer.*an indexed pool"):
        DisaggregatedRouter(eng, engine(cfg, params), eos_id=-1)


def test_one_prefill_span_carries_state_latent_and_index_bytes(tiny):
    _, _, cfg, params = tiny
    eng = engine(cfg, params, tracer=Tracer())
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    for n in (9, 21):
        sched.submit(Request(prompt=tuple(range(2, 2 + n)), max_new_tokens=3,
                             temperature=0.0, seed=n))
    sched.run()
    said = [dict(e.args) for e in eng.tracer.events if e.name == "prefill"]
    assert [set(p) for p in said] == [
        {"bucket", "prompt_tokens", "shared_pages", "page_size",
         "state_bytes", "latent_bytes", "index_bytes"}] * 2
    assert {p["state_bytes"] for p in said} == {cfg.state_bytes_per_slot()}
    page = 1 * PAGE * 128 * 4       # one sparse layer, float32 rows of 128
    assert [p["latent_bytes"] for p in said] == [3 * page, 6 * page]
    keys = 1 * 1 * 16 * 4           # ONE key of 16 a page of 4 positions
    assert [p["index_bytes"] for p in said] == [3 * keys, 6 * keys]
    execs = [dict(e.args) for e in eng.tracer.events if e.name == "exec"]
    assert [e["state_slots"] for e in execs][:2] == [2, 2]


@pytest.mark.parametrize("family", ["hybrid", "deepseek", "ling"])
def test_the_families_with_fewer_facts_build_their_caches_as_before(family):
    """No ``index`` leaf, no ``index_bytes``: leaf counts and shapes as they
    were (a ``None`` leaf vanishes from the donated tuple)."""
    from apex_tpu.models import bailing_hybrid, deepseek, hybrid

    if family == "hybrid":
        cfg = hybrid.hybrid_tiny()
        params = hybrid.init_hybrid(jax.random.PRNGKey(0), cfg)
    elif family == "ling":
        cfg = bailing_hybrid.bailing_hybrid_tiny()
        params = bailing_hybrid.init(jax.random.PRNGKey(0), cfg)
    else:
        cfg = deepseek.deepseek_tiny()
        params = deepseek.init(jax.random.PRNGKey(0), cfg)
    eng = PagedDecodeEngine(
        params, cfg, num_slots=2, max_len=64, num_pages=2 + 2 * 4,
        page_size=16, buckets=(32, 64), cache_dtype=jnp.float32,
        prefix_sharing=family == "deepseek", tracer=Tracer())
    eng.prefill(0, list(range(2, 22)))
    said = [dict(e.args) for e in eng.tracer.events if e.name == "prefill"][0]
    assert "index_bytes" not in said and eng._index_bytes is None
    leaves = len(jax.tree.leaves(eng.cache))
    if family == "deepseek":
        assert isinstance(eng.cache, LatentKVCache) and leaves == 3 + 3
        assert eng.cache.k.shape == (3, 10, 16, 128)
    else:
        assert isinstance(eng.cache, HybridKVCache)
        assert eng.cache.index is None
        if family == "ling":
            assert eng.cache.v is None and leaves == 5 + 3
            assert eng.cache.k.shape == (1, 10, 16, 128)
            assert eng.cache.state.shape == (6, 2, 4, 16, 16)
        else:
            assert eng.cache.v.shape == eng.cache.k.shape and leaves == 6
