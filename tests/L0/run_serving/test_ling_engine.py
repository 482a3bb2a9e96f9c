"""A model with recurrent layers BESIDE a latent pool
(``models.bailing_hybrid``: Kimi Delta Attention's per-channel-gated state a
slot, one MLA layer's rows in ONE pool) through ``PagedDecodeEngine`` and
``ContinuousBatchingScheduler``: against the benchmark's plain reference;
what a slot's prefill resets; preemption by requeue; each refusal by name;
both byte counts on one span; and the caches of the families that state one
of the two facts, built as before."""

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
    ref = harness.load_module("reference", "ling3_flash_vl", BENCH)
    runner = harness.load_module("runners", "ling_serve", BENCH)
    config = harness.rehearsal_view(harness.load_json(
        BENCH, "configs", "ling3_flash_vl.json"))
    sz = {**ref.sizes_of(config), "cache_dtype": "float32",
          "positions": MAX_LEN}
    cfg = runner.model_config(config, sz)
    served = jax.jit(lambda key: ref.make_weights(sz, key))(ref.seed_key(3))
    return ref, sz, cfg, jax.tree.map(
        lambda a: a.astype(jnp.float32), served)


def engine(cfg, params, slots=SLOTS, num_pages=None, **kw):
    kw.setdefault("buckets", (16, 32, 64, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("prefix_sharing", False)
    if num_pages is None:
        num_pages = PagedDecodeEngine.full_pool_pages(slots, MAX_LEN, PAGE)
    return PagedDecodeEngine(params, cfg, num_slots=slots, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=PAGE, **kw)


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
    assert isinstance(eng.cache, HybridKVCache) and eng.cache.v is None
    assert eng.cache.k.shape == (1, eng.pool.num_pages, PAGE, 128)
    assert eng.cache.state.shape == (6, SLOTS, 4, 16, 16)
    assert eng.cache.conv.shape == (6, SLOTS, 3, 192)
    assert eng.cache.state.dtype == eng.cache.conv.dtype == jnp.float32
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
    # the other slots' state was never written, and a table was uploaded at
    # most once a tick
    assert not np.any(np.asarray(eng.cache.state[:, 0]))
    assert not np.any(np.asarray(eng.cache.conv[:, 2]))
    assert 0 < eng.stats.block_table_uploads <= 41
    assert eng.read_counters()["moe_steps"].tolist() == [40]


def test_a_freed_slot_reads_nothing_of_its_predecessor(tiny):
    """A slot's state and tails are written whole by its prefill, and its
    pages are its own: after another request has lived in the slot, a second
    one gives what a fresh engine gives, bit for bit."""
    _, _, cfg, params = tiny
    first, second = draw(1, 50, 6), draw(2, 21, 8)
    used = engine(cfg, params)
    teacher_forced(used, 0, *first)
    used.free_slot(0)
    assert used._slot_pages[0] == [] and used.check_invariants()
    got = teacher_forced(used, 0, *second)
    want = teacher_forced(engine(cfg, params), 0, *second)
    np.testing.assert_array_equal(got, want)


def test_an_inactive_slot_keeps_state_and_pages_while_another_decodes(tiny):
    _, _, cfg, params = tiny
    a, b = draw(3, 30, 25)
    eng = engine(cfg, params)
    eng.prefill(0, a)
    before = jax.tree.map(lambda x: np.asarray(x[:, 0]),
                          (eng.cache.state, eng.cache.conv))
    # the 30 rows the slot holds (an idle slot's next row, past its length
    # and never attended, is where a step parks what it computed for it)
    held = lambda: np.asarray(eng.cache.k[:, np.asarray(
        eng._slot_pages[0])]).reshape(-1, 128)[:30]
    rows = held()
    teacher_forced(eng, 2, b, draw(4, 7)[0])
    after = jax.tree.map(lambda x: np.asarray(x[:, 0]),
                         (eng.cache.state, eng.cache.conv))
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])
    np.testing.assert_array_equal(rows, held())
    assert int(eng.cache.lengths[0]) == 30


def test_the_same_prompt_through_two_bucket_sets_gives_the_same_logits(tiny):
    """37 tokens padded to 64 and to 128: positions at or past the true
    length decay nothing and write nothing, so state, tails and every later
    decode step agree."""
    _, _, cfg, params = tiny
    prompt, cont = draw(5, 37, 5)
    narrow = engine(cfg, params, buckets=(64, 128))
    wide = engine(cfg, params, buckets=(128,))
    a = teacher_forced(narrow, 2, prompt, cont)
    b = teacher_forced(wide, 2, prompt, cont)
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(narrow.cache.state[:, 2],
                               wide.cache.state[:, 2], atol=2e-5)
    np.testing.assert_allclose(narrow.cache.conv[:, 2],
                               wide.cache.conv[:, 2], atol=2e-5)


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


def test_preemption_by_requeue_over_a_small_pool_beside_state(tiny):
    """A pool too small for both requests to finish side by side: one is
    preempted, its pages released, requeued and prefilled again (its state
    is rebuilt from the whole prompt and the tokens it had made); the
    streams are a roomy pool's."""
    _, _, cfg, params = tiny
    reqs = [Request(prompt=tuple(int(t) for t in p), max_new_tokens=24,
                    temperature=0.0, seed=i)
            for i, p in enumerate(draw(7, 14, 12))]
    roomy = run(engine(cfg, params, slots=2), reqs)
    small = engine(cfg, params, slots=2, num_pages=2 + 13)
    assert run(small, reqs, audit=True) == roomy
    assert small.stats.preemptions > 0
    assert small.check_invariants()


def test_the_config_states_both_facts_and_the_engine_names_no_family(tiny):
    from apex_tpu.serving import cache, scheduler
    from apex_tpu.serving.decode import model_cores

    _, _, cfg, params = tiny
    assert model_cores(cfg) and cfg.recurrent and cfg.latent
    assert not hasattr(cfg, "pools")
    assert cfg.state_shapes(5) == ((6, 5, 4, 16, 16), (6, 5, 3, 192))
    assert cfg.state_bytes_per_slot() == 4 * 6 * (4 * 16 * 16 + 3 * 192)
    eng = engine(cfg, params)
    assert eng._exec_stats() == {"state_slots": 0}
    for module in (scheduler, cache):
        text = open(module.__file__).read()
        assert "bailing" not in text.replace(
            "apex_tpu.models.bailing_hybrid", "") and "ling3" not in text


REFUSED = [
    ("prefix_sharing", dict(prefix_sharing=True)),
    ("spec_k", dict(spec_k=2)),
    ("tree_spec", dict(spec_k=2, tree_spec=True)),
    ("int8 pool", dict(cache_dtype=jnp.int8)),
    ("host tier", dict(host_tier="a registry")),
    ("compute_dtype", dict(compute_dtype=jnp.bfloat16)),
]


@pytest.mark.parametrize("name, kw", REFUSED, ids=[n for n, _ in REFUSED])
def test_engine_refuses_by_name_what_needs_a_state_snapshot(tiny, name, kw):
    """Both refusals would apply; the recurrent one speaks first and names
    the feature (nothing new is refused over the latent pool)."""
    _, _, cfg, params = tiny
    with pytest.raises(ValueError, match="recurrent layers") as e:
        engine(cfg, params, **kw)
    assert name.split()[0] in str(e.value)
    assert "BailingHybridConfig" in str(e.value)


def test_the_rest_is_refused_where_it_is_asked_for(tiny):
    """Chunked prefill is the scheduler's option, page transfer the
    router's, int8 weights the engine's."""
    from apex_tpu.serving import DisaggregatedRouter

    _, _, cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match=r"chunked prefill \(chunk_tokens=\)"
                       r".*recurrent layers"):
        ContinuousBatchingScheduler(eng, eos_id=-1, chunk_tokens=16)
    with pytest.raises(ValueError, match="page transfer.*recurrent layers"):
        DisaggregatedRouter(eng, engine(cfg, params), eos_id=-1)
    quantized = {**params, "embedding": {"word": {
        **params["embedding"]["word"], "scale": jnp.ones((8,))}}}
    with pytest.raises(ValueError, match="weight-only int8.*recurrent"):
        engine(cfg, quantized)


def test_one_prefill_span_carries_state_bytes_and_latent_bytes(tiny):
    """The first model that writes both: the slot's state and tails, and its
    private pages of the latent pool; ``exec`` says ``state_slots``."""
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
         "state_bytes", "latent_bytes"}] * 2
    assert {p["state_bytes"] for p in said} == {cfg.state_bytes_per_slot()}
    page = 1 * PAGE * 128 * 4       # one MLA layer, float32 rows of 128
    assert [p["latent_bytes"] for p in said] == [3 * page, 6 * page]
    assert {p["shared_pages"] for p in said} == {0}
    execs = [dict(e.args) for e in eng.tracer.events if e.name == "exec"]
    assert [e["state_slots"] for e in execs][:2] == [2, 2]
    assert all(set(e) == {"kind", "state_slots"} for e in execs)


@pytest.mark.parametrize("family", ["hybrid", "nemotron_h", "deepseek"])
def test_the_one_fact_families_build_their_caches_as_before(family):
    """``recurrent`` alone keeps K AND V pools beside the state, ``latent``
    alone ONE pool and no state; their prefill spans say one byte count."""
    from apex_tpu.models import deepseek, hybrid, nemotron_h

    if family == "hybrid":
        cfg = hybrid.hybrid_tiny()
        params = hybrid.init_hybrid(jax.random.PRNGKey(0), cfg)
    elif family == "nemotron_h":
        cfg = nemotron_h.nemotron_h_tiny()
        params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    else:
        cfg = deepseek.deepseek_tiny()
        params = deepseek.init(jax.random.PRNGKey(0), cfg)
    eng = PagedDecodeEngine(
        params, cfg, num_slots=2, max_len=64, num_pages=2 + 2 * 4,
        page_size=16, buckets=(32, 64), cache_dtype=jnp.float32,
        prefix_sharing=family == "deepseek", tracer=Tracer())
    eng.prefill(0, list(range(2, 22)))
    said = [dict(e.args) for e in eng.tracer.events if e.name == "prefill"][0]
    if family == "deepseek":
        assert isinstance(eng.cache, LatentKVCache) and eng.cache.v is None
        assert "latent_bytes" in said and "state_bytes" not in said
        assert len(jax.tree.leaves(eng.cache)) == 3 + 3
    else:
        assert isinstance(eng.cache, HybridKVCache)
        assert eng.cache.v.shape == eng.cache.k.shape
        assert "state_bytes" in said and "latent_bytes" not in said
        assert len(jax.tree.leaves(eng.cache)) == 6 + (
            3 if family == "nemotron_h" else 0)
