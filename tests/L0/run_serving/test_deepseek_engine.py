"""The ``deepseek_v3`` family through ``PagedDecodeEngine`` and
``ContinuousBatchingScheduler``: ONE pool of latent rows read in place by the
absorbed decode kernel, against the benchmark's plain reference (the EXPANDED
attention, no cache); a latent page on the host is a page like any other
(prefix sharing, copy-on-write, preemption by requeue, page transfer); and
the seam: what needs a core the model does not bring is refused by name."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import deepseek
from apex_tpu.serving import (ContinuousBatchingScheduler,
                              DisaggregatedRouter, PagedDecodeEngine, Request,
                              Tracer)
from apex_tpu.serving.cache import LatentKVCache
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
SLOTS, MAX_LEN, PAGE = 3, 128, 16


@pytest.fixture(scope="module")
def tiny():
    cfg = deepseek.deepseek_tiny()
    return cfg, deepseek.init(jax.random.PRNGKey(3), cfg)


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
    return [list(sched.outcomes[r].tokens) for r in rids]


def test_prefill_then_decode_match_the_references_full_forward(tiny):
    """The headline: logits of prefill and of every decode step through the
    latent pool (absorbed) against the plain reference (expanded), which
    ``run_models/test_deepseek.py`` holds the model's own forward to."""
    from tests.L0.run_models.test_deepseek import sizes_of

    ref = harness.load_module("reference", "deepseek_v3", BENCH)
    cfg, params = tiny
    prompt, cont = draw(0, 37, 12)
    eng = engine(cfg, params)
    assert isinstance(eng.cache, LatentKVCache) and not eng.recurrent
    # ONE pool: every layer, a row of 32 + 8 values padded to a lane tile
    assert eng.cache.k.shape == (3, eng.pool.num_pages, PAGE, 128)
    assert eng.cache.v is None
    ids = jnp.asarray(np.concatenate([prompt, cont]))
    with jax.default_matmul_precision("highest"):
        got = teacher_forced(eng, 1, prompt, cont)
        want = ref.logits_at(params, sizes_of(cfg), ids,
                             jnp.arange(len(prompt) - 1, len(ids)))
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert eng.check_invariants()
    # the rows written: the prompt's and the decoded tokens', nothing beside
    pages = eng._slot_pages[1]
    rows = np.asarray(eng.cache.k[:, pages]).reshape(3, -1, 128)
    assert np.all(np.any(rows[:, :49, :40] != 0, -1))
    assert not np.any(rows[:, :, 40:]) and not np.any(rows[:, 49:])


def test_an_inactive_slot_is_untouched_while_another_decodes(tiny):
    cfg, params = tiny
    a, b, cont = draw(3, 20, 33, 4)
    eng = engine(cfg, params)
    eng.prefill(0, a)
    held = lambda: np.asarray(eng.cache.k[:, eng._slot_pages[0]]).reshape(
        3, -1, 128)[:, :20]
    mine = held()
    first = teacher_forced(eng, 0, a, cont[:1])[1]
    eng.free_slot(0)
    eng.prefill(0, a)
    teacher_forced(eng, 2, b, cont)         # slot 0 inactive all along
    # its 20 rows and its length stand (the step parks an inactive slot's
    # write on the row AT its length, which no mask admits), and what it
    # decodes next is what it would have decoded at once
    np.testing.assert_array_equal(held(), mine)
    assert int(eng.cache.lengths[0]) == 20
    assert eng.prepare_decode({0: 20}) == []
    tokens = jnp.zeros((SLOTS,), jnp.int32).at[0].set(int(cont[0]))
    again = np.asarray(eng.decode(tokens, jnp.arange(SLOTS) == 0))[0]
    np.testing.assert_allclose(again, first, atol=2e-5)


def test_the_same_prompt_through_two_bucket_sets_gives_the_same_logits(tiny):
    cfg, params = tiny
    prompt, cont = draw(2, 37, 5)
    a = teacher_forced(engine(cfg, params, buckets=(64, 128)), 2, prompt, cont)
    b = teacher_forced(engine(cfg, params, buckets=(128,)), 2, prompt, cont)
    np.testing.assert_allclose(a, b, atol=2e-5)


def test_counters_ride_the_cache(tiny):
    cfg, params = tiny
    prompt, cont = draw(4, 24, 5)
    eng = engine(cfg, params)
    zero = eng.read_counters()
    assert {k: v.shape for k, v in zero.items()} == {
        "moe_load": (2, 8), "moe_hit": (2,), "moe_steps": (1,)}
    eng.prefill(1, prompt)                          # a prefill counts nothing
    assert not any(v.any() for v in eng.read_counters().values())
    teacher_forced(eng, 1, prompt, cont)
    got = eng.read_counters()
    assert got["moe_steps"].tolist() == [5]
    ids = jnp.asarray(np.concatenate([prompt, cont]))
    chosen = np.asarray(deepseek.prefill_layers(
        params, cfg, deepseek.embed(params, ids),
        jnp.ones(ids.shape, jnp.int32), routes=True)[-1])[:, len(prompt):]
    want = np.stack([np.bincount(layer.ravel(), minlength=16)[:8]
                     for layer in chosen])
    np.testing.assert_array_equal(got["moe_load"], want)


# -- a latent page is a page like any other ------------------------------------

def shared_prefix_requests():
    head = tuple(int(t) for t in draw(5, 40)[0])
    tails = [tuple(int(t) for t in d) for d in draw(6, 9, 17, 3)]
    return [Request(prompt=head + tail, max_new_tokens=7,
                    temperature=(0.0, 0.8, 0.0)[i], seed=i)
            for i, tail in enumerate(tails)] + [
        Request(prompt=head + tails[0], max_new_tokens=9, temperature=0.0,
                seed=9)]            # the first prompt again: a partial page


def test_prefix_sharing_and_copy_on_write_over_latent_pages(tiny):
    """Requests that open with the same 40 tokens share its two full pages;
    the same prompt twice shares its partial last page too, and the one that
    appends to it copies it first. The streams are those of an engine that
    shares nothing."""
    cfg, params = tiny
    reqs = shared_prefix_requests()
    plain = run(engine(cfg, params, slots=2, prefix_sharing=False), reqs)
    eng = engine(cfg, params, slots=2)
    assert eng.prefix_sharing
    assert run(eng, reqs, audit=True) == plain
    assert eng.pool.num_cached > 0 and eng.stats.cow_copies > 0
    assert eng.check_invariants()


def test_preemption_by_requeue_over_a_small_latent_pool(tiny):
    """A pool too small for both requests to finish side by side: one is
    preempted, its pages released, requeued and served again; the streams are
    a roomy pool's."""
    cfg, params = tiny
    reqs = [Request(prompt=tuple(int(t) for t in p), max_new_tokens=40,
                    temperature=0.0, seed=i)
            for i, p in enumerate(draw(7, 30, 28))]
    roomy = run(engine(cfg, params, slots=2), reqs)
    small = engine(cfg, params, slots=2, num_pages=2 + 7)
    assert run(small, reqs, audit=True) == roomy
    assert small.stats.preemptions > 0
    assert small.check_invariants()


def test_latent_pages_travel_to_a_decode_replica(tiny):
    """Disaggregated serving: the prefill replica's latent pages are shipped
    (a pair with an empty V, the wire format as it is) and installed; the
    streams are the colocated ones."""
    cfg, params = tiny
    reqs = shared_prefix_requests()[:3]
    colocated = run(engine(cfg, params, slots=2, tracer=Tracer()), reqs)
    from apex_tpu.serving import FaultInjector

    trc, inj = Tracer(), FaultInjector()
    pe, de = (engine(cfg, params, slots=2, tracer=trc, injector=inj)
              for _ in range(2))
    router = DisaggregatedRouter(pe, de, eos_id=-1, audit=True)
    for r in reqs:
        router.submit(r)
    assert router.run() == colocated
    assert router.stats.remote_prefills == 3
    assert router.stats.transfer_failures == 0
    # the second and third prompts found the first's two full pages on the
    # decode replica already and shipped only their own
    assert router.stats.transfer_pages_deduped == 4


# -- the seam ----------------------------------------------------------------------

def test_the_config_states_the_seam_and_the_engine_names_no_family(tiny):
    from apex_tpu.serving import scheduler
    from apex_tpu.serving.decode import model_cores

    cfg, params = tiny
    assert model_cores(cfg) and not cfg.recurrent and cfg.latent
    assert not hasattr(cfg, "state_shapes")
    eng = engine(cfg, params)
    assert eng.model_cores and eng._exec_stats() == {}
    assert "deepseek" not in open(scheduler.__file__).read()


REFUSED = [
    ("spec_k", dict(spec_k=2)),
    ("tree_spec", dict(spec_k=2, tree_spec=True)),
    ("int8 pool", dict(cache_dtype=jnp.int8)),
    ("host tier", dict(host_tier="a registry")),
    ("compute_dtype", dict(compute_dtype=jnp.bfloat16)),
]


@pytest.mark.parametrize("name, kw", REFUSED, ids=[n for n, _ in REFUSED])
def test_engine_refuses_by_name_what_needs_a_core_it_lacks(tiny, name, kw):
    cfg, params = tiny
    with pytest.raises(ValueError, match="latent pool") as e:
        engine(cfg, params, **kw)
    assert name.split()[0] in str(e.value)
    assert "DeepseekConfig" in str(e.value)


def test_the_rest_is_refused_where_it_is_asked_for(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match=r"chunked prefill \(chunk_tokens=\)"
                       r".*latent pool"):
        ContinuousBatchingScheduler(eng, eos_id=-1, chunk_tokens=16)
    quantized = {**params, "embedding": {"word": {
        **params["embedding"]["word"], "scale": jnp.ones((8,))}}}
    with pytest.raises(ValueError, match="weight-only int8.*latent pool"):
        engine(cfg, quantized)


def test_the_prefill_span_says_what_it_wrote(tiny):
    cfg, params = tiny
    eng = engine(cfg, params, tracer=Tracer())
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    for n in (20, 37):
        sched.submit(Request(prompt=tuple(range(2, 2 + n)), max_new_tokens=3,
                             temperature=0.0, seed=n))
    sched.run()
    said = [dict(e.args) for e in eng.tracer.events if e.name == "prefill"]
    # whole pages of 16 rows x 128 float32 over 3 layers: two pages, then
    # three of which the first (tokens 2..17) is the first prompt's, shared
    page = 3 * 16 * 128 * 4
    assert [p["shared_pages"] for p in said] == [0, 1]
    assert [p["latent_bytes"] for p in said] == [2 * page, 2 * page]
    assert all("state_bytes" not in p for p in said)
    assert "state_slots" not in [dict(e.args) for e in eng.tracer.events
                                 if e.name == "exec"][0]
