"""A model with recurrent layers (``models.hybrid``) through
``PagedDecodeEngine`` and ``ContinuousBatchingScheduler``: two kinds of
state in one cache, against the benchmark's plain reference; what a slot's
prefill resets; bucket invariance; and the features refused by name."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import hybrid
from apex_tpu.serving import (ContinuousBatchingScheduler, PagedDecodeEngine,
                              Request)
from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
SLOTS, MAX_LEN, PAGE = 3, 128, 4


@pytest.fixture(scope="module")
def tiny():
    """(reference, sizes, config object, float32 weights the reference
    made): float32 so that engine and reference agree to rounding."""
    ref = harness.load_module("reference", "olmo_hybrid_7b",
                              os.path.join(REPO, "benchmark"))
    config = harness.rehearsal_view(harness.load_json(
        REPO, "benchmark", "configs", "olmo_hybrid_7b.json"))
    sz = ref.sizes_of(config)
    cfg = hybrid.HybridConfig.from_layer_types(
        config["layer_types"], vocab_size=sz["vocab"],
        hidden_size=sz["hidden"], num_heads=sz["heads"],
        ffn_hidden_size=sz["ffn"], linear_heads=sz["linear_heads"],
        linear_key_dim=sz["linear_key_dim"],
        linear_value_dim=sz["linear_value_dim"])
    served = jax.jit(lambda key: ref.make_weights(sz, key))(ref.seed_key(3))
    return ref, sz, cfg, jax.tree.map(
        lambda a: a.astype(jnp.float32), served)


def engine(cfg, params, **kw):
    kw.setdefault("buckets", (16, 32, 64, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("prefix_sharing", False)
    return PagedDecodeEngine(
        params, cfg, num_slots=SLOTS, max_len=MAX_LEN,
        num_pages=PagedDecodeEngine.full_pool_pages(SLOTS, MAX_LEN, PAGE),
        page_size=PAGE, **kw)


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


def test_prefill_then_decode_match_the_references_full_forward(tiny):
    ref, sz, cfg, params = tiny
    rng = np.random.RandomState(0)
    prompt, cont = rng.randint(2, sz["vocab"], 37), rng.randint(
        2, sz["vocab"], 12)
    eng = engine(cfg, params)
    assert eng.recurrent and eng.cache.state.dtype == jnp.float32
    assert eng.cache.k.shape == (1, eng.pool.num_pages, PAGE, 128)
    assert eng.cache.state.shape == (3, SLOTS, 2, 16, 32)
    assert eng.cache.conv.shape == (3, SLOTS, 3, 128)
    got = teacher_forced(eng, 1, prompt, cont)
    ids = jnp.asarray(np.concatenate([prompt, cont]))
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(params, sz, ids,
                             jnp.arange(len(prompt) - 1, len(ids)))
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert eng.check_invariants()
    # the other slots' state was never written
    assert not np.any(np.asarray(eng.cache.state[:, 0]))
    assert not np.any(np.asarray(eng.cache.conv[:, 2]))


def test_a_reused_slot_gives_what_a_fresh_engine_gives(tiny):
    """A slot's state is written whole by its prefill and needs no other
    reset: after another request has lived in the slot, a second one sees
    none of it."""
    _, sz, cfg, params = tiny
    rng = np.random.RandomState(1)
    first = (rng.randint(2, sz["vocab"], 50), rng.randint(2, sz["vocab"], 6))
    second = (rng.randint(2, sz["vocab"], 21), rng.randint(2, sz["vocab"], 8))
    used = engine(cfg, params)
    teacher_forced(used, 0, *first)
    used.free_slot(0)
    got = teacher_forced(used, 0, *second)
    want = teacher_forced(engine(cfg, params), 0, *second)
    np.testing.assert_array_equal(got, want)


def test_the_same_prompt_through_two_bucket_sets_gives_the_same_logits(tiny):
    """37 tokens padded to 64 and to 128: positions at or past the true
    length leave the recurrent state and the convolution tail untouched, so
    prefill and every later decode step agree."""
    _, sz, cfg, params = tiny
    rng = np.random.RandomState(2)
    prompt, cont = rng.randint(2, sz["vocab"], 37), rng.randint(
        2, sz["vocab"], 5)
    narrow = engine(cfg, params, buckets=(64, 128))
    wide = engine(cfg, params, buckets=(128,))
    a = teacher_forced(narrow, 2, prompt, cont)
    b = teacher_forced(wide, 2, prompt, cont)
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(narrow.cache.state[:, 2], wide.cache.state[:, 2],
                               atol=2e-5)
    np.testing.assert_array_equal(narrow.cache.conv[:, 2],
                                  wide.cache.conv[:, 2])


def test_scheduler_streams_are_the_references_greedy_tokens(tiny):
    """Five requests over three slots (slots turn over, admissions run
    beside decode): every greedy stream is the reference's argmax, token
    for token, where the reference's margin is not a tie."""
    ref, sz, cfg, params = tiny
    eng = engine(cfg, params)
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    rng = np.random.RandomState(4)
    prompts = [tuple(int(t) for t in rng.randint(2, sz["vocab"], n))
               for n in (9, 40, 17, 70, 25)]
    rids = [sched.submit(Request(prompt=p, max_new_tokens=6, temperature=0.0,
                                 seed=i)) for i, p in enumerate(prompts)]
    sched.run()
    scorer = ref.Scorer({**sz, "positions": MAX_LEN}, 3)
    scorer.params = params
    for rid, prompt in zip(rids, prompts):
        out = sched.outcomes[rid]
        assert out.error is None and len(out.tokens) == 6
        gaps, _ = scorer.gaps(prompt, list(out.tokens))
        assert float(gaps.max()) < 1e-3
    assert eng.check_invariants()


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
    _, _, cfg, params = tiny
    with pytest.raises(ValueError, match="recurrent layers") as e:
        engine(cfg, params, **kw)
    assert name.split()[0] in str(e.value)


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
    # the mark of a weight-only int8 tree: a scale beside the word table
    quantized = {**params, "embedding": {"word": {
        **params["embedding"]["word"], "scale": jnp.ones((8,))}}}
    with pytest.raises(ValueError, match="weight-only int8.*recurrent"):
        engine(cfg, quantized)


def test_spans_gain_their_stats_for_recurrent_layers_only(tiny):
    """``prefill`` keeps every stat and gains ``state_bytes``; ``exec``
    gains ``state_slots``; a GPT engine's spans carry neither."""
    import dataclasses

    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    from apex_tpu.serving import Tracer

    def stats_of(eng):
        sched = ContinuousBatchingScheduler(eng, eos_id=-1)
        for n in (9, 21):
            sched.submit(Request(prompt=tuple(range(2, 2 + n)),
                                 max_new_tokens=3, temperature=0.0, seed=n))
        sched.run()
        by = {}
        for e in eng.tracer.events:
            by.setdefault(e.name, []).append(dict(e.args))
        return by

    _, _, cfg, params = tiny
    got = stats_of(engine(cfg, params, tracer=Tracer()))
    assert [set(p) for p in got["prefill"]] == [
        {"bucket", "prompt_tokens", "shared_pages", "page_size",
         "state_bytes"}] * 2
    assert {p["state_bytes"] for p in got["prefill"]} == {
        cfg.state_bytes_per_slot()}
    assert {p["shared_pages"] for p in got["prefill"]} == {0}
    assert [e["state_slots"] for e in got["exec"]][:2] == [2, 2]
    assert all(set(e) == {"kind", "state_slots"} for e in got["exec"])

    gcfg = dataclasses.replace(gpt_tiny(), hidden_dropout=0.0)
    geng = PagedDecodeEngine(init_gpt(jax.random.PRNGKey(0), gcfg), gcfg,
                             num_slots=2, max_len=32, num_pages=20,
                             page_size=4, buckets=(16, 32), tracer=Tracer())
    assert not geng.recurrent and not hasattr(geng.cache, "state")
    plain = stats_of(geng)
    assert all(set(p) == {"bucket", "prompt_tokens", "shared_pages",
                          "page_size"} for p in plain["prefill"])
    assert all(set(e) == {"kind"} for e in plain["exec"])
