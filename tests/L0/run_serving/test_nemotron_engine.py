"""The ``nemotron_h`` family through ``PagedDecodeEngine`` and
``ContinuousBatchingScheduler``: Mamba-2 state and convolution tails beside a
page pool of two K/V heads, against the model's own full forward
(``nemotron_h.apply``, which ``run_models/test_nemotron_h.py`` holds to the
benchmark's plain reference); what a slot's prefill resets; bucket
invariance; the counters the decode program keeps on the device; and the
seam: both recurrent families construct through the one path, and each
refused feature raises by name for both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import hybrid, nemotron_h as nh
from apex_tpu.serving import (ContinuousBatchingScheduler, PagedDecodeEngine,
                              Request)

SLOTS, MAX_LEN, PAGE = 3, 128, 16


@pytest.fixture(scope="module")
def tiny():
    cfg = nh.nemotron_h_tiny()
    return cfg, nh.init(jax.random.PRNGKey(3), cfg)


def engine(cfg, params, **kw):
    kw.setdefault("buckets", (16, 32, 64, 128))
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("prefix_sharing", False)
    return PagedDecodeEngine(
        params, cfg, num_slots=SLOTS, max_len=MAX_LEN,
        num_pages=PagedDecodeEngine.full_pool_pages(SLOTS, MAX_LEN, PAGE),
        page_size=PAGE, **kw)


def teacher_forced(eng, slot, prompt, cont):
    """Prefill ``prompt`` into ``slot``, then decode ``cont`` token by token:
    the logits rows that predict cont[0], cont[1], ..., and one more."""
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


def test_prefill_then_decode_match_the_full_forward_at_every_position(tiny):
    cfg, params = tiny
    prompt, cont = draw(0, 37, 12)
    eng = engine(cfg, params)
    assert eng.recurrent and eng.cache.state.dtype == jnp.float32
    # one attention layer, rows of 2 K/V heads x 16; two Mamba-2 layers
    assert eng.cache.k.shape == (1, eng.pool.num_pages, PAGE, 32)
    assert eng.cache.state.shape == (2, SLOTS, 8, 16, 32)
    assert eng.cache.conv.shape == (2, SLOTS, 3, 256)
    with jax.default_matmul_precision("highest"):
        got = teacher_forced(eng, 1, prompt, cont)
        want = nh.apply(params, cfg, jnp.asarray(np.concatenate(
            [prompt, cont])))[len(prompt) - 1:]
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert eng.check_invariants()
    # the other slots' state was never written
    assert not np.any(np.asarray(eng.cache.state[:, 0]))
    assert not np.any(np.asarray(eng.cache.conv[:, 2]))


def test_a_reused_slot_gives_what_a_fresh_engine_gives(tiny):
    cfg, params = tiny
    first, first_cont, second, second_cont = draw(1, 50, 6, 21, 8)
    used = engine(cfg, params)
    teacher_forced(used, 0, first, first_cont)
    used.free_slot(0)
    got = teacher_forced(used, 0, second, second_cont)
    want = teacher_forced(engine(cfg, params), 0, second, second_cont)
    np.testing.assert_array_equal(got, want)


def test_the_same_prompt_through_two_bucket_sets_gives_the_same_logits(tiny):
    """37 tokens padded to 64 and to 128: padded positions decay nothing,
    write nothing and are routed to no expert, so prefill and every later
    decode step agree, and so do the states they leave."""
    cfg, params = tiny
    prompt, cont = draw(2, 37, 5)
    narrow = engine(cfg, params, buckets=(64, 128))
    wide = engine(cfg, params, buckets=(128,))
    a = teacher_forced(narrow, 2, prompt, cont)
    b = teacher_forced(wide, 2, prompt, cont)
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(narrow.cache.state[:, 2],
                               wide.cache.state[:, 2], atol=2e-5)
    np.testing.assert_array_equal(narrow.cache.conv[:, 2],
                                  wide.cache.conv[:, 2])


def test_an_inactive_slot_keeps_its_state_while_another_decodes(tiny):
    cfg, params = tiny
    a, b, cont = draw(3, 20, 33, 4)
    eng = engine(cfg, params)
    eng.prefill(0, a)
    state0 = np.asarray(eng.cache.state[:, 0])
    conv0 = np.asarray(eng.cache.conv[:, 0])
    teacher_forced(eng, 2, b, cont)         # slot 0 inactive all along
    np.testing.assert_array_equal(eng.cache.state[:, 0], state0)
    np.testing.assert_array_equal(eng.cache.conv[:, 0], conv0)
    assert int(eng.cache.lengths[0]) == 20


def test_counters_ride_the_cache_and_are_read_on_request(tiny):
    """``moe_load`` / ``moe_hit`` / ``moe_steps`` count decode steps only,
    for the active slots only, and cost no tick a read-back: the engine reads
    them when asked."""
    cfg, params = tiny
    prompt, cont = draw(4, 24, 5)
    eng = engine(cfg, params)
    zero = eng.read_counters()
    assert {k: v.shape for k, v in zero.items()} == {
        "moe_load": (2, 8), "moe_hit": (2,), "moe_steps": (1,)}
    assert not any(v.any() for v in zero.values())
    eng.prefill(1, prompt)                          # a prefill counts nothing
    assert not any(v.any() for v in eng.read_counters().values())
    teacher_forced(eng, 1, prompt, cont)
    got = eng.read_counters()
    assert got["moe_steps"].tolist() == [5]
    # one active slot a step: at most 4 assignments a layer a step, each hit
    # expert has at least one
    assert (got["moe_load"].sum(1) <= 5 * 4).all()
    assert (got["moe_hit"] <= got["moe_load"].sum(1)).all()
    assert got["moe_load"].sum() > 0
    # the model's own router says the same (the tokens decoded, routed by
    # the full forward)
    ids = jnp.asarray(np.concatenate([prompt, cont]))
    chosen = np.asarray(nh.prefill_layers(
        params, cfg, nh.embed(params, ids), jnp.ones(ids.shape, jnp.int32),
        routes=True)[-1])[:, len(prompt):]
    want = np.stack([np.bincount(layer.ravel(), minlength=16)[:8]
                     for layer in chosen])
    np.testing.assert_array_equal(got["moe_load"], want)


def test_scheduler_streams_are_the_models_greedy_tokens(tiny):
    """Five requests over three slots (slots turn over, admissions run beside
    decode): every greedy stream is the full forward's argmax, token for
    token."""
    cfg, params = tiny
    eng = engine(cfg, params)
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    prompts = [tuple(int(t) for t in p) for p in draw(5, 9, 40, 17, 70, 25)]
    rids = [sched.submit(Request(prompt=p, max_new_tokens=6, temperature=0.0,
                                 seed=i)) for i, p in enumerate(prompts)]
    sched.run()
    for rid, prompt in zip(rids, prompts):
        out = sched.outcomes[rid]
        assert out.error is None and len(out.tokens) == 6
        logits = np.asarray(nh.apply(params, cfg, jnp.asarray(
            prompt + tuple(out.tokens))))[len(prompt) - 1:-1]
        best = logits.max(-1)
        served = logits[np.arange(6), list(out.tokens)]
        assert float((best - served).max()) < 1e-3
    assert eng.check_invariants()


# -- the seam: one recurrent path, two families --------------------------------

FAMILIES = {
    "nemotron_h": lambda: (nh.nemotron_h_tiny(), nh.init),
    "hybrid": lambda: (hybrid.hybrid_tiny(), hybrid.init_hybrid),
}
SEAM = ("kv_layers", "kv_row_width", "state_shapes", "state_bytes_per_slot",
        "prefill_core", "decode_core", "logits_of")


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    cfg, init = FAMILIES[request.param]()
    return request.param, cfg, init(jax.random.PRNGKey(0), cfg)


def test_both_families_state_the_seam_and_construct_through_one_path(family):
    from apex_tpu.serving import scheduler
    from apex_tpu.serving.cache import HybridKVCache

    name, cfg, params = family
    assert cfg.recurrent and all(hasattr(cfg, what) for what in SEAM)
    eng = engine(cfg, params, buckets=(32, 64, 128))
    assert isinstance(eng.cache, HybridKVCache)
    state, conv = cfg.state_shapes(SLOTS)
    assert eng.cache.state.shape == state and eng.cache.conv.shape == conv
    assert eng.cache.k.shape[0] == cfg.kv_layers
    assert eng.cache.k.shape[3] == cfg.kv_row_width
    assert (eng.cache.counters is None) == (name == "hybrid")
    assert (eng.read_counters() is None) == (name == "hybrid")
    # a prompt and two tokens go through, whatever the family
    logits = teacher_forced(eng, 0, draw(6, 19)[0], draw(7, 2)[0])
    assert logits.shape == (3, cfg.vocab_size) and np.isfinite(logits).all()
    # the engine names no family: the model's module is never imported there
    source = open(scheduler.__file__).read()
    assert "models.hybrid" not in source and "nemotron" not in source


REFUSED = [
    ("prefix_sharing", dict(prefix_sharing=True)),
    ("spec_k", dict(spec_k=2)),
    ("tree_spec", dict(spec_k=2, tree_spec=True)),
    ("int8 pool", dict(cache_dtype=jnp.int8)),
    ("host tier", dict(host_tier="a registry")),
    ("compute_dtype", dict(compute_dtype=jnp.bfloat16)),
]


@pytest.mark.parametrize("name, kw", REFUSED, ids=[n for n, _ in REFUSED])
def test_engine_refuses_by_name_what_needs_a_state_snapshot(family, name, kw):
    _, cfg, params = family
    with pytest.raises(ValueError, match="recurrent layers") as e:
        engine(cfg, params, **kw)
    assert name.split()[0] in str(e.value)
    assert type(cfg).__name__ in str(e.value)


def test_the_rest_is_refused_where_it_is_asked_for(family):
    from apex_tpu.serving import DisaggregatedRouter

    _, cfg, params = family
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


def test_spans_carry_state_bytes_and_state_slots(tiny):
    from apex_tpu.serving import Tracer

    cfg, params = tiny
    eng = engine(cfg, params, tracer=Tracer())
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    for n in (9, 21):
        sched.submit(Request(prompt=tuple(range(2, 2 + n)), max_new_tokens=3,
                             temperature=0.0, seed=n))
    sched.run()
    by = {}
    for e in eng.tracer.events:
        by.setdefault(e.name, []).append(dict(e.args))
    assert {p["state_bytes"] for p in by["prefill"]} == {
        cfg.state_bytes_per_slot()} == {4 * 2 * (8 * 16 * 32 + 3 * 256)}
    assert [e["state_slots"] for e in by["exec"]][:2] == [2, 2]
