"""Quantized-tier accuracy gates + int8 KV edge cases.

The accuracy contract (documented in docs/source/quantization.rst):
teacher-forced decode under the int8 tiers stays within a fixed
max-|logit-error| envelope of the fp32 full-sequence forward —
``W8_MAX_ABS`` for any weight-quantized config, ``KV8_MAX_ABS`` for an
int8 cache under full-precision weights — on rope AND learned
positions, single-chip AND tp2. Speculative
decoding under int8 weights keeps the stream contract exactly:
token-for-token identical to that config's plain decode.

The edge cases pin the int8 page-pool invariants: the all-zero page
(scale 0) dequantizes to exact zeros, unallocated pages stay pristine
under real traffic, copy-on-write clones a page bit-identically
INCLUDING its scale rows, and physical placement stays invisible.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import apply_gpt_unsharded, gpt_tiny, init_gpt
from apex_tpu.quant import kv_dequantize, kv_quantize, quantize_params
from apex_tpu.serving import (
    ContinuousBatchingScheduler, PagedDecodeEngine, Request,
)

# Compile-heavy (every test jits fresh prefill/decode programs per
# quant config): excluded from the driver's `-m 'not slow'` tier and
# run via `./run_tests.sh L0` (no marker filter) instead.
pytestmark = pytest.mark.slow

S_TOTAL, PROMPT, S_MAX = 16, 8, 32

# Max |logit error| vs the fp32 full forward on the gpt_tiny gate
# model. Measured: ~1.2e-2 for w8 and w8+kv8, ~4e-3 for kv8-only —
# the envelopes leave ~4x headroom without admitting a broken kernel
# (a sign flip or lost scale lands orders of magnitude outside).
W8_MAX_ABS = 0.05
KV8_MAX_ABS = 0.02


def _cfg(use_rope):
    return dataclasses.replace(gpt_tiny(), use_rope=use_rope,
                               hidden_dropout=0.0)


def _full_logits(params, cfg, seq):
    hidden = apply_gpt_unsharded(params, cfg, seq)
    table = params["embedding"]["word"]["embedding"]
    return jnp.dot(hidden, table.T).astype(jnp.float32)


def _paged_teacher_forced(params, cfg, seq, cache_dtype,
                          free_order=None):
    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=cache_dtype, buckets=(8, 16, 32),
                            free_order=free_order)
    logits = eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    rows = [logits[0]]
    for t in range(PROMPT, seq.shape[1]):
        assert eng.prepare_decode({0: t}) == []
        logits = eng.decode(jnp.asarray([int(seq[0, t]), 0], jnp.int32),
                            jnp.asarray([True, False]))
        rows.append(logits[0])
    return jnp.stack(rows)


def _golden(params, cfg, seq):
    return np.asarray(_full_logits(params, cfg, seq)[0, PROMPT - 1:])


def _seq(cfg, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, S_TOTAL), 0,
                              cfg.vocab_size)


# -- accuracy gates ---------------------------------------------------------

@pytest.mark.parametrize("use_rope", [True, False],
                         ids=["rope", "learned_pos"])
def test_w8_teacher_forced_within_tolerance(use_rope):
    """Weight-only int8 over a full-precision cache: every
    teacher-forced logit stays inside W8_MAX_ABS of the fp32 golden.
    The lower bound proves the int8 kernels were actually in the loop —
    a silent fall-through to the fp32 path would read as a pass."""
    cfg = _cfg(use_rope)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg)
    want = _golden(params, cfg, seq)
    qp = quantize_params(params)
    got = _paged_teacher_forced(qp, cfg, seq, jnp.float32)
    err = np.abs(np.asarray(got) - want).max()
    assert err < W8_MAX_ABS, err
    assert err > 1e-4, "suspiciously exact: int8 path not exercised?"


@pytest.mark.parametrize("use_rope", [True, False],
                         ids=["rope", "learned_pos"])
def test_w8kv8_paged_within_tolerance(use_rope):
    """The full quantized tier — int8 weights AND int8 page pool —
    still inside the weight-tier envelope (the KV error rides well
    under the weight error; they don't compound past it)."""
    cfg = _cfg(use_rope)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg)
    want = _golden(params, cfg, seq)
    got = _paged_teacher_forced(quantize_params(params), cfg, seq,
                                jnp.int8)
    err = np.abs(np.asarray(got) - want).max()
    assert err < W8_MAX_ABS, err
    assert err > 1e-4


def test_kv8_only_within_tolerance():
    """int8 page pool under full-precision weights: the tighter
    KV8_MAX_ABS envelope — per-page-per-head scales keep the cache
    error well under the weight-quantization error."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg)
    want = _golden(params, cfg, seq)
    got = _paged_teacher_forced(params, cfg, seq, jnp.int8)
    err = np.abs(np.asarray(got) - want).max()
    assert err < KV8_MAX_ABS, err
    assert err > 1e-5


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.int8],
                         ids=["w8", "w8kv8"])
def test_tp2_w8_decode_matches_unsharded(kv_dtype):
    """tp=2 quantized decode (over a float32 pool and over the int8
    pool): logits match the single-chip quantized step to fp32
    tolerance AND stay inside the accuracy envelope — sharding the int8
    tree (row/column shards of the quantized kernels with their sibling
    scale shards) is a layout change, never an accuracy one."""
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import make_tp_paged_decode_fn
    from apex_tpu.transformer import parallel_state as ps

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params)
    seq = _seq(cfg)
    want_row = _golden(params, cfg, seq)[1]  # logits after seq[PROMPT]
    ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    model = GPTModel(cfg, tp_size=2)
    tokens = jnp.asarray([int(seq[0, PROMPT]), 0], jnp.int32)
    active = jnp.asarray([True, False])

    # an engine-built cache through both decode paths
    eng = PagedDecodeEngine(qp, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=kv_dtype, buckets=(8, 16, 32))
    eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    eng.prepare_decode({0: PROMPT})
    eng.sync_table()    # the clone is launched by hand: upload first
    clone = jax.tree.map(jnp.copy, eng.cache)
    ref = eng.decode(tokens, active)
    _, got = make_tp_paged_decode_fn(
        model, quantized=True, kv_quantized=kv_dtype == jnp.int8)(
            qp, clone, tokens, active)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(got[0]) - want_row).max() < W8_MAX_ABS


# -- speculative decoding under int8 weights --------------------------------

def test_spec_stream_w8_bit_identical_to_plain():
    """The stream contract survives quantization unchanged: spec_k
    draft/verify under int8 weights commits token-for-token the plain
    (spec_k=0) quantized streams — greedy AND seeded sampling. Exact
    integer equality; the accept walk compares the SAME quantized
    logits on both sides, so tolerance would hide a real rollback
    bug. (The int8 CACHE gets the same contract separately —
    test_kv8_rejected_tails_do_not_perturb — via the insert-then-zero
    page requantization rule.)"""
    cfg = _cfg(True)
    qp = quantize_params(init_gpt(jax.random.PRNGKey(0), cfg))
    reqs = [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=6),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=6,
                    temperature=0.8, seed=3),
            Request(prompt=(13, 17, 19), max_new_tokens=4)]

    def run(spec_k):
        eng = PagedDecodeEngine(qp, cfg, num_slots=2, max_len=S_MAX,
                                num_pages=24, page_size=4,
                                buckets=(16, 32), spec_k=spec_k)
        sched = ContinuousBatchingScheduler(eng, eos_id=0)
        for r in reqs:
            sched.submit(r)
        return sched.run(), sched.stats

    plain, _ = run(0)
    spec, stats = run(2)
    assert spec == plain
    assert stats.tokens_drafted > 0


def test_kv8_rejected_tails_do_not_perturb():
    """The int8-cache analogue of
    test_decode.py::test_verify_rejected_rows_not_observable, and the
    contract that makes kv8 speculation exact: two runs whose first
    verify step carried DIFFERENT garbage draft tails must produce
    bit-identical later verify AND plain-decode logits. The verify
    write pins the page scale for tail columns (rescale only at the
    window root) and zeroes rows strictly after each insert, so a
    rejected tail can never re-round committed history. The prompt is
    deliberately NOT page-aligned (6 tokens, page_size 4): the verify
    window straddles a half-full page, the case where a naive
    requantize would perturb committed rows."""
    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    pl = 6  # mid-page: rows 4..5 of page 1 committed, tails land 6..9
    seq = _seq(cfg)

    def run(garbage):
        eng = PagedDecodeEngine(params, cfg, num_slots=1, max_len=S_MAX,
                                num_pages=14, page_size=4,
                                cache_dtype=jnp.int8, buckets=(8, 16),
                                spec_k=k)
        eng.prefill(0, [int(t) for t in np.asarray(seq[0, :pl])])
        eng.prepare_decode({0: pl}, n_new=k + 1)
        bad = jnp.concatenate(
            [seq[:, pl:pl + 1], jnp.full((1, k), garbage, jnp.int32)],
            axis=1)
        eng.verify(bad)
        eng.commit([1])  # only the pending token survives the walk
        eng.prepare_decode({0: pl + 1}, n_new=k + 1)
        l_verify = eng.verify(seq[:, pl + 1:pl + k + 2])
        eng.commit([1])
        eng.prepare_decode({0: pl + 2})
        l_plain = eng.decode(seq[:, pl + 2], jnp.asarray([True]))
        return np.asarray(l_verify), np.asarray(l_plain)

    va, pa = run(3)
    vb, pb = run(499)
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(pa, pb)


# -- int8 KV edge cases -----------------------------------------------------

def test_kv_quantize_all_zero_page():
    """The scale-0 guard: an all-zero page quantizes to exact int8
    zeros with scale 0 and dequantizes to exact fp32 zeros — no NaN/inf
    from the 0/0 — even alongside a non-zero page in the same batch."""
    zero = jnp.zeros((2, 4, 8, 16))
    hot = jnp.concatenate([zero[:1], jnp.ones((1, 4, 8, 16))])
    q, scale = kv_quantize(zero)
    assert q.dtype == jnp.int8 and not np.asarray(q).any()
    assert not np.asarray(scale).any()
    back = np.asarray(kv_dequantize(q, scale))
    assert np.isfinite(back).all() and not back.any()
    q, scale = kv_quantize(hot)
    assert not np.asarray(q[0]).any() and np.asarray(q[1]).any()
    assert not np.asarray(scale[0]).any()
    np.testing.assert_allclose(np.asarray(kv_dequantize(q, scale)[1]),
                               1.0, rtol=1e-2)


def test_int8_unallocated_pages_stay_pristine():
    """Real prefill + decode traffic through an int8 pool must leave
    every page the allocator never handed out — NULL included — at
    exact zeros with zero scales. Inactive-slot writes are redirected
    to SCRATCH, never a free page (prefix sharing off, so no
    registry-cached pages muddy the live set)."""
    from apex_tpu.serving.cache import SCRATCH_PAGE

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg)
    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=jnp.int8, buckets=(8, 16, 32),
                            prefix_sharing=False)
    eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    for t in range(PROMPT, PROMPT + 4):
        eng.prepare_decode({0: t})
        eng.decode(jnp.asarray([int(seq[0, t]), 0], jnp.int32),
                   jnp.asarray([True, False]))
    live = {SCRATCH_PAGE}
    for pages in eng._slot_pages:
        live.update(pages)
    cache = eng.cache
    for page in range(14):
        if page in live:
            continue
        for pool in (cache.k, cache.v):
            assert not np.asarray(pool[:, page]).any(), page
        for scale in (cache.k_scale, cache.v_scale):
            assert not np.asarray(scale[:, page]).any(), page
    # the live pages did take real int8 traffic
    assert any(np.asarray(cache.k[:, p]).any()
               for p in eng._slot_pages[0])


def test_int8_cow_clone_bit_identical():
    """Copy-on-write on a quantized pool clones the page's int8 tiles
    AND its k/v scale rows bitwise, touching nothing else."""
    from apex_tpu.serving.cache import init_paged_cache
    from apex_tpu.serving.decode import make_copy_page_fn

    cfg = _cfg(True)
    cache = init_paged_cache(cfg, 2, S_MAX, 8, 4, jnp.int8)
    rng = np.random.RandomState(0)

    def fill(leaf, lo, hi, dtype):
        return jnp.asarray(rng.randint(lo, hi, leaf.shape), dtype)

    cache = cache._replace(
        k=fill(cache.k, -127, 128, jnp.int8),
        v=fill(cache.v, -127, 128, jnp.int8),
        k_scale=jnp.asarray(rng.rand(*cache.k_scale.shape), jnp.float32),
        v_scale=jnp.asarray(rng.rand(*cache.v_scale.shape), jnp.float32))
    before = jax.tree.map(np.asarray, cache)
    src, dst = 3, 6
    after = jax.tree.map(
        np.asarray, make_copy_page_fn()(cache, jnp.int32(src),
                                        jnp.int32(dst)))
    for b, a in zip(before[:2] + before[4:], after[:2] + after[4:]):
        np.testing.assert_array_equal(a[:, dst], b[:, src])
        mask = np.arange(a.shape[1]) != dst
        np.testing.assert_array_equal(a[:, mask], b[:, mask])
    np.testing.assert_array_equal(after.lengths, before.lengths)
    np.testing.assert_array_equal(after.block_tables,
                                  before.block_tables)


def test_int8_cow_does_not_perturb_sharing_request():
    """The bf16 COW acceptance contract holds verbatim on an int8
    pool: two requests sharing a partial prompt page both append
    (copy-on-write), and each one's logits are BIT-IDENTICAL to its
    alone run — the clone carried the scales, the shared original was
    never re-quantized."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prompt = [5, 7, 11, 13, 17, 19]  # 1.5 pages of 4: partial shared
    div = (31, 37)

    def engine(num_pages=12):
        return PagedDecodeEngine(params, cfg, num_slots=2,
                                 max_len=S_MAX, num_pages=num_pages,
                                 page_size=4, cache_dtype=jnp.int8,
                                 buckets=(16, 32))

    def alone(slot, token):
        eng = engine()
        eng.prefill(slot, prompt)
        assert eng.prepare_decode({slot: len(prompt)}) == []
        toks = [0, 0]
        toks[slot] = token
        active = jnp.asarray([i == slot for i in range(2)])
        return np.asarray(eng.decode(jnp.asarray(toks, jnp.int32),
                                     active)[slot])

    refs = [alone(0, div[0]), alone(1, div[1])]
    eng = engine()
    eng.prefill(0, prompt)
    eng.prefill(1, prompt)
    shared = eng._slot_pages[0][1]
    assert eng.prepare_decode({0: len(prompt), 1: len(prompt)}) == []
    assert eng._slot_pages[0][1] != shared  # both COW'd
    assert eng._slot_pages[1][1] != shared
    step = eng.decode(jnp.asarray(div, jnp.int32),
                      jnp.asarray([True, True]))
    np.testing.assert_array_equal(np.asarray(step[0]), refs[0])
    np.testing.assert_array_equal(np.asarray(step[1]), refs[1])


def test_int8_decode_bit_identical_across_page_placements():
    """Physical placement stays invisible on the quantized pool: the
    same request through permuted free-list orders produces
    BIT-IDENTICAL logits at every step — scales live with their pages,
    so re-placement can't re-quantize anything."""
    from apex_tpu.serving.cache import RESERVED_PAGES

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg)
    usable = list(range(RESERVED_PAGES, 14))
    rng = np.random.RandomState(3)
    orders = [None, list(rng.permutation(usable))]
    runs = [np.asarray(_paged_teacher_forced(params, cfg, seq, jnp.int8,
                                             free_order=order))
            for order in orders]
    for other in runs[1:]:
        np.testing.assert_array_equal(runs[0], other)
