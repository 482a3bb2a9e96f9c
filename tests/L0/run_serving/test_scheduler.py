"""Continuous-batching scheduler: admit/evict lifecycle over a fixed
slot pool, and output invariance to slot placement and pool size
(page placement, pool pressure, and preemption-by-requeue must all be
invisible in the outputs). The oracle for WHAT a stream commits is the
model's full forward (``full_forward.reference_stream``)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from full_forward import reference_stream

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (ContinuousBatchingScheduler,
                              PagedDecodeEngine, Request)

EOS = 0
MAX_LEN = 32


def _cfg():
    return dataclasses.replace(gpt_tiny(), use_rope=True,
                               hidden_dropout=0.0)


def _params(cfg):
    return init_gpt(jax.random.PRNGKey(0), cfg)


def _engine(params, cfg, num_slots, **kw):
    """An engine over a full pool (nothing is ever preempted)."""
    kw.setdefault("buckets", (16, 32))
    return PagedDecodeEngine(
        params, cfg, num_slots=num_slots, max_len=MAX_LEN,
        num_pages=PagedDecodeEngine.full_pool_pages(num_slots, MAX_LEN, 4),
        page_size=4, **kw)


def _run(params, cfg, requests, num_slots, top_k=0):
    sched = ContinuousBatchingScheduler(
        _engine(params, cfg, num_slots, top_k=top_k), eos_id=EOS)
    for r in requests:
        sched.submit(r)
    return sched.run()


_REFERENCE = {}


def _reference(params, cfg, requests):
    """What the full forward says each request commits (``_params`` are
    the same in every test, so a request's stream is made once)."""
    for r in requests:
        if r not in _REFERENCE:
            _REFERENCE[r] = reference_stream(params, cfg, r, EOS, MAX_LEN)
    return [_REFERENCE[r] for r in requests]


def test_more_requests_than_slots():
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(2 + i, 3 + i, 5 + i), max_new_tokens=5)
            for i in range(5)]
    outs = _run(params, cfg, reqs, num_slots=2)
    assert len(outs) == 5
    for toks in outs:
        assert 1 <= len(toks) <= 5
        assert all(isinstance(t, int) for t in toks)
        if len(toks) < 5:  # early exit only ever means EOS
            assert toks[-1] == EOS


def test_greedy_output_independent_of_num_slots():
    """The same greedy request set must decode to the same tokens
    whether it runs 1-at-a-time or fully batched — slot packing is a
    throughput concern, never a numerics one."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=4),
            Request(prompt=(17, 19), max_new_tokens=4),
            Request(prompt=(23, 29, 31, 37), max_new_tokens=4)]
    a = _run(params, cfg, reqs, num_slots=1)
    b = _run(params, cfg, reqs, num_slots=3)
    assert a == b


def test_seeded_sampling_independent_of_slot_placement():
    """Per-request keys are derived from (seed, tokens generated so
    far), not from slot index or admission order — so a sampled request
    is reproducible regardless of what else shares the batch."""
    cfg = _cfg()
    params = _params(cfg)
    probe = Request(prompt=(5, 7, 11), max_new_tokens=6,
                    temperature=0.8, seed=42)
    alone = _run(params, cfg, [probe], num_slots=1)[0]
    filler = [Request(prompt=(2, 3), max_new_tokens=6,
                      temperature=0.9, seed=i) for i in range(3)]
    crowded = _run(params, cfg, [probe] + filler, num_slots=4)[0]
    assert alone == crowded


def test_max_new_tokens_respected():
    cfg = _cfg()
    params = _params(cfg)
    outs = _run(params, cfg, [Request(prompt=(3, 5), max_new_tokens=1),
                              Request(prompt=(3, 5), max_new_tokens=3)],
                num_slots=2)
    assert len(outs[0]) == 1
    assert len(outs[1]) <= 3


def test_submit_validates():
    cfg = _cfg()
    engine = _engine(_params(cfg), cfg, 1)
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    with pytest.raises(ValueError):
        sched.submit(Request(prompt=()))
    with pytest.raises(ValueError):
        sched.submit(Request(prompt=tuple(range(MAX_LEN + 1))))


def test_run_on_empty_queue():
    cfg = _cfg()
    engine = _engine(_params(cfg), cfg, 1)
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    assert sched.run() == []


# -- the page pool ----------------------------------------------------------

def _run_paged(params, cfg, requests, num_slots, num_pages, page_size=4,
               free_order=None, cache_dtype=jnp.bfloat16):
    engine = PagedDecodeEngine(params, cfg, num_slots=num_slots,
                               max_len=MAX_LEN, num_pages=num_pages,
                               page_size=page_size, buckets=(16, 32),
                               free_order=free_order,
                               cache_dtype=cache_dtype)
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    for r in requests:
        sched.submit(r)
    return sched.run(), engine


def _mixed_requests():
    return [Request(prompt=(7, 11, 13), max_new_tokens=5),
            Request(prompt=(17, 19), max_new_tokens=5,
                    temperature=0.8, seed=3),
            Request(prompt=(7, 11, 13, 29), max_new_tokens=4),
            Request(prompt=(7, 11, 13), max_new_tokens=5,
                    temperature=0.7, seed=9)]


def test_paged_outputs_match_full_forward():
    """The engine commits what the model says: the request mix (greedy
    + seeded sampling, shared prompt prefixes) through the scheduler
    produces the token streams the full forward generates one token at
    a time with the same keys (float32 pool: the comparison is the
    math's, not a cache rounding's)."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _mixed_requests()
    paged, engine = _run_paged(params, cfg, reqs, num_slots=2,
                               num_pages=20, cache_dtype=jnp.float32)
    assert paged == _reference(params, cfg, reqs)
    assert engine.pool.num_cached > 0   # the shared prefix was shared


def test_paged_outputs_independent_of_page_placement():
    """Permuted free-list orders scatter the same requests across
    different physical pages — the outputs (including seeded sampling)
    must not change."""
    from apex_tpu.serving.cache import RESERVED_PAGES

    cfg = _cfg()
    params = _params(cfg)
    reqs = _mixed_requests()
    usable = list(range(RESERVED_PAGES, 20))
    a, _ = _run_paged(params, cfg, reqs, num_slots=2, num_pages=20)
    b, _ = _run_paged(params, cfg, reqs, num_slots=2, num_pages=20,
                      free_order=list(reversed(usable)))
    assert a == b


def test_paged_preemption_requeues_and_resumes():
    """A pool too small for the full batch preempts a slot mid-decode
    (pages released, request requeued WITH its progress); the resumed
    request must finish with exactly the tokens an uncontended run
    produces — preemption is a capacity event, never a numerics one."""
    cfg = _cfg()
    params = _params(cfg)
    # two greedy requests, each individually fine (4 pages needed, 5
    # usable) but over-committed together: both cross a page boundary
    # at pos 8 and only one new page remains
    reqs = [Request(prompt=(7, 11, 13, 17, 19), max_new_tokens=8),
            Request(prompt=(23, 29, 31, 37, 41), max_new_tokens=8)]
    roomy, _ = _run_paged(params, cfg, reqs, num_slots=2, num_pages=20)

    engine = PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                               num_pages=7, page_size=4,
                               buckets=(16, 32))
    preempted = []
    orig = engine.prepare_decode

    def spy(positions, n_new=1):
        out = orig(positions, n_new=n_new)
        preempted.extend(out)
        return out

    engine.prepare_decode = spy
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    for r in reqs:
        sched.submit(r)
    tight = sched.run()
    assert preempted  # the pool pressure actually bit
    assert tight == roomy


def test_paged_cow_exact_fit_pool_completes():
    """Regression (livelock): with prefix sharing on, prefill
    registers the prompt's partial last page (refcount 2), so the
    first decode append wants a COW page — transiently one MORE page
    than submit validated. With usable pages == the validated need the
    clone alloc fails; the old code preempted, and re-admission
    recreated the identical state, spinning run() forever. The failed
    alloc's LRU sweep already dropped the registry's reference, so the
    append is in-place legal and the run must finish with exactly the
    uncontended tokens."""
    from apex_tpu.serving.cache import RESERVED_PAGES

    cfg = _cfg()
    params = _params(cfg)
    # 5-token prompt + 3 new = 8 rows = exactly 2 pages of 4
    req = Request(prompt=(7, 11, 13, 17, 19), max_new_tokens=3)
    roomy, _ = _run_paged(params, cfg, [req], num_slots=1, num_pages=20)

    engine = PagedDecodeEngine(params, cfg, num_slots=1, max_len=MAX_LEN,
                               num_pages=2 + RESERVED_PAGES, page_size=4,
                               buckets=(16, 32))
    prefills = 0
    orig = engine.prefill

    def spy(slot, prompt):
        nonlocal prefills
        prefills += 1
        assert prefills < 10, "re-prefilling forever — COW livelock"
        return orig(slot, prompt)

    engine.prefill = spy
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    sched.submit(req)
    assert sched.run() == roomy


def test_preempted_slots_requeue_in_submission_order():
    """Several slots preempted in one tick must rejoin the queue front
    in submission order, not slot-index order (FIFO fairness)."""
    cfg = _cfg()
    params = _params(cfg)
    engine = PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                               num_pages=20, page_size=4,
                               buckets=(16, 32))
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    # request 0 finishes on its prefill logits, freeing slot 0 for
    # request 2 — leaving the LATER request in the LOWER slot
    sched.submit(Request(prompt=(3, 5), max_new_tokens=1))
    sched.submit(Request(prompt=(7, 11), max_new_tokens=8))
    sched._admit()
    sched.submit(Request(prompt=(13, 17), max_new_tokens=8))
    sched._admit()
    assert [s.request_id for s in sched._slots] == [2, 1]
    engine.prepare_decode = lambda positions, n_new=1: list(positions)
    sched._tick()
    assert [rid for rid, _, _ in sched._queue] == [1, 2]


def test_paged_prefill_rejects_oversized_prompt():
    """Engine-level guard: prefill driven directly (without the
    scheduler's submit check) must reject a prompt beyond max_len with
    a clear error, before any page references are taken."""
    cfg = _cfg()
    params = _params(cfg)
    engine = PagedDecodeEngine(params, cfg, num_slots=1, max_len=8,
                               num_pages=20, page_size=4, buckets=(4, 8))
    free_before = engine.pool.num_free
    with pytest.raises(ValueError, match="max_len"):
        engine.prefill(0, tuple(range(2, 11)))
    assert engine.pool.num_free == free_before  # nothing leaked


# -- speculative decoding ---------------------------------------------------
#
# THE contract: spec_k only changes how many ticks a stream takes,
# never which tokens it emits. Every test here compares committed
# token streams with == (exact integer equality) against the plain
# spec_k=0 run — tolerance would hide a real divergence in the accept
# rule or the verify step's rollback.

def _spec_requests():
    # repetitive prompts give the n-gram drafter traction (suffixes
    # recur, so real accept/reject mixes are exercised, not just the
    # all-rejected path); the sampled requests pin the
    # fold_in(seed, n_generated + j) key alignment
    return [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=8),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=8,
                    temperature=0.8, seed=3),
            Request(prompt=(7, 11, 7, 11), max_new_tokens=6,
                    temperature=0.7, seed=9),
            Request(prompt=(13, 17, 19), max_new_tokens=5)]


def _spec_stats(params, cfg, requests, num_slots, spec_k,
                cache_dtype=jnp.bfloat16):
    engine = PagedDecodeEngine(params, cfg, num_slots=num_slots,
                               max_len=MAX_LEN, num_pages=24,
                               page_size=4, buckets=(16, 32),
                               spec_k=spec_k, cache_dtype=cache_dtype)
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS, audit=True)
    for r in requests:
        sched.submit(r)
    return sched.run(), sched.stats


#: What a speculating run's streams are held to: the plain ``spec_k=0``
#: run of the same engine (bfloat16 pool, bit for bit), or the full
#: forward's own streams (float32 pool).
ORACLES = pytest.mark.parametrize("oracle", ["full_forward", "plain"])


@ORACLES
@pytest.mark.parametrize("spec_k", [1, 2, 3])
def test_spec_stream_bit_identical_to_plain(spec_k, oracle):
    """Greedy + seeded-sampled requests through the draft→verify→accept
    loop: the committed streams equal the plain spec_k=0 streams
    token-for-token, and the full forward's, at every draft depth."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _spec_requests()
    if oracle == "plain":
        plain, _ = _spec_stats(params, cfg, reqs, 2, 0)
        spec, stats = _spec_stats(params, cfg, reqs, 2, spec_k)
    else:
        plain = _reference(params, cfg, reqs)
        spec, stats = _spec_stats(params, cfg, reqs, 2, spec_k,
                                  jnp.float32)
    assert spec == plain
    assert stats.tokens_drafted > 0  # the drafter actually proposed
    assert stats.tokens_accepted >= 0


def test_spec_accepts_make_progress():
    """On a maximally predictable greedy stream the accept walk must
    actually commit drafted tokens (acceptance_rate > 0) — otherwise
    spec mode silently degenerates to plain decode plus overhead."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(7, 11, 7, 11, 7, 11, 7), max_new_tokens=10)]
    plain, _ = _spec_stats(params, cfg, reqs, 1, 0)
    spec, stats = _spec_stats(params, cfg, reqs, 1, 3)
    assert spec == plain
    assert stats.tokens_accepted > 0
    assert 0.0 < stats.acceptance_rate <= 1.0


def test_spec_stream_independent_of_slot_placement():
    """The sampled probe request decodes to the same stream alone and
    crowded, under spec — keys stay a pure function of
    (seed, n_generated), never of slot index or batch mix."""
    cfg = _cfg()
    params = _params(cfg)
    probe = Request(prompt=(5, 7, 5, 7, 5), max_new_tokens=6,
                    temperature=0.8, seed=42)
    alone, _ = _spec_stats(params, cfg, [probe], 1, 2)
    filler = [Request(prompt=(2, 3, 2, 3), max_new_tokens=6,
                      temperature=0.9, seed=i) for i in range(3)]
    crowded, _ = _spec_stats(params, cfg, [probe] + filler, 4, 2)
    assert alone[0] == crowded[0]


def test_spec_respects_max_new_tokens_and_eos():
    """A verify tick can sample EOS or hit max_new_tokens mid-grid —
    the walk must stop committing exactly where the plain stream
    stops (never over-commit from an accepted tail)."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(7, 11, 7, 11), max_new_tokens=1),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=2),
            Request(prompt=(13, 17, 13, 17), max_new_tokens=16)]
    plain, _ = _spec_stats(params, cfg, reqs, 3, 0)
    spec, _ = _spec_stats(params, cfg, reqs, 3, 3)
    assert spec == plain
    assert len(spec[0]) == 1 and len(spec[1]) <= 2


def test_spec_near_max_len_degrades_to_plain():
    """When any active slot is within spec_k+1 rows of max_len the tick
    runs plain (the dynamic_update_slice clamp hazard) — streams still
    finish and match the plain run exactly."""
    cfg = _cfg()
    params = _params(cfg)
    # 5 prompt + 8 new = 13 of max_len 16: the last ticks CANNOT fit a
    # k=3 verify window, so the guard must kick in
    def run(spec_k):
        engine = PagedDecodeEngine(params, cfg, num_slots=1, max_len=16,
                                   num_pages=24, page_size=4,
                                   buckets=(8, 16), spec_k=spec_k)
        sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
        sched.submit(Request(prompt=(7, 11, 7, 11, 7),
                             max_new_tokens=8))
        return sched.run()

    assert run(3) == run(0)


def test_paged_submit_validates_page_demand():
    cfg = _cfg()
    engine = PagedDecodeEngine(_params(cfg), cfg, num_slots=1,
                               max_len=MAX_LEN, num_pages=5, page_size=4,
                               buckets=(16, 32))
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    with pytest.raises(ValueError, match="pages"):
        # 3 usable pages = 12 rows; 5 prompt + 8 new = 13 can't fit
        sched.submit(Request(prompt=(2, 3, 5, 7, 11), max_new_tokens=8))
    sched.submit(Request(prompt=(2, 3, 5, 7, 11), max_new_tokens=7))
    outs = sched.run()
    assert len(outs) == 1 and 1 <= len(outs[0]) <= 7


# -- model-based & tree speculation -----------------------------------------
#
# Same contract as linear n-gram spec, new machinery: a TP-shardable
# draft GPT proposes the candidates (DraftModel), optionally as trees
# verified in one forward through the ancestor-matrix mask, with a
# per-stream adaptive depth controller. Every mode must keep committed
# streams integer-identical to plain spec_k=0 decode.

def _draft_for(params, cfg, num_slots):
    # the TARGET doubles as its own drafter: acceptance is high, so the
    # accept walk, the tree path commit, and the draft-cache resync all
    # run on real accept/reject mixes instead of the all-rejected path
    from apex_tpu.serving import DraftModel
    return DraftModel(params, cfg, num_slots=num_slots, max_len=MAX_LEN)


def _model_spec_run(params, cfg, requests, num_slots, spec_k,
                    tree=False, adaptive=False, self_draft=True,
                    cache_dtype=jnp.bfloat16):
    if self_draft:
        dm = _draft_for(params, cfg, num_slots) if spec_k else None
    else:  # a genuinely different (randomly-initialised) draft net
        dm = (None if not spec_k else
              _draft_for(init_gpt(jax.random.PRNGKey(99), cfg), cfg,
                         num_slots))
    kw = dict(spec_k=spec_k, draft_model=dm, tree_spec=tree,
              adaptive_spec=adaptive)
    if not spec_k:
        kw = {}
    engine = PagedDecodeEngine(params, cfg, num_slots=num_slots,
                               max_len=MAX_LEN, num_pages=24,
                               page_size=4, buckets=(16, 32),
                               cache_dtype=cache_dtype, **kw)
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS, audit=True)
    for r in requests:
        sched.submit(r)
    return sched.run(), sched.stats


def _model_spec_against(oracle, params, cfg, reqs, **kw):
    """(streams, what they are held to, stats) of a model-drafted run."""
    if oracle == "plain":
        plain, _ = _model_spec_run(params, cfg, reqs, 2, 0)
        spec, stats = _model_spec_run(params, cfg, reqs, 2, 3, **kw)
    else:
        plain = _reference(params, cfg, reqs)
        spec, stats = _model_spec_run(params, cfg, reqs, 2, 3,
                                      cache_dtype=jnp.float32, **kw)
    return spec, plain, stats


@ORACLES
def test_model_draft_stream_bit_identical_to_plain(oracle):
    """Model-drafted linear speculation (greedy + seeded sampled): the
    committed streams equal the plain run token-for-token, and the
    self-draft actually lands accepts (the resync path is exercised on
    both full and partial acceptance)."""
    cfg = _cfg()
    params = _params(cfg)
    spec, plain, stats = _model_spec_against(
        oracle, params, cfg, _spec_requests())
    assert spec == plain
    assert stats.tokens_drafted > 0
    assert stats.tokens_accepted > 0  # self-draft must make progress


@ORACLES
def test_tree_spec_stream_bit_identical_to_plain(oracle):
    """Tree speculation: multi-branch drafts verified in ONE forward
    via the ancestor mask, the accept walk following the committed
    root-to-leaf path. Streams stay integer-identical to plain decode
    and to the full forward's, and the tree path commits accepted
    tokens."""
    cfg = _cfg()
    params = _params(cfg)
    spec, plain, stats = _model_spec_against(
        oracle, params, cfg, _spec_requests(), tree=True)
    assert spec == plain
    assert stats.spec_ticks > 0
    assert stats.tokens_accepted > 0


def test_tree_spec_with_mismatched_draft_still_exact():
    """A randomly-initialised draft net proposes mostly-wrong trees —
    the rejected tails and the forced-chain re-sends must still leave
    the committed stream exactly equal to plain decode (the rollback /
    resync contract under worst-case rejection)."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _spec_requests()
    plain, _ = _model_spec_run(params, cfg, reqs, 2, 0)
    spec, _ = _model_spec_run(params, cfg, reqs, 2, 3,
                              tree=True, self_draft=False)
    assert spec == plain


def test_ngram_tree_spec_matches_plain():
    """tree_spec without a draft model: n-gram chains ride the tree
    verify path as single-branch trees. Still exact."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _spec_requests()
    engine = _engine(params, cfg, 2, spec_k=3, tree_spec=True)
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS)
    for r in reqs:
        sched.submit(r)
    plain, _ = _model_spec_run(params, cfg, reqs, 2, 0)
    assert sched.run() == plain


def test_adaptive_controller_converges_to_plain():
    """On an adversarial stream (high-temperature sampling against a
    mismatched draft net) the per-stream EWMA controller must shrink
    spec_k to plain ticks: the run stays integer-identical to plain
    decode, most ticks are plain, and the tick count never exceeds the
    plain run's (each tick commits >= 1 token, so adaptive spec can
    only match or beat plain pace — the same-process A/B contract)."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(3, 1, 4, 1, 5), max_new_tokens=20,
                    temperature=5.0, seed=123),
            Request(prompt=(2, 7, 1, 8), max_new_tokens=20,
                    temperature=4.0, seed=77)]
    plain, pstats = _model_spec_run(params, cfg, reqs, 2, 0)
    out, stats = _model_spec_run(params, cfg, reqs, 2, 4,
                                 adaptive=True, self_draft=False)
    assert out == plain
    assert stats.plain_ticks > stats.spec_ticks  # converged toward plain
    assert (stats.plain_ticks + stats.spec_ticks
            <= pstats.plain_ticks)  # never slower than plain (in ticks)


def test_adaptive_controller_keeps_speculating_when_accepted():
    """The flip side: with the target as its own drafter, acceptance
    stays high and the controller must KEEP the depth up (mostly spec
    ticks), finishing in fewer ticks than plain decode."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=12),
            Request(prompt=(13, 17, 19), max_new_tokens=12)]
    plain, pstats = _model_spec_run(params, cfg, reqs, 2, 0)
    out, stats = _model_spec_run(params, cfg, reqs, 2, 3,
                                 adaptive=True)
    assert out == plain
    assert stats.spec_ticks > 0
    assert (stats.plain_ticks + stats.spec_ticks
            < pstats.plain_ticks)  # strictly fewer parameter reads


def test_spec_config_validation():
    """draft_model / tree_spec / adaptive_spec all require spec_k >= 1;
    the draft net must match the target's slot count and vocab; tree
    verify refuses the int8 page pool."""
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="spec_k"):
        _engine(params, cfg, 1, tree_spec=True)
    with pytest.raises(ValueError, match="spec_k"):
        _engine(params, cfg, 1, adaptive_spec=True)
    with pytest.raises(ValueError, match="slots"):
        _engine(params, cfg, 2, spec_k=2,
                draft_model=_draft_for(params, cfg, 1))
    with pytest.raises(ValueError, match="int8"):
        PagedDecodeEngine(params, cfg, num_slots=1, max_len=MAX_LEN,
                          num_pages=24, page_size=4, spec_k=2,
                          tree_spec=True, cache_dtype=jnp.int8)


def _other_vocab_draft(params, cfg):
    from apex_tpu.serving import DraftModel
    small = dataclasses.replace(cfg, vocab_size=cfg.vocab_size // 2)
    return DraftModel(init_gpt(jax.random.PRNGKey(1), small), small,
                      num_slots=1, max_len=MAX_LEN)


#: Every refusal the ONE engine's constructor makes for a GPT config (the
#: families on the model seam have theirs in ``test_*_engine.py``), by the
#: words a user reads: name -> (what was asked for, the message).
_REFUSED = {
    "buckets_cut_pages": (dict(buckets=(6, 32)),
                          r"buckets \[6\] are not multiples of page_size 4"),
    "draft_without_spec_k": (
        dict(draft_model=lambda p, c: _draft_for(p, c, 1)),
        "draft_model / tree_spec / adaptive_spec require spec_k >= 1"),
    "draft_of_another_vocabulary": (
        dict(spec_k=2, draft_model=_other_vocab_draft),
        r"must share a vocabulary \(256 vs 512\)"),
    "tree_verify_over_int8": (
        dict(spec_k=2, tree_spec=True, cache_dtype=jnp.int8),
        "tree verify is not offered over the int8 page pool"),
    "host_tier_without_a_wire_tag": (
        dict(cache_dtype=jnp.float8_e4m3fn, host_tier="registry"),
        "has no spill wire tag"),
    "pool_of_reserved_pages_only": (
        dict(num_pages=2), "must exceed the 2 reserved pages"),
    "no_slots": (dict(num_slots=0), "need positive num_slots"),
    "free_order_not_a_permutation": (
        dict(free_order=[2, 2, 3]), "free_order must be a permutation"),
    "learned_positions_too_few": (
        dict(learned=True, max_len=1024),
        "exceeds the learned position table"),
}


@pytest.mark.parametrize("asked,message", list(_REFUSED.values()),
                         ids=list(_REFUSED))
def test_engine_refuses_at_construction(asked, message):
    from apex_tpu.serving import PrefixRegistry

    asked = dict(asked)
    cfg = _cfg()
    if asked.pop("learned", False):
        cfg = dataclasses.replace(cfg, use_rope=False)
    params = _params(cfg)
    if callable(asked.get("draft_model")):
        asked["draft_model"] = asked["draft_model"](params, cfg)
    if "host_tier" in asked:
        asked["host_tier"] = PrefixRegistry(1 << 20)
    kw = dict(num_slots=1, max_len=MAX_LEN, num_pages=10, page_size=4,
              buckets=(16, 32))
    kw.update(asked)
    with pytest.raises(ValueError, match=message):
        PagedDecodeEngine(params, cfg, **kw)


# -- chunked prefill ---------------------------------------------------------
#
# Same invariance contract as speculation: ``chunk_tokens=`` only moves
# WHEN prompt work runs (between decode ticks, under the tick token
# budget), never which tokens any stream commits. Every comparison is
# exact integer equality against the synchronous (monolithic-admission)
# scheduler.


def _chunky_requests():
    """_mixed_requests stretched: prompts long enough that
    chunk_tokens in {4, 8} actually splits them, with a shared prefix
    pair and mixed greedy/sampled."""
    base = (7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47)
    return [Request(prompt=base, max_new_tokens=5),
            Request(prompt=base[:9], max_new_tokens=5,
                    temperature=0.8, seed=3),
            Request(prompt=base + (53, 59, 61), max_new_tokens=4),
            Request(prompt=(5, 3), max_new_tokens=5,
                    temperature=0.7, seed=9)]


def _run_chunked(params, cfg, requests, num_slots, chunk_tokens,
                 num_pages=24, spec_k=0, tick_token_budget=None):
    # fp32 cache on BOTH sides of every comparison: the identity
    # contract is "chunking moves when prompt work runs, never the
    # math" — at bf16 the cache itself rounds K/V, so a monolithic
    # forward (unrounded in-forward activations) and a chunked one
    # (re-read rounded cache) can legitimately differ in the last bit.
    engine = PagedDecodeEngine(params, cfg, num_slots=num_slots,
                               max_len=MAX_LEN, num_pages=num_pages,
                               page_size=4, buckets=(16, 32),
                               spec_k=spec_k, cache_dtype=jnp.float32)
    sched = ContinuousBatchingScheduler(
        engine, eos_id=EOS, audit=True, chunk_tokens=chunk_tokens,
        tick_token_budget=tick_token_budget)
    for r in requests:
        sched.submit(r)
    return sched.run(), sched


@pytest.mark.parametrize("chunk_tokens", [4, 8])
def test_chunked_streams_match_full_forward(chunk_tokens):
    cfg = _cfg()
    params = _params(cfg)
    reqs = _chunky_requests()
    want = _reference(params, cfg, reqs)
    got, sched = _run_chunked(params, cfg, reqs, 2, chunk_tokens)
    assert got == want
    # the prompts really were split, not admitted in one piece
    assert sched.stats.prefill_chunks > len(reqs)


@pytest.mark.parametrize("chunk_tokens", [4, 8])
def test_chunked_streams_match_sync_paged(chunk_tokens):
    cfg = _cfg()
    params = _params(cfg)
    reqs = _chunky_requests()
    want, _ = _run_chunked(params, cfg, reqs, 2, None)
    got, sched = _run_chunked(params, cfg, reqs, 2, chunk_tokens)
    assert got == want
    assert sched.stats.prefill_chunks > len(reqs)


def test_chunked_streams_invariant_to_tick_token_budget():
    """The budget only throttles how many chunks share a tick — a huge
    budget (whole prompts per tick) and the tight default must commit
    the same tokens."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _chunky_requests()
    tight, _ = _run_chunked(params, cfg, reqs, 2, 4)
    wide, _ = _run_chunked(params, cfg, reqs, 2, 4,
                           tick_token_budget=64)
    assert tight == wide


def test_chunked_spec_streams_match_plain_sync():
    """Chunked prefill composes with speculative decode: the chunked +
    speculating scheduler still matches the plain synchronous one
    token-for-token (spec == plain and chunked == sync, composed)."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(7, 11, 7, 11, 7, 11, 7, 11, 7, 11),
                    max_new_tokens=6),
            Request(prompt=(5, 3, 5, 3, 5, 3, 5, 3), max_new_tokens=6,
                    temperature=0.8, seed=3)]
    want, _ = _run_chunked(params, cfg, reqs, 2, None)
    got, sched = _run_chunked(params, cfg, reqs, 2, 4,
                              spec_k=3)
    assert got == want
    assert sched.stats.prefill_chunks > len(reqs)
    assert sched.stats.tokens_drafted > 0  # speculation really ran


def test_chunked_final_logits_match_one_shot_paged():
    """Engine-level contract: the final chunk's last-token logits are a
    one-shot prefill's of the same prompt to float32 rounding: the same
    argmax, and no logit further off than 32 ulps of the largest one
    (5.5 measured here, 9 with XLA's fusion off). They are NOT bitwise
    equal and never were on this installation: the two are different
    programs. The one-shot prefill attends over its 16-token bucket
    through ``flash_attention`` (scores times ``1/sqrt(hd)``, its own
    softmax); a chunk attends over the ``max_len`` gathered positions
    (scores divided by ``sqrt(hd)``, ``jax.nn.softmax``); and XLA fuses
    the two differently, so even layer 0's K rows, equal stage by
    stage, land a float32 ulp apart. What the scheduler promises on top
    of this is the neighbouring tests': the committed token streams are
    identical."""
    import numpy as np

    cfg = _cfg()
    params = _params(cfg)
    prompt = tuple(range(2, 2 + 13))    # 13 tokens -> 4 chunks of 4

    def engine():
        # fp32 cache for the same reason as _run_chunked: the bound is
        # float32's only where the cache itself doesn't round
        return PagedDecodeEngine(params, cfg, num_slots=1,
                                 max_len=MAX_LEN, num_pages=24,
                                 page_size=4, buckets=(16, 32),
                                 cache_dtype=jnp.float32)

    one_shot = np.asarray(engine().prefill(0, prompt))
    eng = engine()
    state = eng.begin_chunk_prefill(0, prompt)
    pos, ct = int(state.get("start", 0)), 4
    while True:
        chunk = prompt[pos:pos + ct]
        final = pos + ct >= len(prompt)
        logits = eng.chunk_prefill(0, chunk, pos, state, ct, final)
        if final:
            break
        pos += ct
    eng.finish_chunk_prefill(0, state)
    eng.check_invariants()
    chunked = np.asarray(logits)
    assert chunked.argmax() == one_shot.argmax()
    ulp = np.spacing(np.abs(one_shot).max())
    assert np.abs(chunked - one_shot).max() <= 32 * ulp


def test_chunked_bounds_cotenant_itl_tail_on_the_tick_clock():
    """The point of the feature, on the deterministic work-charged
    clock: a long prompt admitted mid-run opens an inter-token gap in
    the co-tenant stream equal to its WHOLE prefill when monolithic,
    but bounded near chunk_tokens when chunked — with the committed
    streams themselves identical."""
    from apex_tpu.serving import Tracer

    cfg = _cfg()
    params = _params(cfg)
    reqs = [Request(prompt=(5, 3), max_new_tokens=12),
            Request(prompt=(3, 5), max_new_tokens=4),
            Request(prompt=tuple(range(2, 26)), max_new_tokens=2)]

    def run(chunk_tokens):
        trc = Tracer()
        engine = PagedDecodeEngine(params, cfg, num_slots=2,
                                   max_len=MAX_LEN, num_pages=24,
                                   page_size=4, buckets=(16, 32),
                                   tracer=trc)
        # eos_id=-1: unreachable, so the co-tenant really decodes all
        # 12 tokens while the long prompt prefills
        sched = ContinuousBatchingScheduler(engine, eos_id=-1,
                                            chunk_tokens=chunk_tokens)
        for r in reqs:
            sched.submit(r)
        return sched.run(), trc.latency_summary()["itl_p99"]

    streams_c, tail_chunked = run(4)
    streams_m, tail_mono = run(None)
    assert streams_c == streams_m       # identity first, then latency
    assert tail_chunked < tail_mono     # the tail actually collapsed


def test_chunk_config_validation():
    """chunk_tokens must be >= 1, divide max_len, be page-aligned on a
    paged engine, and is refused over the int8 page pool; the tick
    token budget must be positive."""
    cfg = _cfg()
    params = _params(cfg)
    paged = PagedDecodeEngine(params, cfg, num_slots=1, max_len=MAX_LEN,
                              num_pages=8, page_size=4,
                              buckets=(16, 32))
    with pytest.raises(ValueError, match=">= 1"):
        ContinuousBatchingScheduler(paged, eos_id=EOS, chunk_tokens=0)
    with pytest.raises(ValueError, match="divide"):
        ContinuousBatchingScheduler(paged, eos_id=EOS, chunk_tokens=5)
    with pytest.raises(ValueError, match="page_size"):
        ContinuousBatchingScheduler(paged, eos_id=EOS, chunk_tokens=2)
    int8 = PagedDecodeEngine(params, cfg, num_slots=1, max_len=MAX_LEN,
                             num_pages=8, page_size=4, buckets=(16, 32),
                             cache_dtype=jnp.int8)
    with pytest.raises(ValueError, match="int8"):
        ContinuousBatchingScheduler(int8, eos_id=EOS, chunk_tokens=4)
    with pytest.raises(ValueError, match="tick_token_budget"):
        ContinuousBatchingScheduler(paged, eos_id=EOS, chunk_tokens=4,
                                    tick_token_budget=0)
