"""Chaos tier: deterministic fault injection against the serving
engine's graceful-degradation layer.

Every test drives the REAL scheduler/engine through the named fault
sites (``serving.faults.SITES``) and checks the degradation contract:

- every submitted request ends in a typed ``RequestOutcome``;
- pool invariants hold after every tick (``audit=True``);
- a stream untouched by faults is BIT-IDENTICAL to the fault-free
  golden run, and a degraded request's tokens are a PREFIX of its
  golden stream (quarantine never commits a corrupt token);
- the same seed replays the same faults and the same outcomes.

``eos_id=-1`` throughout: no token can match it, so golden streams
always run to ``max_new_tokens`` and prefix assertions are exact.
"""

import dataclasses
import os

import jax
import pytest

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (
    AdmissionRejected, ContinuousBatchingScheduler, DeadlineExceeded,
    FaultInjector, LivelockError, PagedDecodeEngine,
    PoolInvariantError, Request, RetryBudgetExhausted, FINISH_REASONS,
    Tracer,
)
from apex_tpu.serving.faults import SITES, fault_draw

pytestmark = pytest.mark.chaos

EOS = -1       # unreachable: every healthy stream runs to max_new_tokens
MAX_LEN = 32


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True,
                              hidden_dropout=0.0)
    return cfg, init_gpt(jax.random.PRNGKey(0), cfg)


def _engine(model, injector=None, num_slots=2, num_pages=20, **kw):
    cfg, params = model
    # tracing is ON for the whole chaos tier: every bit-identity /
    # golden-equality contract below must hold with the observability
    # hooks live (they are host-side and must never perturb a stream)
    kw.setdefault("tracer", Tracer())
    return PagedDecodeEngine(params, cfg, num_slots=num_slots,
                             max_len=MAX_LEN, num_pages=num_pages,
                             page_size=4, buckets=(16, 32),
                             injector=injector, **kw)


def _drive(engine, reqs, **kw):
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS, **kw)
    for r in reqs:
        sched.submit(r)
    return sched, sched.run()


def _golden(model, reqs, num_slots=2):
    _, outs = _drive(_engine(model, num_slots=num_slots), reqs)
    return outs


def _check_contract(sched, reqs, golden):
    """The degradation contract every chaos run must satisfy."""
    assert sorted(sched.outcomes) == list(range(len(reqs)))
    for rid, out in sched.outcomes.items():
        assert out.reason in FINISH_REASONS
        want = golden[rid]
        if out.ok:
            assert list(out.tokens) == want, f"request {rid} diverged"
        else:   # degraded: committed tokens are a golden prefix
            assert list(out.tokens) == want[:len(out.tokens)], \
                f"request {rid}: degraded stream is not a golden prefix"


# -- the injector itself -----------------------------------------------------

def test_fault_draw_is_pure():
    """Schedules are pure functions of (seed, site, index) — the
    replay guarantee rests on this, not on any RNG state."""
    assert fault_draw(3, "sample", 7) == fault_draw(3, "sample", 7)
    draws = {fault_draw(s, site, i) for s in (0, 1) for site in SITES
             for i in (0, 5)}
    assert len(draws) == 2 * len(SITES) * 2  # no collisions across keys
    u01s = [fault_draw(0, "pool_alloc", i)[0] for i in range(200)]
    assert all(0.0 <= u < 1.0 for u in u01s)
    # roughly uniform: a rate-0.5 site fires about half the time
    assert 60 < sum(u < 0.5 for u in u01s) < 140


def test_injector_inert_by_default_and_validates_sites():
    inert = FaultInjector()
    assert not inert.armed
    assert all(not inert.fire(s) for s in SITES for _ in range(50))
    assert inert.counts == {s: 0 for s in SITES}
    assert inert.calls("sample") == 50
    with pytest.raises(ValueError, match="unknown fault sites"):
        FaultInjector(rates={"warp_core": 1.0})
    with pytest.raises(ValueError, match="unknown fault sites"):
        FaultInjector(schedule={"holodeck": (0,)})
    with pytest.raises(KeyError):
        inert.fire("not_a_site")


def test_injector_schedule_replays_bit_for_bit():
    """Same seed, same visit order -> same fired pattern; pinned
    schedule entries fire regardless of rates."""
    a = FaultInjector(seed=11, rates={"decode_exec": 0.3})
    b = FaultInjector(seed=11, rates={"decode_exec": 0.3})
    pat_a = [a.draw("decode_exec") for _ in range(64)]
    assert pat_a == [b.draw("decode_exec") for _ in range(64)]
    assert any(f for f, _ in pat_a)
    c = FaultInjector(seed=12, rates={"decode_exec": 0.3})
    assert pat_a != [c.draw("decode_exec") for _ in range(64)]
    pinned = FaultInjector(schedule={"prefill_exec": (2,)})
    assert [pinned.fire("prefill_exec") for _ in range(4)] == [
        False, False, True, False]


# -- one site at a time, pinned schedules ------------------------------------

def test_pool_alloc_fault_recovers_to_golden(model):
    """A transient allocation refusal parks the admission (typed
    internally as PoolExhausted, no retry charged — capacity is not the
    request's fault) and the next tick succeeds bit-identically."""
    reqs = [Request(prompt=(7, 11, 13, 17, 19), max_new_tokens=4)]
    golden = _golden(model, reqs)
    eng = _engine(model, FaultInjector(schedule={"pool_alloc": (0,)}))
    sched, outs = _drive(eng, reqs, audit=True)
    assert outs == golden
    assert sched.stats.pool_exhausted == 1
    assert sched.stats.retries == 0
    assert sched.outcomes[0].ok


def test_cow_clone_fault_preempts_and_recovers(model):
    """A failed copy-on-write clone preempts the slot (pages released,
    request requeued with its progress); the resumed stream matches the
    fault-free run exactly."""
    reqs = [Request(prompt=(7, 11, 13, 17, 19), max_new_tokens=4)]
    golden = _golden(model, reqs)
    eng = _engine(model, FaultInjector(schedule={"cow_clone": (0,)}))
    sched, outs = _drive(eng, reqs, audit=True)
    assert outs == golden
    assert sched.stats.preemptions == 1
    assert sched.stats.cow_copies >= 1  # the retried clone succeeded
    assert sched.outcomes[0].ok


def test_prefill_exec_fault_retries_to_golden(model):
    """A transient prefill failure charges the retry budget and leaves
    nothing behind (audit on); the retried admission is bit-identical."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=4)]
    golden = _golden(model, reqs)
    eng = _engine(model, FaultInjector(schedule={"prefill_exec": (0,)}))
    sched, outs = _drive(eng, reqs, audit=True)
    assert outs == golden
    assert sched.stats.retries == 1
    assert sched.outcomes[0].ok and sched.outcomes[0].retries == 1


def test_decode_nan_quarantine_keeps_cotenant_bit_identical(model):
    """A NaN decode row quarantines ONE slot. With a zero retry budget
    the victim terminates typed, its tokens a golden prefix — and the
    co-tenant stream must be bit-identical to the fault-free run (the
    corrupt row never touches other slots' logits or keys)."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=5),
            Request(prompt=(23, 29), max_new_tokens=5)]
    golden = _golden(model, reqs)
    eng = _engine(model, FaultInjector(schedule={"decode_exec": (0,)}))
    sched, _ = _drive(eng, reqs, audit=True, max_retries=0)
    assert sched.stats.nan_events == 1
    bad = [rid for rid, o in sched.outcomes.items() if not o.ok]
    assert len(bad) == 1
    victim = sched.outcomes[bad[0]]
    assert victim.reason == "retry_budget"
    assert isinstance(victim.error, RetryBudgetExhausted)
    # first token (from prefill) committed, the corrupt one never was
    assert list(victim.tokens) == golden[bad[0]][:1]
    ok = (set(sched.outcomes) - set(bad)).pop()
    assert list(sched.outcomes[ok].tokens) == golden[ok]


def test_decode_nan_quarantine_retry_is_bit_identical(model):
    """Same fault, default retry budget: the victim resumes from its
    committed tokens and BOTH streams equal the golden run exactly."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=5),
            Request(prompt=(23, 29), max_new_tokens=5)]
    golden = _golden(model, reqs)
    eng = _engine(model, FaultInjector(schedule={"decode_exec": (0,)}))
    sched, outs = _drive(eng, reqs, audit=True)
    assert outs == golden
    assert sched.stats.nan_events == 1
    assert all(o.ok for o in sched.outcomes.values())


def test_sample_fault_at_admission_recovers(model):
    """An out-of-vocabulary first token is caught by the admission
    range gate; the request retries and matches golden."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=4,
                    temperature=0.8, seed=5)]
    golden = _golden(model, reqs)
    eng = _engine(model, FaultInjector(schedule={"sample": (0,)}))
    sched, outs = _drive(eng, reqs, audit=True)
    assert outs == golden
    assert sched.stats.bad_samples == 1
    assert sched.outcomes[0].ok and sched.outcomes[0].retries == 1


def _spec_engine(model, injector=None, spec_k=3, num_pages=20):
    cfg, params = model
    return PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=4,
                             buckets=(16, 32), spec_k=spec_k,
                             injector=injector)


def test_draft_fault_degrades_to_plain_and_recovers(model):
    """A mid-stream draft fault degrades that slot to an empty draft
    (an all-empty tick runs plain decode) for the tick — drafting is
    best-effort, so NO retry budget is charged — and the recovered
    stream is bit-identical to both the fault-free speculative golden
    and the never-speculated plain run."""
    reqs = [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=6),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=6,
                    temperature=0.8, seed=3)]

    def run(injector=None):
        return _drive(_spec_engine(model, injector), reqs, audit=True)

    _, golden = run()
    assert golden == _golden(model, reqs)  # spec == plain, fault-free
    sched, outs = run(FaultInjector(schedule={"draft_exec": (1, 4)}))
    assert outs == golden
    assert sched.stats.draft_faults == 2
    assert sched.stats.retries == 0
    assert all(o.ok for o in sched.outcomes.values())
    # degraded ticks still drafted nothing FOR THE VICTIM only: the
    # co-tenant kept speculating (drafted counters moved)
    assert sched.stats.tokens_drafted > 0


def _model_spec_engine(model, injector=None, spec_k=3, tree=False,
                       num_pages=20):
    from apex_tpu.serving import DraftModel

    cfg, params = model
    dm = DraftModel(params, cfg, num_slots=2, max_len=MAX_LEN)
    return PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=4,
                             buckets=(16, 32), spec_k=spec_k,
                             draft_model=dm, tree_spec=tree,
                             injector=injector)


def test_model_draft_fault_ladder_degrades_and_recovers(model):
    """The draft_exec LADDER on a model-drafting engine: one fired draw
    mid-stream degrades that tick from model drafts to n-gram drafts
    (one draft_fault, no retry charged); a consecutive fired pair kills
    the tick's drafting entirely (plain tick, two draft_faults). Both
    degradations recover bit-identical to the fault-free golden — the
    draft cache's resync-by-common-prefix absorbs the skipped ticks."""
    reqs = [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=6),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=6,
                    temperature=0.8, seed=3)]

    def run(injector=None):
        return _drive(_model_spec_engine(model, injector), reqs,
                      audit=True)

    _, golden = run()
    assert golden == _golden(model, reqs)  # model spec == plain decode
    # rung 1: model draft -> n-gram draft for the tick
    sched, outs = run(FaultInjector(schedule={"draft_exec": (1,)}))
    assert outs == golden
    assert sched.stats.draft_faults == 1
    assert sched.stats.retries == 0
    # rung 2: n-gram fails too -> plain tick, still golden
    sched, outs = run(FaultInjector(schedule={"draft_exec": (1, 2)}))
    assert outs == golden
    assert sched.stats.draft_faults == 2
    assert sched.stats.retries == 0
    assert all(o.ok for o in sched.outcomes.values())


def test_tree_spec_fault_ladder_recovers(model):
    """Same ladder under TREE speculation: a degraded tick loses its
    draft trees (n-gram chains or a plain tick) but the committed
    streams stay bit-identical to the fault-free tree golden."""
    reqs = [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=6),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=6,
                    temperature=0.8, seed=3)]

    def run(injector=None):
        return _drive(_model_spec_engine(model, injector, tree=True),
                      reqs, audit=True)

    _, golden = run()
    assert golden == _golden(model, reqs)
    sched, outs = run(FaultInjector(schedule={"draft_exec": (1, 2)}))
    assert outs == golden
    assert sched.stats.draft_faults == 2
    assert sched.stats.retries == 0


@pytest.mark.parametrize("seed", [0, 1])
def test_spec_multi_fault_chaos_is_typed_prefixed_and_replayable(
        model, seed):
    """Randomized faults at every site INCLUDING draft_exec against the
    speculative scheduler: typed outcomes, golden-prefix degradation,
    bit-for-bit replay — the spec tick must compose with quarantine,
    preemption and retry exactly like the plain one."""
    reqs = [Request(prompt=(7, 11, 7, 11), max_new_tokens=5),
            Request(prompt=(17, 19, 17, 19), max_new_tokens=5,
                    temperature=0.8, seed=3),
            Request(prompt=(7, 11, 13, 29), max_new_tokens=4),
            Request(prompt=(5, 3, 5, 3), max_new_tokens=6,
                    temperature=0.7, seed=9)]
    golden = _golden(model, reqs)
    rates = {"pool_alloc": 0.1, "cow_clone": 0.2, "prefill_exec": 0.15,
             "decode_exec": 0.1, "sample": 0.1, "draft_exec": 0.3}

    def chaos_run():
        eng = _spec_engine(model,
                           FaultInjector(seed=seed, rates=rates),
                           num_pages=14)
        sched, _ = _drive(eng, reqs, audit=True)
        return sched

    sched = chaos_run()
    _check_contract(sched, reqs, golden)
    replay = chaos_run()
    assert replay.outcomes == sched.outcomes
    assert replay.stats.as_dict() == sched.stats.as_dict()
    assert replay.engine.injector.counts == sched.engine.injector.counts


# -- the gate and the sampler are one program, read back once ------------------

#: (nan_events, bad_samples, retries, faults fired, sites consulted) of the
#: run below as the tree BEFORE the two were one program gave them (commit
#: ef5603f: ``finite`` and ``sample`` two programs, each waited for), by
#: (mode, injector seed). ``decode_exec`` is drawn by the step, ``sample``
#: behind the sampler's launch: one order of draws, then as now.
_GATE_CHAOS = {
    ("plain", 0): (2, 3, 5, (2, 3), (13, 18)),
    ("plain", 1): (1, 2, 3, (2, 2), (13, 18)),
    ("spec", 0): (1, 3, 4, (2, 3), (13, 17)),
    ("spec", 1): (1, 2, 3, (1, 2), (11, 15)),
    ("tree", 0): (1, 1, 2, (1, 3), (11, 16)),
    ("tree", 1): (1, 2, 3, (2, 2), (12, 16)),
    ("chunked", 0): (2, 2, 4, (2, 4), (18, 22)),
    ("chunked", 1): (3, 2, 5, (3, 4), (17, 22)),
}


@pytest.mark.parametrize("mode,seed", sorted(_GATE_CHAOS))
def test_decode_and_sample_faults_on_one_schedule_keep_their_counts(
        model, mode, seed):
    """``decode_exec`` and ``sample`` armed together: a NaN row and an
    out-of-range token are told apart although both now come back in one
    array (``nan_events`` against ``bad_samples``), every site is consulted
    as often and fires as often as before, as many retries are charged, and
    every stream recovers to the fault-free golden one."""
    reqs = [Request(prompt=(7, 11, 7, 11, 7), max_new_tokens=6),
            Request(prompt=(17, 19, 17, 19), max_new_tokens=6,
                    temperature=0.8, seed=3),
            Request(prompt=(7, 11, 13, 29), max_new_tokens=5),
            Request(prompt=(5, 3) * 5, max_new_tokens=6,
                    temperature=0.7, seed=9)]
    golden = _golden(model, reqs)
    inj = FaultInjector(seed=seed, rates={"decode_exec": 0.2,
                                          "sample": 0.2})
    if mode in ("spec", "tree"):
        eng = _model_spec_engine(model, inj, tree=True) if mode == "tree" \
            else _spec_engine(model, inj)
    else:
        eng = _engine(model, inj)
    sched, outs = _drive(eng, reqs, audit=True,
                         **({"chunk_tokens": 4} if mode == "chunked"
                            else {}))
    assert outs == golden
    assert all(o.ok for o in sched.outcomes.values())
    st = sched.stats
    sites = ("decode_exec", "sample")
    assert (st.nan_events, st.bad_samples, st.retries,
            tuple(inj.counts[s] for s in sites),
            tuple(inj.calls(s) for s in sites)) == _GATE_CHAOS[mode, seed]


def _nan_once(engine, method, when=lambda *a: True):
    """``engine.<method>`` returns NaN logits the first time ``when`` holds
    of its arguments: a prefill whose output is not finite, which no fault
    site makes (``prefill_exec`` raises before the program runs)."""
    real, done = getattr(engine, method), []

    def spoiled(*args, **kw):
        logits = real(*args, **kw)
        if not done and when(*args):
            done.append(True)
            return logits * float("nan")
        return logits

    setattr(engine, method, spoiled)


@pytest.mark.parametrize("fault", ["non_finite", "out_of_range"])
@pytest.mark.parametrize("path", ["admit", "finish_prefill"])
def test_first_token_gates_fail_the_admission_and_free_the_slot(
        model, path, fault):
    """Both gates on a request's FIRST token, read from one result of one
    program: a prefill that is not finite and a first token outside the
    vocabulary each fail the admission, monolithic (``_admit``) and after
    the final chunk (``_finish_prefill``) alike: the slot and its pages are
    freed, one retry is charged, the fault is counted under its own name,
    nothing is committed, and the retried request gives the golden stream."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=4,
                    temperature=0.8, seed=5)]
    golden = _golden(model, reqs)
    chunked = path == "finish_prefill"
    eng = _engine(model, FaultInjector(schedule={"sample": (0,)})
                  if fault == "out_of_range" else None)
    if fault == "non_finite":
        _nan_once(eng, *(("chunk_prefill", lambda *a: a[5]) if chunked
                         else ("prefill",)))
    sched = ContinuousBatchingScheduler(
        eng, eos_id=EOS, audit=True,
        **({"chunk_tokens": 4} if chunked else {}))
    sched.submit(reqs[0])
    sched.step()
    st = sched.stats
    counted = (1, 0) if fault == "non_finite" else (0, 1)
    assert (st.nan_events, st.bad_samples) == counted
    assert st.retries == 1 and sched._tokens_emitted == 0
    assert all(s is None for s in sched._slots)
    assert all(not pages for pages in eng._slot_pages)
    assert [rid for rid, _, _ in sched._queue] == [0]
    assert sched.run() == golden
    assert sched.outcomes[0].ok and sched.outcomes[0].retries == 1
    assert (st.nan_events, st.bad_samples) == counted      # and no more


# -- typed terminations ------------------------------------------------------

def test_retry_budget_exhausted_surfaces_typed(model):
    """A persistently failing request terminates with
    ``RetryBudgetExhausted`` carrying its id and retry count — it never
    wedges the scheduler."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=4)]
    eng = _engine(model,
                  FaultInjector(schedule={"prefill_exec": range(10)}))
    sched, outs = _drive(eng, reqs, audit=True, max_retries=2)
    assert outs == [[]]
    out = sched.outcomes[0]
    assert out.reason == "retry_budget" and not out.ok
    assert isinstance(out.error, RetryBudgetExhausted)
    assert out.error.request_id == 0
    assert out.retries == 3  # budget of 2 + the exhausting charge


def test_deadline_exceeded_queued_and_mid_decode(model):
    """Deadlines are scheduler ticks — deterministic. A request expiring
    while queued ends empty; one expiring mid-decode keeps its golden
    prefix."""
    probe = Request(prompt=(7, 11, 13), max_new_tokens=8)
    golden = _golden(model, [probe], num_slots=1)
    # starved in the queue behind a slot hog
    hog = Request(prompt=(23, 29), max_new_tokens=8)
    starved = dataclasses.replace(probe, deadline_ticks=2)
    sched, _ = _drive(_engine(model, num_slots=1), [hog, starved])
    out = sched.outcomes[1]
    assert out.reason == "deadline" and isinstance(out.error,
                                                   DeadlineExceeded)
    assert out.tokens == ()
    assert sched.stats.deadline_expired == 1
    # cut mid-decode: tokens committed so far are a golden prefix
    cut = dataclasses.replace(probe, deadline_ticks=3)
    sched2, _ = _drive(_engine(model, num_slots=1), [cut])
    out2 = sched2.outcomes[0]
    assert out2.reason == "deadline"
    assert 0 < len(out2.tokens) < len(golden[0])
    assert list(out2.tokens) == golden[0][:len(out2.tokens)]


def test_admission_backpressure(model):
    """A bounded queue sheds load typed instead of growing without
    bound; accepted requests are unaffected."""
    eng = _engine(model, num_slots=2)
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS, max_queue=2)
    sched.submit(Request(prompt=(7, 11), max_new_tokens=2))
    sched.submit(Request(prompt=(13, 17), max_new_tokens=2))
    with pytest.raises(AdmissionRejected):
        sched.submit(Request(prompt=(19, 23), max_new_tokens=2))
    assert sched.stats.admission_rejections == 1
    outs = sched.run()
    assert len(outs) == 2 and all(len(t) == 2 for t in outs)
    # queue drained: there is room again
    sched.submit(Request(prompt=(19, 23), max_new_tokens=2))


def test_livelock_watchdog_raises_with_diagnostics(model):
    """Regression for the PR-8 COW livelock, generalized: force the
    unfixable variant (every page always claims to need a copy on an
    exact-fit pool) and the watchdog must raise a diagnostic
    ``LivelockError`` — stuck request set + pool snapshot — instead of
    spinning forever."""
    from apex_tpu.serving.cache import RESERVED_PAGES

    cfg, params = model
    eng = PagedDecodeEngine(params, cfg, num_slots=1, max_len=MAX_LEN,
                            num_pages=2 + RESERVED_PAGES, page_size=4,
                            buckets=(16, 32))
    eng.pool.needs_copy = lambda page: True   # re-create the bug, hard
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS,
                                        watchdog_limit=8)
    sched.submit(Request(prompt=(7, 11, 13, 17, 19), max_new_tokens=3))
    with pytest.raises(LivelockError) as exc:
        sched.run()
    stuck = exc.value.stuck
    assert stuck["queued"] == [0] or stuck["slots"] == {0: 0}
    # the cycle ends each tick preempted: pages released back, nothing
    # leaked — the snapshot is the diagnostic that shows the pool was
    # NOT exhausted, i.e. a logic livelock rather than real pressure
    assert exc.value.pool["num_free"] == 2
    assert exc.value.pool["refcounts"] == {}
    assert exc.value.pool["slot_pages"] == [[]]


def test_invariant_audit_catches_corruption(model):
    """The audit actually detects broken books, host side and device
    side (a green chaos run is only meaningful if it can fail)."""
    eng = _engine(model, num_slots=1)
    eng.prefill(0, (7, 11, 13, 17, 19))
    eng.check_invariants()  # healthy baseline
    # host side: a slot claiming a reference the pool never granted
    eng._slot_pages[0].append(eng._slot_pages[0][0])
    with pytest.raises(PoolInvariantError, match="out of balance"):
        eng.check_invariants()
    eng._slot_pages[0].pop()
    eng.check_invariants()
    # device side: block table repointed behind the allocator's back
    eng.cache = eng.cache._replace(
        block_tables=eng.cache.block_tables.at[0, 0].set(9))
    with pytest.raises(PoolInvariantError, match="device row"):
        eng.check_invariants()


# -- randomized multi-fault chaos --------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_multi_fault_chaos_is_typed_prefixed_and_replayable(model, seed):
    """Randomized faults at every site at once, invariants audited
    after every tick. Every request must end typed; healthy outcomes
    equal the golden run bit-for-bit, degraded ones are golden
    prefixes; and replaying the same seed reproduces the run exactly."""
    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=5),
            Request(prompt=(17, 19), max_new_tokens=5,
                    temperature=0.8, seed=3),
            Request(prompt=(7, 11, 13, 29), max_new_tokens=4),
            Request(prompt=(23, 29, 31, 37, 41), max_new_tokens=6),
            Request(prompt=(7, 11, 13), max_new_tokens=5,
                    temperature=0.7, seed=9)]
    golden = _golden(model, reqs)
    rates = {"pool_alloc": 0.1, "cow_clone": 0.2, "prefill_exec": 0.15,
             "decode_exec": 0.1, "sample": 0.1}

    def chaos_run():
        eng = _engine(model, FaultInjector(seed=seed, rates=rates),
                      num_pages=12)
        sched, _ = _drive(eng, reqs, audit=True)
        return sched

    sched = chaos_run()
    _check_contract(sched, reqs, golden)
    replay = chaos_run()
    assert replay.outcomes == sched.outcomes
    assert replay.stats.as_dict() == sched.stats.as_dict()
    assert replay.engine.injector.counts == sched.engine.injector.counts
    # the deterministic tick-clock trace stream replays byte-exactly
    assert replay.engine.tracer.tick_stream() \
        == sched.engine.tracer.tick_stream()


# -- chunked prefill under faults --------------------------------------------

_CHUNKY = (7, 11, 13, 17, 19, 23, 29, 31, 37, 41)   # 10 tokens, 3 chunks


def test_chunk_prefill_fault_mid_prompt_requeues_clean(model):
    """A fault on the SECOND chunk of a staged prefill frees the slot
    with zero leaked pages or refcounts (audit runs every tick), charges
    one retry, and the retried request — restarted from the prompt
    head — commits a stream bit-identical to the fault-free golden."""
    reqs = [Request(prompt=_CHUNKY, max_new_tokens=4)]
    golden = _golden(model, reqs)
    eng = _engine(model,
                  FaultInjector(schedule={"chunk_prefill_exec": (1,)}))
    sched, outs = _drive(eng, reqs, audit=True, chunk_tokens=4)
    assert outs == golden
    assert sched.stats.retries == 1
    assert sched.outcomes[0].ok and sched.outcomes[0].retries == 1
    # nothing left behind: no slot holds pages, books balance
    eng.check_invariants()
    assert all(not pages for pages in eng._slot_pages)


def test_chunk_prefill_fault_on_final_chunk_recovers(model):
    """Same contract when the FINAL chunk faults — the chunk whose
    logits feed the first token. The staged progress is discarded whole
    and the retry is still bit-identical."""
    reqs = [Request(prompt=_CHUNKY, max_new_tokens=4,
                    temperature=0.8, seed=5)]
    golden = _golden(model, reqs)
    eng = _engine(model,
                  FaultInjector(schedule={"chunk_prefill_exec": (2,)}))
    sched, outs = _drive(eng, reqs, audit=True, chunk_tokens=4)
    assert outs == golden
    assert sched.stats.retries == 1
    assert sched.outcomes[0].ok
    eng.check_invariants()
    assert all(not pages for pages in eng._slot_pages)


def test_chunk_prefill_fault_exhausts_retry_budget_typed(model):
    """A persistently faulting chunk terminates typed with an empty
    stream — staged chunks never commit tokens — and leaks nothing."""
    reqs = [Request(prompt=_CHUNKY, max_new_tokens=4)]
    eng = _engine(model,
                  FaultInjector(schedule={"chunk_prefill_exec":
                                          range(20)}))
    sched, outs = _drive(eng, reqs, audit=True, chunk_tokens=4,
                         max_retries=2)
    assert outs == [[]]
    out = sched.outcomes[0]
    assert out.reason == "retry_budget" and not out.ok
    assert isinstance(out.error, RetryBudgetExhausted)
    eng.check_invariants()
    assert all(not pages for pages in eng._slot_pages)


@pytest.mark.parametrize("seed", [0, 1])
def test_chunked_multi_fault_chaos_is_typed_prefixed_and_replayable(
        model, seed):
    """The randomized sweep with chunked prefill on and the
    chunk_prefill_exec site armed: typed outcomes, golden-prefix
    degradation against the SYNCHRONOUS golden, bit-for-bit replay."""
    reqs = [Request(prompt=_CHUNKY, max_new_tokens=5),
            Request(prompt=_CHUNKY[:7], max_new_tokens=5,
                    temperature=0.8, seed=3),
            Request(prompt=(5, 3), max_new_tokens=4),
            Request(prompt=_CHUNKY + (43, 47), max_new_tokens=4,
                    temperature=0.7, seed=9)]
    golden = _golden(model, reqs)
    rates = {"pool_alloc": 0.1, "cow_clone": 0.2,
             "chunk_prefill_exec": 0.2, "decode_exec": 0.1,
             "sample": 0.1}

    def chaos_run():
        eng = _engine(model, FaultInjector(seed=seed, rates=rates),
                      num_pages=14)
        sched, _ = _drive(eng, reqs, audit=True, chunk_tokens=4)
        return sched

    sched = chaos_run()
    _check_contract(sched, reqs, golden)
    assert sched.engine.injector.counts["chunk_prefill_exec"] > 0 \
        or sched.stats.prefill_chunks > 0
    replay = chaos_run()
    assert replay.outcomes == sched.outcomes
    assert replay.stats.as_dict() == sched.stats.as_dict()
    assert replay.engine.injector.counts == sched.engine.injector.counts
    assert replay.engine.tracer.tick_stream() \
        == sched.engine.tracer.tick_stream()
    # CI post-mortem artifact: run_tests.sh chaos points this env var
    # at a tmp path and the workflow uploads the dumps
    out = os.environ.get("APEX_CHAOS_TRACE_OUT")
    if out:
        root, ext = os.path.splitext(out)
        sched.engine.tracer.dump_jsonl(
            f"{root}.seed{seed}{ext or '.jsonl'}")

@pytest.mark.slow
def test_multi_fault_chaos_on_int8_pool(model):
    """One seed of the randomized sweep on the QUANTIZED page pool
    (kv_dtype=int8): the degradation contract and bit-exact replay
    must hold with per-page scales riding the COW-clone, preemption
    and retry paths. Golden is the int8 engine's own fault-free run —
    the contract is about fault transparency, not quantization
    accuracy (that lives in test_quant.py / the L1 parity gate)."""
    import jax.numpy as jnp

    reqs = [Request(prompt=(7, 11, 13), max_new_tokens=5),
            Request(prompt=(17, 19), max_new_tokens=5,
                    temperature=0.8, seed=3),
            Request(prompt=(23, 29, 31, 37, 41), max_new_tokens=6),
            Request(prompt=(7, 11, 13), max_new_tokens=5,
                    temperature=0.7, seed=9)]
    _, golden = _drive(_engine(model, cache_dtype=jnp.int8), reqs)
    rates = {"pool_alloc": 0.1, "cow_clone": 0.2, "prefill_exec": 0.15,
             "decode_exec": 0.1, "sample": 0.1}

    def chaos_run():
        eng = _engine(model, FaultInjector(seed=1, rates=rates),
                      num_pages=12, cache_dtype=jnp.int8)
        sched, _ = _drive(eng, reqs, audit=True)
        return sched

    sched = chaos_run()
    _check_contract(sched, reqs, golden)
    replay = chaos_run()
    assert replay.outcomes == sched.outcomes
    assert replay.stats.as_dict() == sched.stats.as_dict()
    assert replay.engine.injector.counts == sched.engine.injector.counts


def test_error_taxonomy_contract():
    """Every SITE_CONTRACTS degrade error is a real taxonomy class,
    every taxonomy class carries the payload contract, and the table
    covers SITES exactly — the static half of what apxlint APX802/
    APX803 verify, exercised live so a rename breaks a test before it
    breaks the lint."""
    from apex_tpu.serving import (
        InjectedFault, NonFiniteLogits, PromoteFailed, QuotaExhausted,
        ReplicaUnavailable, ServingError, SloViolation, SpillFailed,
        StreamFailed, health,
    )
    from apex_tpu.serving.faults import SITE_CONTRACTS

    assert set(SITE_CONTRACTS) == set(SITES)
    for site, (err_name, sweep) in SITE_CONTRACTS.items():
        if err_name is None:
            continue  # policy-only fault (routing fallback)
        cls = getattr(health, err_name, None) or (
            InjectedFault if err_name == "InjectedFault" else None)
        assert cls is not None, f"{site}: unknown error {err_name}"
        assert issubclass(cls, (ServingError, InjectedFault))
        if sweep is not None:
            assert sweep.startswith("APEX_CHAOS_")

    # payload contract: ServingError subclasses ship diagnostics a
    # flight recorder can attach to
    base = ServingError("boom")
    assert base.payload == {}
    nf = NonFiniteLogits("nan logits in slot 3")
    assert isinstance(nf, ServingError) and nf.payload == {}
    ru = ReplicaUnavailable("decode down", replica="decode_1")
    assert ru.replica == "decode_1" and ru.payload["replica"] == "decode_1"
    sf = SpillFailed("dropped", key="ab12")
    assert sf.key == "ab12" and sf.payload["key"] == "ab12"
    pf = PromoteFailed("stale header", key="cd34", pages=2)
    assert pf.pages == 2 and pf.payload == {"key": "cd34", "pages": 2}
    sfl = StreamFailed("emit dropped", request_id=3, delivered=5,
                       dropped=2)
    assert sfl.payload == {"request_id": 3, "delivered": 5, "dropped": 2}
    qx = QuotaExhausted("over quota", tenant="small", need=6, quota=4,
                        charged=0)
    assert qx.tenant == "small" and qx.payload["need"] == 6
    sv = SloViolation("ttft blown", tenant="chat", metric="ttft",
                      observed=9, bound=4)
    assert sv.metric == "ttft" and sv.payload["bound"] == 4

    # InjectedFault is the injector's typed carrier, not a ServingError:
    # the scheduler's retry ladder catches it by ITS type
    inj = InjectedFault("prefill_exec", 4)
    assert inj.site == "prefill_exec" and inj.index == 4
    assert not isinstance(inj, ServingError)
