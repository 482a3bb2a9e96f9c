"""Chaos tier for the disaggregated serving tier (``serving.router``
+ ``serving.transfer``): two-replica prefill/decode split with
fault-tolerant page handoff, replica health, and failover.

The load-bearing contracts:

- FAULT-FREE IDENTITY — disaggregated committed streams are
  integer-identical to the colocated scheduler's (greedy and sampled,
  speculation on and off): the remote prefill runs the same jitted
  program and its pages ship verbatim, so there is nothing for the
  split to change;
- every injected transfer/replica fault yields a TYPED outcome and a
  recovered stream BIT-IDENTICAL to golden — retries, quarantines,
  colocated fallback and mid-stream failover are all invisible in the
  token streams (failover resumes via the preemption path: re-prefill
  from prompt + generated, keys fold token counts);
- corrupt payloads are quarantined at the checksum, never installed,
  never attended;
- the randomized multi-fault sweep replays bit-for-bit (outcomes,
  stats, injector counts, tick-clock event stream) under ``audit=True``.

``APEX_CHAOS_TRANSFER_SEED`` (comma-separated ints) overrides the
sweep's seed set — the CI chaos matrix fans one seed per leg and
uploads each leg's Perfetto dump.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (
    ContinuousBatchingScheduler, DisaggregatedRouter, FaultInjector,
    PagedDecodeEngine, PageTransfer, Request, Tracer, TransferCorrupt,
    TransferFailed, FINISH_REASONS, transfer_checksum,
)
from apex_tpu.serving.cache import audit_block_tables
from apex_tpu.serving.paging import prefix_page_keys

pytestmark = pytest.mark.chaos

EOS = -1       # unreachable: healthy streams run to max_new_tokens
MAX_LEN = 32

#: The randomized sweep's seeds; the CI chaos matrix overrides this to
#: one seed per leg.
_TRANSFER_SEEDS = tuple(
    int(s) for s in os.environ.get("APEX_CHAOS_TRANSFER_SEED",
                                   "0,1,2").split(","))


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True,
                              hidden_dropout=0.0)
    return cfg, init_gpt(jax.random.PRNGKey(0), cfg)


def _engine(model, injector=None, tracer=None, num_pages=20, **kw):
    cfg, params = model
    kw.setdefault("tracer", tracer if tracer is not None else Tracer())
    return PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=4,
                             buckets=(16, 32), injector=injector, **kw)


def _router(model, schedule=None, rates=None, seed=0, num_pages=20,
            spec_k=0, **kw):
    inj = FaultInjector(seed=seed, rates=rates, schedule=schedule)
    trc = Tracer()
    return DisaggregatedRouter(
        _engine(model, inj, trc, num_pages=num_pages, spec_k=spec_k),
        _engine(model, inj, trc, num_pages=num_pages, spec_k=spec_k),
        EOS, audit=True, **kw)


_REQS = [Request(prompt=(1, 2, 3, 4, 5), max_new_tokens=8),
         Request(prompt=(6, 7, 8), max_new_tokens=6, temperature=0.8,
                 seed=7),
         Request(prompt=(9, 10, 11, 12), max_new_tokens=4,
                 temperature=1.1, seed=5)]


def _drive(sched, reqs=_REQS):
    for r in reqs:
        sched.submit(r)
    return sched.run()


def _golden(model, reqs=_REQS, spec_k=0):
    eng = _engine(model, spec_k=spec_k)
    return _drive(ContinuousBatchingScheduler(eng, eos_id=EOS,
                                              audit=True), reqs)


def _assert_all_ok_golden(router, golden):
    """Every request finished ok with its exact golden stream — the
    recovery paths are invisible in the committed tokens."""
    assert sorted(router.outcomes) == list(range(len(golden)))
    for rid, out in router.outcomes.items():
        assert out.reason in FINISH_REASONS and out.ok
        assert list(out.tokens) == golden[rid], f"request {rid} diverged"


# -- fault-free identity -----------------------------------------------------

@pytest.mark.parametrize("spec_k", [0, 2])
def test_fault_free_streams_match_colocated(model, spec_k):
    """The headline contract: greedy AND sampled streams, speculation
    on and off, all integer-identical to the colocated scheduler —
    with every admission actually served by the remote prefill
    replica."""
    golden = _golden(model, spec_k=spec_k)
    router = _router(model, spec_k=spec_k)
    assert _drive(router) == golden
    assert router.stats.remote_prefills == len(_REQS)
    assert router.stats.colocated_prefills == 0
    assert router.stats.failovers == 0
    assert all(h.state == "healthy" for h in router.health.values())
    _assert_all_ok_golden(router, golden)


def test_remote_install_survives_the_next_upload(model):
    """The decode replica's block table is its engine's to write
    (``PagedDecodeEngine.install_slot``): the router's install goes into
    the host table, so the upload in front of the next decode step
    carries it. No audit here: its own upload would hide a row that the
    decode launch had lost. Prompts of 5 clone their partial page on the
    first append, so the first tick's launch does upload."""
    golden = _golden(model)
    router = _router(model)
    router.audit = False
    for r in _REQS:
        router.submit(r)
    router.step()           # admit two by remote prefill, decode once
    act = router.engine.active
    assert router.stats.remote_prefills == 2
    assert act.stats.block_table_uploads == 1 and not act._table_dirty
    assert all(act._slot_pages)
    audit_block_tables(act.cache.block_tables, act._slot_pages)
    while router.busy:
        router.step()
    assert [list(router.outcomes[i].tokens) for i in range(len(_REQS))] \
        == golden
    assert router.stats.remote_prefills == len(_REQS)
    router.engine.check_invariants()


def test_cross_replica_prefix_dedup(model):
    """Requests 0 and 1 share a full prompt page: the decode replica
    already holds it (registered at request 0's install), so request
    1's handoff ships one page fewer — content addressing IS the
    dedup, and the shared-page stream still matches golden."""
    reqs = [Request(prompt=(1, 2, 3, 4, 5), max_new_tokens=6),
            Request(prompt=(1, 2, 3, 4, 9), max_new_tokens=6,
                    temperature=0.8, seed=7)]
    golden = _golden(model, reqs)
    router = _router(model)
    assert _drive(router, reqs) == golden
    assert router.stats.transfer_pages_deduped == 1
    assert router.stats.remote_prefills == 2


# -- one pinned fault per new site ------------------------------------------

def test_single_send_fault_retries_to_golden(model):
    """One dropped send: retried inside the same handoff, delivered on
    attempt 2, stream bit-identical."""
    golden = _golden(model)
    router = _router(model, schedule={"page_send": (0,)})
    assert _drive(router) == golden
    assert router.stats.transfer_retries == 1
    assert router.stats.transfer_failures == 0
    assert router.stats.remote_prefills == len(_REQS)
    _assert_all_ok_golden(router, golden)


def test_single_recv_corruption_quarantines_to_golden(model):
    """One in-flight byte flip: the checksum catches it, the payload
    is quarantined (never installed — golden equality is the proof
    that no corrupt page was ever attended), and the retry
    re-extracts clean tiles."""
    golden = _golden(model)
    router = _router(model, schedule={"page_recv": (0,)})
    assert _drive(router) == golden
    assert router.stats.transfer_corrupt == 1
    assert router.stats.transfer_retries == 1
    assert router.stats.transfer_failures == 0
    _assert_all_ok_golden(router, golden)


def test_single_health_probe_fault_recovers(model):
    """One failed probe degrades the replica (still routable); clean
    probes walk it back to healthy. No routing change, no stream
    change."""
    golden = _golden(model)
    router = _router(model, schedule={"replica_health": (0,)})
    assert _drive(router) == golden
    assert router.stats.remote_prefills == len(_REQS)
    assert router.stats.colocated_prefills == 0
    assert router.health["prefill"].state == "healthy"
    assert router.health["prefill"].transitions >= 2  # dip + recovery
    _assert_all_ok_golden(router, golden)


# -- degradation ladder ------------------------------------------------------

def test_transfer_budget_exhausted_falls_back_colocated(model):
    """Every attempt of the first handoff dropped: TransferFailed is
    raised, caught, and the admission is served colocated — the
    request never observes the fault and its stream is golden."""
    golden = _golden(model)
    router = _router(model, schedule={"page_send": (0, 1, 2)})
    assert _drive(router) == golden
    assert router.stats.transfer_failures == 1
    assert router.stats.colocated_prefills >= 1
    names = [e.name for e in router.tracer.events]
    assert "failover" in names  # the fallback instant, typed cause
    _assert_all_ok_golden(router, golden)


def test_transfer_corrupt_exhaustion_is_typed(model):
    """Driving the channel directly: persistent corruption exhausts
    the budget with a TYPED TransferCorrupt carrying attempts/pages —
    and the tiles never reached any cache (quarantine, not install)."""
    inj = FaultInjector(schedule={"page_recv": (0, 1, 2)})
    src = _engine(model, inj)
    src.prefill(0, [1, 2, 3, 4, 5])
    transfer = PageTransfer(injector=inj, tracer=src.tracer,
                            stats=src.stats, max_retries=2)
    with pytest.raises(TransferCorrupt) as ei:
        transfer.ship(src, [1, 2, 3, 4, 5], src._slot_pages[0],
                      replica="prefill")
    assert ei.value.attempts == 3 and ei.value.pages == 2
    assert src.stats.transfer_corrupt == 3
    assert src.stats.transfer_failures == 1
    # a clean channel still ships the same pages fine afterwards
    k_tile, v_tile, attempts = transfer.ship(
        src, [1, 2, 3, 4, 5], src._slot_pages[0], replica="prefill")
    assert attempts == 1 and k_tile.shape[1] == 2


def test_checksum_binds_payload_to_prompt(model):
    """The chain key is folded into the transfer checksum: a payload
    can only verify against the prompt whose pages it carries — a
    key mismatch is indistinguishable from corruption and quarantines
    the same way."""
    k = np.zeros((2, 1, 2, 4, 4), np.float32)
    v = np.ones_like(k)
    key_a = prefix_page_keys([1, 2, 3, 4], 4)[-1]
    key_b = prefix_page_keys([1, 2, 3, 9], 4)[-1]
    assert transfer_checksum(k, v, key_a) != transfer_checksum(k, v,
                                                               key_b)
    flipped = np.array(k, copy=True)
    flipped.reshape(-1).view(np.uint8)[3] ^= 0xFF
    assert transfer_checksum(k, v, key_a) != \
        transfer_checksum(flipped, v, key_a)


def test_remote_replica_down_routes_colocated(model):
    """Persistent probe failures take the prefill replica down (even
    probe indices hit it — fixed draw order); admissions after that
    are served colocated, streams stay golden, and nothing hangs."""
    golden = _golden(model)
    router = _router(
        model, schedule={"replica_health": tuple(range(0, 40, 2))})
    assert _drive(router) == golden
    assert router.health["prefill"].state == "down"
    assert router.stats.colocated_prefills >= 1
    assert router.stats.failovers == 0
    _assert_all_ok_golden(router, golden)


def test_active_replica_down_mid_stream_fails_over(model):
    """The DECODE (active) replica dies mid-stream (odd probe
    indices, two consecutive failures): every occupied slot drains
    back to the queue front, the replicas swap roles, and the resumed
    streams are integer-identical to golden — the failover is pure
    placement."""
    golden = _golden(model)
    router = _router(model, schedule={"replica_health": (1, 3)})
    assert _drive(router) == golden
    assert router.stats.failovers == 1
    assert router.engine.active_name == "prefill"  # roles swapped
    names = [e.name for e in router.tracer.events]
    assert "failover" in names and "preempted" in names
    _assert_all_ok_golden(router, golden)


def test_both_replicas_down_keeps_serving(model):
    """Both ladders bottom out — the REMOTE first (probe indices are
    per-tick pairs: even = prefill, odd = decode; prefill fails from
    tick 2 on, decode at ticks 3-4), so when the active replica dies
    there is no routable target and failover is refused: health gates
    ROUTING, not survival, and the incumbent keeps decoding. Streams
    golden, outcomes typed, no hang — and the decode ladder later
    climbs back up through clean probes."""
    reqs = _REQS[:2]  # both admitted tick 1; no later handoff boosts
    golden = _golden(model, reqs)
    schedule = {"replica_health": tuple(range(2, 32, 2)) + (5, 7)}
    router = _router(model, schedule=schedule)
    assert _drive(router, reqs) == golden
    assert router.stats.failovers == 0
    assert router.health["prefill"].state == "down"
    # decode walked healthy -> degraded -> down, then back up the
    # ladder through clean probes (the drain ends mid-climb)
    assert router.health["decode"].state in ("degraded", "healthy")
    assert router.health["decode"].transitions >= 3
    _assert_all_ok_golden(router, golden)


# -- construction contracts --------------------------------------------------

def test_router_validates_replica_pair(model):
    cfg, params = model
    inj, trc = FaultInjector(), Tracer()

    def eng(**kw):
        return _engine(model, kw.pop("injector", inj),
                       kw.pop("tracer", trc), **kw)

    with pytest.raises(ValueError, match="two engine instances"):
        e = eng()
        DisaggregatedRouter(e, e, EOS)
    with pytest.raises(ValueError, match="agree on page_size"):
        cfg2, params2 = model
        other = PagedDecodeEngine(params2, cfg2, num_slots=2,
                                  max_len=MAX_LEN, num_pages=20,
                                  page_size=8, buckets=(16, 32),
                                  injector=inj, tracer=trc)
        DisaggregatedRouter(other, eng(), EOS)
    with pytest.raises(ValueError, match="ONE FaultInjector"):
        DisaggregatedRouter(eng(injector=FaultInjector()), eng(), EOS)
    with pytest.raises(ValueError, match="ONE Tracer"):
        DisaggregatedRouter(eng(tracer=Tracer()), eng(), EOS)
    with pytest.raises(ValueError, match="chunked prefill"):
        DisaggregatedRouter(eng(), eng(), EOS, chunk_tokens=4)


# -- randomized multi-fault sweep -------------------------------------------

@pytest.mark.parametrize("seed", _TRANSFER_SEEDS)
def test_multi_fault_chaos_replays_bit_for_bit(model, seed):
    """All three new sites armed at once (plus a legacy decode fault
    for cross-talk), audited every tick: every outcome typed, every
    ok stream exactly golden, every degraded stream a golden prefix
    — and the whole run replays bit-for-bit: outcomes, stats,
    injector counts, and the tick-clock event stream."""
    golden = _golden(model)
    rates = {"page_send": 0.25, "page_recv": 0.2,
             "replica_health": 0.12, "decode_exec": 0.05}

    def chaos_run():
        router = _router(model, rates=rates, seed=seed)
        _drive(router)
        return router

    router = chaos_run()
    assert sorted(router.outcomes) == list(range(len(_REQS)))
    for rid, out in router.outcomes.items():
        assert out.reason in FINISH_REASONS
        want = golden[rid]
        if out.ok:
            assert list(out.tokens) == want, f"request {rid} diverged"
        else:
            assert list(out.tokens) == want[:len(out.tokens)], \
                f"request {rid}: degraded stream not a golden prefix"
    replay = chaos_run()
    assert replay.outcomes == router.outcomes
    assert replay.stats.as_dict() == router.stats.as_dict()
    assert replay.engine.injector.counts == router.engine.injector.counts
    assert replay.tracer.tick_stream() == router.tracer.tick_stream()
    assert {h.state for h in replay.health.values()} \
        == {h.state for h in router.health.values()}
    # CI post-mortem artifact: one Perfetto dump per sweep seed,
    # uploaded by the chaos workflow legs
    out_path = os.environ.get("APEX_CHAOS_TRACE_OUT")
    if out_path:
        root, ext = os.path.splitext(out_path)
        router.tracer.dump_jsonl(
            f"{root}.transfer_seed{seed}{ext or '.jsonl'}")
