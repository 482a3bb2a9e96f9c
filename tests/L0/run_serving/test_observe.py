"""Observability layer: tracer determinism, metric math, flight
recorder, and export formats.

The tracer/registry/recorder are host-side hooks with the same inert
contract as the fault injector, so the load-bearing claims are:

- the TICK-CLOCK event stream is replay-exact (two runs at the same
  seed — fault-free or under a pinned fault schedule — produce equal
  ``tick_stream()``\\ s), while wall-clock stamps are explicitly
  outside that contract;
- enabling tracing never perturbs the committed token streams;
- histogram bucket math agrees with a brute-force quantile to within
  one bucket width;
- a forced livelock ships the flight-recorder ring in its typed
  error payload;
- the Perfetto dump is valid JSON-per-line with ``ph``/``ts``/``name``.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (
    ContinuousBatchingScheduler, FaultInjector, LivelockError,
    PagedDecodeEngine, Request, ServingStats, Tracer,
)
from apex_tpu.serving.observe import (
    LIFECYCLE, PHASES, FlightRecorder, Histogram, MetricsRegistry,
)

EOS = -1
MAX_LEN = 32


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True,
                              hidden_dropout=0.0)
    return cfg, init_gpt(jax.random.PRNGKey(0), cfg)


def _engine(model, tracer=None, injector=None, spec_k=0, num_pages=20):
    cfg, params = model
    return PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                             num_pages=num_pages, page_size=4,
                             buckets=(16, 32), spec_k=spec_k,
                             injector=injector, tracer=tracer)


def _drive(engine, n_reqs=3, max_new=6):
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS, audit=True)
    for s in range(n_reqs):
        sched.submit(Request(prompt=(7, 11, 13 + s), max_new_tokens=max_new,
                             temperature=0.7, seed=s))
    return sched, sched.run()


# -- metric math -------------------------------------------------------------

def test_histogram_quantile_matches_bruteforce():
    """Bucket-interpolated quantiles vs numpy's exact ones on a seeded
    workload: the estimate must land within one bucket width."""
    bounds = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)
    h = Histogram("ttft", buckets=bounds)
    rng = np.random.RandomState(42)
    vals = np.concatenate([rng.randint(1, 30, size=400),
                           rng.randint(30, 100, size=40)])
    for v in vals:
        h.observe(float(v))
    assert h.count == len(vals)
    assert h.sum == pytest.approx(float(vals.sum()))
    edges = [float(vals.min()), *bounds, float(vals.max())]
    for q in (0.1, 0.5, 0.9, 0.95, 0.99):
        est = h.quantile(q)
        true = float(np.percentile(vals, q * 100))
        # tolerance: the width of the bucket containing the true value
        idx = int(np.searchsorted(bounds, true))
        width = edges[idx + 1] - edges[idx] if idx < len(bounds) \
            else edges[-1] - edges[-2]
        assert abs(est - true) <= max(width, 1.0), (q, est, true)


def test_histogram_bucket_counts_are_cumulative_le():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 3.0, 100.0):
        h.observe(v)
    # le-semantics: v == bound lands IN that bucket
    assert h.counts == [2, 2, 1, 1]
    assert h.quantile(0.0) is not None
    assert h.quantile(1.0) == 100.0  # tail interpolates toward the max


def test_registry_get_or_create_and_type_conflict():
    r = MetricsRegistry()
    c = r.counter("x", help="h")
    assert r.counter("x") is c
    assert r.gauge("g", labels={"slot": 0}) \
        is not r.gauge("g", labels={"slot": 1})
    with pytest.raises(TypeError):
        r.gauge("x")


def test_prometheus_text_format():
    r = MetricsRegistry()
    r.counter("serving_retries_total", help="fault-path requeues").inc(3)
    r.gauge("serving_queue_depth").set(2)
    h = r.histogram("serving_ttft_ticks", buckets=(1.0, 4.0))
    h.observe(1.0)
    h.observe(9.0)
    text = r.to_prometheus()
    assert "# HELP serving_retries_total fault-path requeues" in text
    assert "# TYPE serving_retries_total counter" in text
    assert "serving_retries_total 3" in text
    assert "# TYPE serving_ttft_ticks histogram" in text
    assert 'serving_ttft_ticks_bucket{le="1.0"} 1' in text
    assert 'serving_ttft_ticks_bucket{le="+Inf"} 2' in text
    assert "serving_ttft_ticks_sum 10.0" in text
    assert "serving_ttft_ticks_count 2" in text


def test_servingstats_is_a_registry_view():
    """The legacy counter block and the registry share storage — a
    write through either face is visible through the other, so the
    exports can never drift from ``as_dict``."""
    stats = ServingStats()
    stats.retries += 2
    assert stats.registry.counter("serving_retries_total").value == 2
    stats.registry.counter("serving_retries_total").inc(1)
    assert stats.retries == 3
    stats.tokens_drafted = 10
    stats.tokens_accepted = 4
    d = stats.as_dict()
    assert d["retries"] == 3
    assert d["acceptance_rate"] == pytest.approx(0.4)
    with pytest.raises(TypeError):
        ServingStats(not_a_counter=1)
    with pytest.raises(AttributeError):
        stats.not_a_counter = 1


def test_flight_recorder_ring_is_bounded():
    rec = FlightRecorder(capacity=8)
    trc = Tracer(recorder=rec)
    for i in range(50):
        trc.set_tick(i)
        trc.instant("submitted", request_id=i)
    assert len(rec) == 8
    assert [e.request_id for e in rec.events()] == list(range(42, 50))
    assert len(trc.events) == 50  # the full event log is separate


# -- scheduler integration ---------------------------------------------------

pytest_chaos = pytest.mark.chaos


@pytest_chaos
def test_tracing_never_perturbs_streams(model):
    """Same seeds, tracer on vs off (and spec on): identical committed
    token streams — the hooks are host-side only."""
    _, bare = _drive(_engine(model))
    _, traced = _drive(_engine(model, tracer=Tracer()))
    assert traced == bare
    _, spec_traced = _drive(_engine(model, tracer=Tracer(), spec_k=2))
    assert spec_traced == bare


@pytest_chaos
@pytest.mark.parametrize("spec_k", [0, 2])
def test_tick_stream_is_replay_exact_under_pinned_faults(model, spec_k):
    """Two chaos runs at the same seed produce byte-identical
    tick-clock event streams; wall-clock stamps differ but are
    excluded from ``tick_key`` by construction."""
    rates = {"cow_clone": 0.2, "decode_exec": 0.1, "sample": 0.1}

    def go():
        trc = Tracer()
        _drive(_engine(model, tracer=trc, spec_k=spec_k,
                       injector=FaultInjector(seed=5, rates=rates),
                       num_pages=12))
        return trc

    a, b = go(), go()
    assert a.tick_stream() == b.tick_stream()
    assert len(a.tick_stream()) > 0
    walls_a = [e.wall for e in a.events]
    walls_b = [e.wall for e in b.events]
    assert walls_a != walls_b  # wall clock really is outside the key


@pytest_chaos
def test_event_taxonomy_and_metrics_after_run(model):
    trc = Tracer()
    # repetitive greedy prompts: the n-gram drafter proposes only where
    # a suffix of prompt + generated recurs, which three distinct tokens
    # sampled at T=0.7 (_drive's mix) never gave it — no tick
    # speculated and no slot got an acceptance gauge
    sched = ContinuousBatchingScheduler(
        _engine(model, tracer=trc, spec_k=2), eos_id=EOS, audit=True)
    for p in ((7, 11, 7, 11, 7, 11, 7), (5, 3, 5, 3, 5), (7, 11, 7, 11)):
        sched.submit(Request(prompt=p, max_new_tokens=8))
    sched.run()
    assert sched.stats.spec_ticks > 0 and sched.stats.tokens_drafted > 0
    names = {e.name for e in trc.events}
    assert {"submitted", "admitted", "first_token", "finished"} <= names
    assert {"step", "admit", "prefill", "draft", "prepare_decode",
            "build_inputs", "exec", "accept", "commit"} <= names
    assert names <= set(PHASES) | set(LIFECYCLE)
    reg = trc.registry
    assert reg.get("serving_ttft_ticks").count == 3
    assert reg.get("serving_itl_ticks").count > 0
    assert reg.get("serving_committed_tokens_per_tick").count > 0
    assert reg.get("serving_queue_depth") is not None
    # per-stream acceptance gauges exist for the speculating slots
    assert reg.get("serving_stream_acceptance_rate",
                   labels={"slot": 0}) is not None
    # the stats view and the registry agree by construction
    assert sched.stats.registry is reg
    assert reg.counter("serving_spec_ticks_total").value \
        == sched.stats.spec_ticks


def test_prepare_decode_span_and_block_table_counters(model):
    """The ``prepare_decode`` event closes with what the call did, and the
    two counters of the host-owned block table say the same: pages mapped
    at a boundary, and whole-table uploads, at most one a tick."""
    trc = Tracer()
    sched = ContinuousBatchingScheduler(_engine(model, tracer=trc),
                                        eos_id=EOS)
    for s in range(3):      # one prompt three times: its partial page is
        sched.submit(Request(prompt=(7, 11, 13, 17, 19, 23),    # cloned
                             max_new_tokens=5, temperature=0.7, seed=s))
    sched.run()
    spans = [dict(e.args) for e in trc.events if e.name == "prepare_decode"]
    assert spans and all(set(a) == {"boundaries", "cow", "preempted"}
                         for a in spans)
    st = sched.stats
    assert sum(a["boundaries"] for a in spans) == st.page_boundaries > 0
    assert sum(a["cow"] for a in spans) == st.cow_copies > 0
    assert sum(a["preempted"] for a in spans) == st.preemptions == 0
    assert 0 < st.block_table_uploads <= st.plain_ticks
    reg = trc.registry
    assert reg.counter("serving_page_boundaries_total").value \
        == st.page_boundaries
    assert reg.counter("serving_block_table_uploads_total").value \
        == st.block_table_uploads
    text = reg.to_prometheus()
    assert "serving_page_boundaries_total" in text
    assert "serving_block_table_uploads_total" in text


@pytest_chaos
def test_pool_gauges_track_the_pool(model):
    trc = Tracer()
    sched, _ = _drive(_engine(model, tracer=trc))
    eng = sched.engine
    reg = trc.registry
    assert reg.get("serving_pages_free").value == eng.pool.num_free
    assert reg.get("serving_pages_cached").value == eng.pool.num_cached
    assert reg.get("serving_page_pool_occupancy").value \
        == pytest.approx(eng.pool.occupancy)
    assert 0.0 <= eng.pool.occupancy <= 1.0
    # dense/tier-less engines never create the host-tier gauges
    assert reg.get("serving_page_pool_host_pages") is None


def _drive_hierarchy(model, tracer):
    """Churn a hot prefix through a small pool so the host tier's
    spill AND promote paths both run under tracing."""
    from apex_tpu.serving import PrefixRegistry
    cfg, params = model
    tier = PrefixRegistry(1 << 20)
    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=MAX_LEN,
                            num_pages=10, page_size=4, buckets=(16, 32),
                            tracer=tracer, host_tier=tier)
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS, audit=True)
    hot = tuple(range(7, 15))
    for p in (hot, (101, 102, 103, 104, 105, 106, 107, 108),
              (201, 202, 203, 204, 205, 206, 207, 208),
              (301, 302, 303, 304, 305, 306, 307, 308), hot):
        sched.submit(Request(prompt=p, max_new_tokens=4))
    sched.run()
    return eng, tier, sched


def test_host_tier_gauges_track_both_tiers(model):
    """Host-tier engines grow the pool gauge family with per-tier
    breakdowns, and the values mirror ``PagePool.stats()`` exactly."""
    trc = Tracer()
    eng, tier, _ = _drive_hierarchy(model, trc)
    assert eng.stats.host_spills > 0 and eng.stats.host_promotes > 0
    reg, stats = trc.registry, eng.pool.stats()
    assert reg.get("serving_page_pool_hbm_used").value \
        == stats["hbm_used"]
    assert reg.get("serving_page_pool_host_pages").value \
        == stats["host_pages"] == tier.num_pages
    assert reg.get("serving_page_pool_host_bytes").value \
        == stats["host_bytes"] == tier.nbytes
    assert reg.get("serving_page_pool_host_hit_rate").value \
        == pytest.approx(stats["host_hit_rate"])
    assert stats["host_hit_rate"] > 0
    # the spill/promote lifecycle instants carry byte+tick payloads
    spills = [e for e in trc.events if e.name == "host_spill"]
    promotes = [e for e in trc.events if e.name == "host_promote"]
    assert spills and promotes
    assert all(dict(e.args).get("bytes", 0) > 0 for e in spills)
    assert any(dict(e.args).get("ticks", 0) >= 1 for e in promotes)


def test_host_tier_tick_stream_is_replay_exact(model):
    """The replay contract holds with the hierarchy live: two runs of
    the same pinned schedule produce byte-identical tick-clock event
    streams, spill/promote instants included."""
    a = Tracer()
    b = Tracer()
    _drive_hierarchy(model, a)
    _drive_hierarchy(model, b)
    assert a.tick_stream() == b.tick_stream()
    names = {e.name for e in a.events}
    assert {"host_spill", "host_promote"} <= names
    assert names <= set(PHASES) | set(LIFECYCLE)


@pytest_chaos
def test_request_outcome_carries_tick_latencies(model):
    sched, _ = _drive(_engine(model, tracer=Tracer()))
    for out in sched.outcomes.values():
        assert out.ttft_ticks is not None and out.ttft_ticks >= 1
        assert out.total_ticks >= out.ttft_ticks
    # and without a tracer the fields are still populated (they feed
    # the outcome record, not just the histograms)
    sched2, _ = _drive(_engine(model))
    assert all(o.ttft_ticks is not None
               for o in sched2.outcomes.values())


def _drive_chunked(engine, chunk_tokens=4, n_reqs=3, max_new=6):
    """_drive with chunked prefill on and prompts long enough that
    every admission really splits into several chunks."""
    sched = ContinuousBatchingScheduler(engine, eos_id=EOS, audit=True,
                                        chunk_tokens=chunk_tokens)
    for s in range(n_reqs):
        sched.submit(Request(
            prompt=(7, 11, 13 + s, 17, 19, 23, 29 + s, 31, 37, 41),
            max_new_tokens=max_new, temperature=0.7, seed=s))
    return sched, sched.run()


@pytest_chaos
def test_chunked_tick_stream_is_replay_exact_under_pinned_faults(model):
    """The replay contract holds with chunked prefill on and the
    chunk_prefill_exec site armed: byte-identical tick-clock event
    streams across two runs at the same seed."""
    rates = {"cow_clone": 0.2, "chunk_prefill_exec": 0.2,
             "decode_exec": 0.1, "sample": 0.1}

    def go():
        trc = Tracer()
        _drive_chunked(_engine(model, tracer=trc,
                               injector=FaultInjector(seed=5,
                                                      rates=rates),
                               num_pages=12))
        return trc

    a, b = go(), go()
    assert a.tick_stream() == b.tick_stream()
    assert any(e.name == "chunk_prefill" for e in a.events)
    walls_a = [e.wall for e in a.events]
    walls_b = [e.wall for e in b.events]
    assert walls_a != walls_b  # wall clock stays outside the key


@pytest_chaos
def test_chunked_taxonomy_counters_and_outcomes(model):
    """Chunked runs stay inside the event taxonomy (chunk_prefill is a
    named phase), the chunk counter is a registry view of the stats
    block, and outcomes report how many ticks their prefill spanned."""
    trc = Tracer()
    sched, chunked = _drive_chunked(_engine(model, tracer=trc))
    names = {e.name for e in trc.events}
    assert "chunk_prefill" in names
    assert names <= set(PHASES) | set(LIFECYCLE)
    # 3 requests x 10-token prompts in 4-token chunks: 3 chunks each
    assert sched.stats.prefill_chunks == 9
    assert trc.registry.counter("serving_prefill_chunks_total").value \
        == sched.stats.prefill_chunks
    for out in sched.outcomes.values():
        assert out.prefill_ticks >= 2   # the prefill really spanned ticks
        assert out.ttft_ticks is not None and out.ttft_ticks >= 1
    # and tracing never perturbed the chunked streams
    _, bare = _drive_chunked(_engine(model))
    assert chunked == bare


@pytest_chaos
def test_livelock_error_carries_flight_recorder_ring(model):
    """The watchdog's LivelockError payload must include the stuck
    request's last trace events — the black box of the failure."""
    from apex_tpu.serving.cache import RESERVED_PAGES

    cfg, params = model
    trc = Tracer()
    eng = PagedDecodeEngine(params, cfg, num_slots=1, max_len=MAX_LEN,
                            num_pages=2 + RESERVED_PAGES, page_size=4,
                            buckets=(16, 32), tracer=trc)
    eng.pool.needs_copy = lambda page: True   # the PR-8 bug, forced
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS,
                                        watchdog_limit=8)
    sched.submit(Request(prompt=(7, 11, 13, 17, 19), max_new_tokens=3))
    with pytest.raises(LivelockError) as exc:
        sched.run()
    payload = exc.value.payload
    assert payload["stuck"] == exc.value.stuck
    flight = payload["flight"]
    assert flight, "flight recorder ring missing from the payload"
    assert flight == trc.flight()
    # the stuck request's lifecycle is in the ring, and every entry is
    # a chrome event (JSON-safe: the payload must serialize)
    names = {e["name"] for e in flight}
    assert "preempted" in names or "prepare_decode" in names
    assert any(e["args"].get("request_id") == 0 for e in flight)
    json.dumps(flight)


def test_inert_tracer_contract(model):
    """An engine built without a tracer gets a disabled one: no events
    recorded, but the stats view still lives on a real registry (its
    begin/end open the profiler's spans and record nothing)."""
    sched, _ = _drive(_engine(model))
    trc = sched.engine.tracer
    assert trc.enabled is False
    assert trc.events == []
    assert len(trc.recorder) == 0
    assert sched.stats.registry is trc.registry
    assert trc.registry.counter("serving_plain_ticks_total").value \
        == sched.stats.plain_ticks > 0


@pytest_chaos
def test_perfetto_jsonl_dump_is_valid(model, tmp_path):
    trc = Tracer()
    _drive(_engine(model, tracer=trc, spec_k=2))
    path = tmp_path / "trace.jsonl"
    n = trc.dump_jsonl(str(path))
    lines = path.read_text().splitlines()
    assert n == len(lines) == len(trc.events) > 0
    phs = set()
    for line in lines:
        d = json.loads(line)          # valid JSON per line
        assert {"ph", "ts", "name"} <= set(d)
        assert d["ts"] == d["args"]["tick"] * 1000
        assert "wall_s" in d["args"]
        phs.add(d["ph"])
        if d["ph"] == "X":
            assert d["dur"] >= 1
    assert phs == {"X", "i"}  # spans and instants both present


@pytest_chaos
def test_disagg_transfer_metrics_and_replay(model):
    """The disaggregated tier's observability surface: one
    ``page_transfer`` span per handoff, per-replica LABELED transfer
    counters, the replica health gauge, and the
    ``serving_transfer_ticks`` histogram — all inside the event
    taxonomy, and the whole tick-clock event stream replay-exact under
    a pinned transfer fault."""
    from apex_tpu.serving import DisaggregatedRouter
    from apex_tpu.serving.health import HEALTH_STATES

    def go():
        inj = FaultInjector(schedule={"page_send": (0,)})
        trc = Tracer()
        router = DisaggregatedRouter(_engine(model, trc, inj),
                                     _engine(model, trc, inj),
                                     EOS, audit=True)
        for s in range(3):
            router.submit(Request(prompt=(7, 11, 13 + s),
                                  max_new_tokens=6, temperature=0.7,
                                  seed=s))
        router.run()
        return router, trc

    router, trc = go()
    names = {e.name for e in trc.events}
    assert "page_transfer" in names
    assert names <= set(PHASES) | set(LIFECYCLE)
    spans = [e for e in trc.events if e.name == "page_transfer"]
    assert len(spans) == router.stats.remote_prefills == 3
    # the pinned send drop retried inside the FIRST span, delivered on
    # attempt 2 — never a second span, never a failure
    assert router.stats.transfer_retries == 1
    assert router.stats.transfer_failures == 0
    reg = trc.registry
    labels = {"replica": "prefill"}
    assert reg.get("serving_transfer_src_bytes_total",
                   labels=labels).value > 0
    assert reg.get("serving_transfer_src_retries_total",
                   labels=labels).value == 1
    assert reg.get("serving_transfer_src_failures_total",
                   labels=labels).value == 0
    hist = reg.get("serving_transfer_ticks", labels=labels)
    assert hist.count == 3  # one charged tick cost per delivered handoff
    # both replicas publish their health-state gauge; the one flaky
    # probe recovered, so both sit at the top of the ladder
    for replica in ("prefill", "decode"):
        g = reg.get("serving_replica_health",
                    labels={"replica": replica})
        assert g.value == HEALTH_STATES.index("healthy")
    # the stats view over the shared registry stays coherent: the
    # engines and the router share ONE counter block
    assert router.stats.registry is reg
    assert reg.counter("serving_transfers_total").value \
        == router.stats.transfers == 3
    # replay-exactness: same seed, same schedule -> byte-equal
    # tick-clock event stream, transfer spans included
    _, trc2 = go()
    assert trc.tick_stream() == trc2.tick_stream()


def test_pool_metrics_and_replay(model):
    """The pool tier's observability surface: one ``reshard`` span per
    device-to-device handoff, the per-replica
    ``serving_pool_replica_load`` gauge and per-reason
    ``serving_pool_routing_total`` counters, per-replica labeled
    reshard counters, and the ``rebalance`` lifecycle instant on a
    failover placement move — all inside the event taxonomy, and the
    whole tick-clock event stream replay-exact under a pinned fault
    schedule (a reshard drop AND a mid-stream decode failover)."""
    from apex_tpu.serving import PoolRouter
    from apex_tpu.serving.health import HEALTH_STATES

    def go():
        # reshard_send 0 -> first handoff retries inside its span;
        # replica_health 2,6 -> decode0 (probe order prefill0,
        # prefill1, decode0, decode1) dies and the slots move to
        # decode1
        inj = FaultInjector(schedule={"reshard_send": (0,),
                                      "replica_health": (2, 6)})
        trc = Tracer()
        pool = PoolRouter(
            [_engine(model, trc, inj), _engine(model, trc, inj)],
            [_engine(model, trc, inj), _engine(model, trc, inj)],
            EOS, audit=True)
        for s in range(3):
            pool.submit(Request(prompt=(7, 11, 13 + s),
                                max_new_tokens=6, temperature=0.7,
                                seed=s))
        pool.run()
        return pool, trc

    pool, trc = go()
    names = {e.name for e in trc.events}
    assert "reshard" in names
    assert "rebalance" in names
    assert names <= set(PHASES) | set(LIFECYCLE)
    spans = [e for e in trc.events if e.name == "reshard"]
    assert len(spans) == pool.stats.reshards >= 3
    assert pool.stats.reshard_retries == 1
    assert pool.stats.reshard_failures == 0
    assert pool.stats.failovers == 1 and pool.stats.rebalances == 1
    reg = trc.registry
    # per-reason routing counters: every remote admission routed by
    # load (no pool_route fault pinned)
    assert reg.get("serving_pool_routing_total",
                   labels={"reason": "load"}).value \
        == pool.stats.remote_prefills
    # the load gauge exists per prefill replica and ends at the last
    # pass's link-busy value (deterministic)
    for replica in ("prefill0", "prefill1"):
        assert reg.get("serving_pool_replica_load",
                       labels={"replica": replica}) is not None
    # per-replica labeled reshard counters on the routed source
    total_bytes = sum(
        reg.get("serving_reshard_src_bytes_total",
                labels={"replica": r}).value
        for r in ("prefill0", "prefill1")
        if reg.get("serving_reshard_src_bytes_total",
                   labels={"replica": r}) is not None)
    assert total_bytes > 0
    # all four replicas publish the health gauge; decode0 took the
    # two pinned probe hits
    for replica in ("prefill0", "prefill1", "decode0", "decode1"):
        g = reg.get("serving_replica_health",
                    labels={"replica": replica})
        assert g is not None and g.value <= HEALTH_STATES.index("healthy")
    # replay-exactness under the pinned schedule: byte-equal tick
    # stream, reshard spans and the rebalance instant included
    pool2, trc2 = go()
    assert trc.tick_stream() == trc2.tick_stream()
    assert pool2.stats.as_dict() == pool.stats.as_dict()
