"""The traffic generator: seeded, and the same amount of work for every
seed; latencies of the open loop count from when a request was due."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BIG = 2 ** 31 + 12345          # more than 32 signed bits hold


def mix(name):
    return json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                       name + ".json")))


@pytest.mark.parametrize("name", ["offline_decode", "serve_prompts"])
def test_requests_are_deterministic_in_the_seed(name):
    a = traffic.requests(mix(name), BIG, 30.0, 50257, 1024)
    b = traffic.requests(mix(name), BIG, 30.0, 50257, 1024)
    c = traffic.requests(mix(name), BIG + 1, 30.0, 50257, 1024)
    assert a == b
    assert [x.prompt for x in a] != [x.prompt for x in c]


@pytest.mark.parametrize("name", ["offline_decode", "serve_prompts"])
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    a = traffic.requests(mix(name), 1, 30.0, 50257, 1024)
    b = traffic.requests(mix(name), BIG, 30.0, 50257, 1024)
    sizes = lambda rs: sorted((len(r.prompt), r.max_new_tokens,
                               r.shared_prefix is not None) for r in rs)
    assert sizes(a) == sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due_s for r in rs]),
                                      9))
    assert gaps(a) == gaps(b)


def test_offline_backlog_is_all_due_at_once_and_fits_the_cache():
    rs = traffic.requests(mix("offline_decode"), 3, 30.0, 50257, 1024)
    assert len(rs) == mix("offline_decode")["arrivals"]["requests"]
    assert all(r.due_s == 0.0 for r in rs)
    assert all(32 <= len(r.prompt) <= 256 for r in rs)
    assert all(256 <= r.max_new_tokens <= 768 for r in rs)
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in rs)
    assert {r.temperature for r in rs} == {0.0, 0.8}
    assert all(2 <= t < 50257 for r in rs for t in r.prompt)


def test_open_loop_fills_the_window_at_its_rate_and_shares_prefixes():
    m = mix("serve_prompts")
    rs = traffic.requests(m, 9, 30.0, 50257, 1024)
    assert len(rs) == round(m["arrivals"]["rate_per_s"] * 30.0)
    due = [r.due_s for r in rs]
    assert due == sorted(due) and 0.0 < due[0] and due[-1] < 30.0
    assert all(128 <= len(r.prompt) <= 960 for r in rs)
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in rs)
    shared = [r for r in rs if r.shared_prefix is not None]
    assert abs(len(shared) - len(rs) / 3) <= 1
    heads = collections.defaultdict(set)
    for r in shared:
        heads[r.shared_prefix].add(r.prompt[:256])
        assert len(r.prompt) > 256
    assert len(heads) == 4 and all(len(v) == 1 for v in heads.values())
    assert 300 <= np.median([len(r.prompt) for r in rs]) <= 480


def test_training_batches_are_seeded_and_every_row_differs():
    m = mix("pretrain_s128")
    a = traffic.Batches(m, BIG, 30522, 1)
    b = traffic.Batches(m, BIG, 30522, 1)
    ids, mask = a.next()
    assert ids.shape == mask.shape == (64, 128) and ids.dtype == np.int32
    assert np.array_equal(ids, b.batch(0)[0])
    assert not np.array_equal(ids, a.next()[0])
    assert not np.array_equal(ids, traffic.Batches(m, 5, 30522, 1).next()[0])
    assert len({row.tobytes() for row in ids}) == 64
    assert mask.all() and 0 <= ids.min() and ids.max() < 30522
    assert traffic.Batches(m, 5, 30522, 4).next()[0].shape == (256, 128)


def test_open_loop_latency_counts_from_the_due_time():
    """A request due at 1.0 s, submitted late at 1.4 s, first token at 2.0 s:
    its time to first token is 1.0 s, not 0.6."""
    from benchmark.harness import load_module

    serve = load_module("runners", "gpt_serve",
                        os.path.join(REPO, "benchmark"))
    arrivals = [traffic.Arrival(1.0, (5, 6, 7), 3, 0.0, 1, None),
                traffic.Arrival(2.0, (5, 6, 8), 3, 0.0, 2, None)]
    t0 = 100.0
    clock = {"t0": t0, "t1": t0 + 10.5, "end": t0 + 10.0,
             "rid_of": {0: 0, 1: 1}, "submitted": 2,
             "submitted_at": {0: t0 + 1.4, 1: t0 + 2.0},
             "step_walls": [(t0, 0.5)], "queue_depth": [1]}
    deliveries = {0: [(t0 + 2.0, 1), (t0 + 2.5, 1), (t0 + 3.5, 1)]}
    Out = collections.namedtuple("Out", "tokens error")
    sched = type("S", (), {"outcomes": {0: Out((1, 2, 3), None)}})
    ctx = type("C", (), {"seconds": 10.0, "t_start": 90.0})
    mix_ = {"arrivals": {"process": "poisson"}}
    values, counts, failed, finished = serve.measures(
        ctx, arrivals, clock, deliveries, sched, mix_)
    assert counts["gen_late_ms"] == pytest.approx([400.0, 0.0])
    # the second request never got a token: it counts as the window's length
    assert failed == 1 and finished == [0]
    assert counts["ttft_ms_p50"] == pytest.approx(1e3 * (1.0 + 10.0) / 2)
    assert values["itl_ms_p95"] == pytest.approx(
        1e3 * (0.5 + 0.95 * 0.5))
    assert values["serve_tokens_per_s"] == pytest.approx(3 / 10.0)
    assert values["setup_s"] == pytest.approx(10.0)
