"""The traffic generator: seeded, and the same amount of work for every
seed (a backlog: the same work in the same order); latencies of the open
loop count from when a request was due."""

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
    """... where the window takes every request (the open loop). A backlog
    outlasts its window, and gets them in the same order."""
    a = traffic.requests(mix(name), 1, 30.0, 50257, 1024)
    b = traffic.requests(mix(name), BIG, 30.0, 50257, 1024)
    sizes = lambda rs: sorted((len(r.prompt), r.max_new_tokens,
                               r.shared_prefix is not None) for r in rs)
    assert sizes(a) == sizes(b)
    same_order = [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert same_order == (mix(name)["arrivals"]["process"] == "backlog")
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due_s for r in rs]),
                                      9))
    assert gaps(a) == gaps(b)


BACKLOGS = sorted(
    f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark", "traffic"))
    if f.endswith(".json") and mix(f[:-5]).get("arrivals", {}).get(
        "process") == "backlog")


def test_the_backlog_mixes_are_the_three_serving_cells():
    assert BACKLOGS == ["long_prompt_decode", "offline_decode",
                        "prompt_backlog"]


@pytest.mark.parametrize("rehearsal", [False, True])
@pytest.mark.parametrize("name", BACKLOGS)
def test_a_backlog_offers_one_order_of_work_on_every_seed(name, rehearsal):
    """A window samples the HEAD of a backlog it cannot drain: request ``i``
    has the same prompt length, shared prefix, ``max_new_tokens`` and
    temperature on every seed, and other token contents."""
    from benchmark.harness import rehearsal_view

    m = rehearsal_view(mix(name)) if rehearsal else mix(name)
    a = traffic.requests(m, 11, 30.0, 50257, 4096)
    b = traffic.requests(m, BIG, 30.0, 50257, 4096)
    work = lambda rs: [(len(r.prompt), r.max_new_tokens, r.temperature,
                        r.shared_prefix) for r in rs]
    assert work(a) == work(b) and len(a) == m["arrivals"]["requests"]
    assert {r.prompt for r in a}.isdisjoint(r.prompt for r in b)
    assert len({r.seed for r in a} | {r.seed for r in b}) > len(a)
    temps = m["temperatures"]
    assert [r.temperature for r in a[:4]] == (temps * 4)[:4]
    # no sorted order: any head of the list is a sample of the whole mix
    head = [len(r.prompt) for r in a[:max(len(a) // 4, 4)]]
    assert min(head) < sum(len(r.prompt) for r in a) / len(a) < max(head)
    if m.get("shared_prefix"):
        every = m["shared_prefix"]["one_request_in"]
        shared = [r.shared_prefix is not None for r in a]
        assert abs(sum(shared) - len(a) / every) <= 1
        first = max(len(a) // 4, 2 * every)
        assert 0 < sum(shared[:first]) < first
        heads = {r.shared_prefix: r.prompt[:m["shared_prefix"]["tokens"]]
                 for r in a if r.shared_prefix is not None}
        assert all(r.prompt[:m["shared_prefix"]["tokens"]]
                   == heads[r.shared_prefix]
                   for r in a if r.shared_prefix is not None)


def test_the_two_gpt_backlogs_outlast_a_server_twice_as_fast():
    """What the server finishes in a window today (PERF.md, section 5: about
    30 requests a second of ``prompt_backlog``, about 95,000 tokens of
    ``offline_decode``), twice over."""
    assert mix("prompt_backlog")["arrivals"]["requests"] >= 2 * 30 * 30
    offline = traffic.requests(mix("offline_decode"), 1, 30.0, 50257, 1024)
    assert sum(r.max_new_tokens for r in offline) >= 2 * 95_000
    assert mix("prompt_backlog")["trace_start_s"] == 2.0
    assert mix("prompt_backlog")["trace_seconds"] == 4.0


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
             "step_walls": [(t0, 0.5)], "queue_depth": [1],
             "backlog_left": 0, "drained_at_s": None}
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
    assert (counts["backlog_left"], counts["drained_at_s"]) == (0, None)
