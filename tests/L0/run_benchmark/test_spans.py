"""The program's ``apex:sched/*`` spans and what the benchmark reads from
them (PR 24): nesting and the split of idle gaps on synthetic intervals, a
tiny scheduler under a real profiler session on the CPU, and the readers on
a small serving trace recorded on the chip (TPU v5 lite: the rehearsal sizes
of ``gpt2_medium.prompt_backlog`` through ``--rehearsal-on-chip --trace 1
--option trace_seconds=... --option keep_trace=...``, as the BERT one was)."""

import dataclasses
import glob
import os
import types

import numpy as np
import pytest

from benchmark import harness, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
RECORDED = os.path.join(HERE, "data", "small_gpt_serve.xplane.pb.gz")


# -- synthetic intervals -----------------------------------------------------

def _tick(t0, rid=None):
    """One tick's spans from ``t0``: (phase, start, end, stats) rows."""
    rows = [("step", t0, t0 + 10, {"tick": int(t0)}),
            ("expire", t0 + 0.1, t0 + 0.2, {}),
            ("admit", t0 + 1, t0 + 3, {}),
            ("build_inputs", t0 + 3, t0 + 4, {}),
            ("prepare_decode", t0 + 4, t0 + 4.2, {}),
            ("exec", t0 + 4.2, t0 + 5, {"kind": "decode"}),
            ("accept", t0 + 5, t0 + 8, {}),
            ("commit", t0 + 8, t0 + 9, {}),
            ("flush", t0 + 9, t0 + 9.5, {})]
    if rid is not None:
        rows.append(("prefill", t0 + 1.5, t0 + 2.5,
                     {"rid": rid, "bucket": 16, "prompt_tokens": 10,
                      "shared_pages": 1, "page_size": 4}))
    return rows


def test_nest_gives_each_span_the_span_it_lies_inside():
    rows = _tick(0.0, rid=7) + _tick(20.0) + [
        ("exec", 15.0, 16.0, {}),       # its step was cut off: left out
        ("accept", 16.0, 17.0, {})]
    got = spans.nest(rows)
    assert [s.phase for s in got if s.parent < 0] == ["step", "step"]
    assert len(got) == len(rows) - 2
    by = {(s.phase, s.start): s for s in got}
    prefill = by["prefill", 1.5]
    assert got[prefill.parent].phase == "admit"
    assert got[got[prefill.parent].parent].phase == "step"
    assert prefill.stats["rid"] == 7
    for s in got:
        if s.phase not in ("step", "prefill"):
            assert got[s.parent].phase == "step"
            assert got[s.parent].start <= s.start <= s.end \
                <= got[s.parent].end


def test_idle_gaps_split_by_exact_overlap_and_add_up():
    got = spans.nest(_tick(0.0, rid=1) + _tick(20.0))
    gaps = np.asarray([
        [1.2, 1.4],      # admit's own time
        [2.0, 2.2],      # inside prefill, which counts under admit
        [3.5, 4.1],      # straddles build_inputs (0.5) and dispatch (0.1)
        [4.5, 6.0],      # exec 0.5 (dispatch), accept 1.0
        [8.5, 9.25],     # commit 0.5, flush 0.25
        [9.6, 9.9],      # inside step under no child
        [12.0, 13.0],    # between the ticks, outside any step
        [24.9, 25.1]])   # second tick: exec 0.1, accept 0.1
    by = spans.split_idle(gaps, got)
    assert set(by) == set(spans.GROUPS)
    want = {"admit": 0.4, "build_inputs": 0.5, "dispatch": 0.7,
            "accept": 1.1, "commit_flush": 0.75, "unspanned": 1.3}
    for g, v in want.items():
        assert by[g] == pytest.approx(v), g
    assert sum(by.values()) == pytest.approx(
        float((gaps[:, 1] - gaps[:, 0]).sum()))
    # no span at all (the parent's trace): everything is unspanned
    none = spans.split_idle(gaps, [])
    assert none["unspanned"] == pytest.approx(sum(by.values()))
    assert sum(none.values()) == pytest.approx(sum(by.values()))


def _run(reduced, span_list, **counts):
    return {"trace": reduced, "apex_spans": span_list, "counts": counts}


def test_readers_return_none_without_step_spans():
    """What the parent gives: a trace with device work and no ``apex:``
    span. Every new reader leaves its metric out and none raises."""
    fake = types.SimpleNamespace(
        window=(0.0, 30.0), idle_gaps=lambda: np.asarray([[1.0, 2.0]]),
        kernel_time=lambda match: (0.0, 0))
    run = _run(fake, [], sizes={"layers": 24})
    bench = os.path.join(REPO, "benchmark")
    for m in harness.load_json(REPO, "BENCHMARK.json")["per_layer"][-8:]:
        assert harness.load_module("metrics", m["name"], bench).read(
            run) is None, m["name"]


def test_tick_idle_is_per_step_that_begins_in_the_window():
    fake = types.SimpleNamespace(
        window=(0.0, 25.0),     # the third step begins after it
        idle_gaps=lambda: np.asarray([[5.0, 7.0], [25.0, 26.0]]))
    got = spans.nest(_tick(0.0) + _tick(20.0) + _tick(40.0))
    by = spans.idle_by_phase(_run(fake, got))
    assert by["accept"] == pytest.approx(1e3 * (2.0 + 1.0) / 2)
    assert sum(by.values()) == pytest.approx(1e3 * 3.0 / 2)


def test_prefill_shared_pct_counts_pages_found_over_prompt_tokens():
    fake = types.SimpleNamespace(window=(0.0, 100.0))
    second = _tick(20.0, rid=2)
    second[-1][3].update(shared_pages=3)    # 12 > 10: a partial last page
    rows = _tick(0.0, rid=1) + second + _tick(40.0)
    read = harness.load_module(
        "metrics", "prefill_shared_pct",
        os.path.join(REPO, "benchmark")).read
    assert read(_run(fake, spans.nest(rows))) == pytest.approx(
        100.0 * (4 + 10) / 20)
    assert read(_run(fake, spans.nest(_tick(0.0)))) is None


@pytest.mark.parametrize("calls, want", [
    (48, 1e3 * 0.012 / 2), (0, None), (47, None)])
def test_flash_kernel_ms_per_prefill_wants_whole_executions(calls, want):
    names = {"%apex_flash_fwd.3 = bf16[1,16,512,64]{3,2,1,0} custom-call("
             "bf16[1,16,512,64] %a)": (0.012, calls),
             "%apex_flash_fwd_other = f32[8] custom-call(f32[8] %b)": (9, 9),
             "%apex_ln_fwd.1 = f32[8] custom-call(f32[8] %c)": (5.0, 48)}

    def kernel_time(match):
        hit = [v for n, v in names.items() if match(n)]
        return sum(s for s, _ in hit), sum(c for _, c in hit)

    read = harness.load_module(
        "metrics", "flash_kernel_ms_per_prefill",
        os.path.join(REPO, "benchmark")).read
    got = read(_run(types.SimpleNamespace(kernel_time=kernel_time), [],
                    sizes={"layers": 24}))
    assert got == (pytest.approx(want) if want else None)


# -- a tiny scheduler under a profiler session -------------------------------

@pytest.fixture(scope="module")
def model():
    import jax

    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True, hidden_dropout=0.0)
    return cfg, init_gpt(jax.random.PRNGKey(0), cfg)


def _serve(model, tracer):
    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, Request)
    cfg, params = model
    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=32,
                            num_pages=20, page_size=4, buckets=(16, 32),
                            tracer=tracer)
    sched = ContinuousBatchingScheduler(eng, eos_id=-1, streams=True)
    for s in range(3):      # the third prompt finds the first's pages
        sched.submit(Request(prompt=(7, 11, 13, 17, 19 + (s == 1), 23),
                             max_new_tokens=4, temperature=0.7, seed=s))
    sched.run()
    return sched


@pytest.mark.parametrize("enabled", [False, True],
                         ids=["tracer_off", "tracer_on"])
def test_scheduler_spans_reach_the_profiler(model, enabled, tmp_path):
    """With the ``Tracer`` disabled (an engine built without one) and
    enabled: the same ``apex:sched/*`` spans, nested, with their stats, in
    the profiler's own trace; a disabled tracer records no event; an
    enabled one's tick stream is the same with and without a session."""
    from apex_tpu.serving import Tracer
    from apex_tpu.utils import profiler

    _serve(model, None)         # compile outside the session
    tracer = Tracer() if enabled else None
    with profiler.trace(str(tmp_path), python_tracer=False):
        sched = _serve(model, tracer)
    if enabled:
        again = Tracer()
        _serve(model, again)
        assert tracer.tick_stream() == again.tick_stream()
        assert {"step", "admit", "prefill", "build_inputs", "exec",
                "flush"} <= {e.name for e in tracer.events}
    else:
        assert sched.tracer.enabled is False
        assert sched.tracer.events == [] and len(sched.tracer.recorder) == 0

    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    got = spans.load(path)
    steps = [i for i, s in enumerate(got) if s.phase == "step"]
    assert len(steps) >= 4
    assert all(got[i].parent < 0 and "tick" in got[i].stats
               and "decoding" in got[i].stats and "queued" in got[i].stats
               for i in steps)
    ticks = [got[i].stats["tick"] for i in steps]
    assert ticks == sorted(set(ticks))
    inside = {}
    for s in got:
        if s.parent >= 0:
            inside.setdefault(got[s.parent].phase, set()).add(s.phase)
    assert {"expire", "admit", "prepare_decode", "build_inputs", "exec",
            "accept", "commit", "flush"} <= inside["step"]
    assert inside["admit"] == {"prefill"}
    prefills = [s for s in got if s.phase == "prefill"]
    assert [s.stats["rid"] for s in prefills] == [0, 1, 2]
    for s in prefills:
        assert s.stats["bucket"] == 16 and s.stats["prompt_tokens"] == 6
        assert s.stats["page_size"] == 4 and s.stats["slot"] in (0, 1)
    # the first four tokens of every prompt are one page, shared by the
    # second; the third repeats the first prompt whole
    assert [s.stats["shared_pages"] for s in prefills] == [0, 1, 2]
    assert {s.stats["kind"] for s in got if s.phase == "exec"} == {"decode"}


# -- the small serving trace recorded on the chip ----------------------------
# Recorded with the Python tracer off and the rehearsal's trace starting at
# t = 0 (a scratch wrapper around run.py: at the rehearsal's own 0.5 s the
# tiny backlog has nearly drained): 17 ticks, 13 admissions, 0.4 s.

@pytest.fixture(scope="module")
def recorded():
    return {"trace": trace.reduce_file(RECORDED),
            "apex_spans": spans.load(RECORDED),
            "counts": {"sizes": {"layers": 2}}}


def _read(name, run):
    return harness.load_module(
        "metrics", name, os.path.join(REPO, "benchmark")).read(run)


def test_recorded_serving_trace_is_small_and_holds_the_tick(recorded):
    assert os.path.getsize(RECORDED) < 1 << 20
    got = recorded["apex_spans"]
    steps = [s for s in got if s.phase == "step"]
    assert len(steps) == 17 == len(spans.in_window(recorded, "step"))
    assert recorded["trace"].executions("jit_decode") == 17
    for s in steps:     # the ticks lie one after another on one thread
        assert s.parent < 0
    assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
    prefills = spans.in_window(recorded, "prefill")
    assert len(prefills) == recorded["trace"].executions("jit_prefill") == 13
    assert all(got[p.parent].phase == "admit" for p in prefills)
    assert {p.stats["bucket"] for p in prefills} == {64}
    assert {p.stats["page_size"] for p in prefills} == {4}


def test_tick_idle_adds_up_to_the_idle_gaps_of_the_recorded_trace(recorded):
    gaps = recorded["trace"].idle_gaps()
    per_tick = 1e3 * float((gaps[:, 1] - gaps[:, 0]).sum()) / 17
    six = {g: _read(f"tick_idle_ms.{g}", recorded) for g in spans.GROUPS}
    assert sum(six.values()) == pytest.approx(per_tick, rel=1e-9)
    assert all(v > 0 for v in six.values())
    # the tiny model idles the chip nearly all the time, and every phase
    # is spanned: what no span covers is under one hundredth of it
    assert six["unspanned"] < 0.01 * per_tick
    assert six["build_inputs"] == pytest.approx(7.94, abs=0.01)
    assert six["admit"] == pytest.approx(7.00, abs=0.01)
    # a little under the idle share of the window, which also counts the
    # device's own gaps of under 20 us between operations
    reduced = recorded["trace"]
    whole = 1e3 * (reduced.window_s - reduced.busy_s) / 17
    assert per_tick <= whole <= 1.01 * per_tick


def test_prefill_shared_pct_of_the_recorded_trace(recorded):
    # two of the thirteen prompts found 2 pages of 4 tokens: 16 of 185
    assert _read("prefill_shared_pct", recorded) == pytest.approx(
        100.0 * 16 / 185)


def test_recorded_kernels_carry_their_names(recorded):
    """Every Mosaic kernel of the serving programs is named in the trace
    after its scope; the tiny buckets take no flash kernel, so that reader
    reports nothing."""
    calls = recorded["trace"].custom_calls()
    timed = [n for n, (k, s) in calls.items() if s > 50e-9 * k]
    assert timed and all(n.startswith("%apex_ln_fwd.") for n in timed)
    assert _read("flash_kernel_ms_per_prefill", recorded) is None
