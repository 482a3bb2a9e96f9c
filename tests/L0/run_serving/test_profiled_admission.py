"""What PR 26 died on, guarded where it costs no chip time: the admit ->
prefill path under a LIVE ``jax.profiler`` session, started as
``benchmark/run.py`` starts it (Python tracer on), while the scheduler admits
beside decode, shares a prefix, copies a page and prefills; then the readers
that only ``gpt2_medium.prompt_backlog`` runs in that state
(``prefill_shared_pct``, which indexes ``prompt_tokens`` on every ``prefill``
span, and the six ``tick_idle_ms.*``) over that very trace. An exception that
only a session reaches, or a span stat renamed, fails here. The same drive
over a model with recurrent layers, whose spans carry two stats more."""

import dataclasses
import glob
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import (ContinuousBatchingScheduler, PagedDecodeEngine,
                              Request)
from benchmark import harness, spans

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
GROUPS = ("admit", "build_inputs", "dispatch", "accept", "commit_flush",
          "unspanned")


def gpt_engine():
    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True, hidden_dropout=0.0)
    return PagedDecodeEngine(init_gpt(jax.random.PRNGKey(0), cfg), cfg,
                             num_slots=2, max_len=32, num_pages=24,
                             page_size=4, buckets=(16, 32))


def hybrid_engine():
    from apex_tpu.models import hybrid
    cfg = hybrid.hybrid_tiny()
    return PagedDecodeEngine(
        hybrid.init_hybrid(jax.random.PRNGKey(0), cfg), cfg, num_slots=2,
        max_len=32, num_pages=24, page_size=4, buckets=(16, 32),
        cache_dtype=jnp.float32, prefix_sharing=False)


def serve(make):
    """Five requests over two slots: three admissions happen beside decode;
    prompts 0 and 2 are the same six tokens (a page and a half: the second
    shares both, and the first to append to the half page copies it) and
    prompt 1 shares their first page."""
    eng = make()
    sched = ContinuousBatchingScheduler(eng, eos_id=-1, streams=True)
    base = (7, 11, 13, 17, 19, 23)
    for s, prompt in enumerate([base, base[:4] + (29, 31), base,
                                (3, 5, 9, 2, 8, 6, 4), base[:5]]):
        # unequal lengths: a slot frees while the other still decodes
        sched.submit(Request(prompt=prompt, max_new_tokens=3 + 2 * (s % 3),
                             temperature=(0.0, 0.7)[s % 2], seed=s))
    sched.run()
    assert all(o.error is None and len(o.tokens) == 3 + 2 * (rid % 3)
               for rid, o in sched.outcomes.items())
    return eng, sched


@pytest.mark.parametrize("make", [gpt_engine, hybrid_engine],
                         ids=["gpt", "hybrid"])
def test_readers_run_over_a_trace_taken_while_admitting(make, tmp_path):
    serve(make)                             # compile outside the session
    jax.profiler.start_trace(str(tmp_path))         # as benchmark/run.py
    try:
        eng, sched = serve(make)
    finally:
        jax.profiler.stop_trace()
    recurrent = eng.recurrent
    if not recurrent:       # the prefix cache and the page copy did run
        assert eng.pool.num_cached > 0 and eng.stats.cow_copies > 0

    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    got = spans.load(path)
    steps = [s for s in got if s.phase == "step"]
    prefills = [s for s in got if s.phase == "prefill"]
    assert len(prefills) == 5 and len(steps) >= 8
    # admissions beside decode: a step that prefilled while a slot decoded
    assert any(s.stats["decoding"] > 0 and any(
        p.start >= s.start and p.end <= s.end for p in prefills)
        for s in steps)
    want = {"rid", "slot", "bucket", "prompt_tokens", "shared_pages",
            "page_size"} | ({"state_bytes"} if recurrent else set())
    assert all(set(p.stats) == want for p in prefills)
    execs = [s for s in got if s.phase == "exec"]
    assert execs and all(set(e.stats) == (
        {"kind", "state_slots"} if recurrent else {"kind"}) for e in execs)

    # the CPU backend's trace has no device plane: the window is the spans'
    # and the device counts as idle throughout, so the six groups have to
    # add up to the window per tick
    lo, hi = min(s.start for s in got), max(s.end for s in got)
    run = {"trace": types.SimpleNamespace(
               window=(lo, hi),
               idle_gaps=lambda: np.asarray([[lo, hi]], float)),
           "apex_spans": got, "counts": {}}

    def read(name):
        return harness.load_module("metrics", name, BENCH).read(run)

    shared = read("prefill_shared_pct")
    if recurrent:
        assert shared == 0.0
    else:
        # 6 + 4 + 6 + 0 + 4 of the 6 + 6 + 6 + 7 + 5 prompt tokens but the
        # first request's own: pages it found were its predecessors'
        pages = [p.stats["shared_pages"] for p in prefills]
        assert pages[0] == 0 and pages[2] == 2 and pages[1] == 1
        assert 0.0 < shared < 100.0
    idle = {g: read("tick_idle_ms." + g) for g in GROUPS}
    assert all(v is not None and v >= 0.0 for v in idle.values())
    assert idle["admit"] > 0.0 and idle["dispatch"] > 0.0
    assert sum(idle.values()) == pytest.approx(
        1e3 * (hi - lo) / len(steps), rel=1e-6)
