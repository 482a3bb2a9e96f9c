"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on the chip (TPU v5 lite, PR 23: the tiny rehearsal sizes of
``bert_large.pretrain_s128``, a few steps; ``benchmark/README.md`` says how)
through the reduction and the readers."""

import os

import numpy as np
import pytest

from benchmark import harness, trace

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
RECORDED = os.path.join(HERE, "data", "small_bert.xplane.pb.gz")


def test_union_merges_overlapping_and_nested_intervals():
    iv = np.asarray([[5, 6], [0, 2], [1, 3], [1.5, 1.7], [8, 9], [9, 10]],
                    float)
    assert trace._union(iv).tolist() == [[0, 3], [5, 6], [8, 10]]
    assert trace._measure(trace._union(iv)) == 6.0
    assert trace._union(np.zeros((0, 2))).shape == (0, 2)


def test_subtract_leaves_what_the_second_does_not_cover():
    a = np.asarray([[0, 10], [20, 30]], float)
    b = np.asarray([[-1, 1], [2, 3], [9, 21], [25, 26], [40, 50]], float)
    assert trace._subtract(a, b).tolist() == [
        [1, 2], [3, 9], [21, 25], [26, 30]]
    assert trace._subtract(a, np.zeros((0, 2))).tolist() == a.tolist()
    # collectives wholly hidden under compute expose nothing
    assert trace._measure(trace._subtract(
        np.asarray([[1, 2]], float), np.asarray([[0, 3]], float))) == 0.0


@pytest.mark.parametrize("text, want", [
    ("%copy.772 = f32[64,128,30522]{2,1,0:T(8,128)} copy(f32[64,128,30522]"
     "{1,2,0:T(8,128)} %convert_add_fusion)",
     "%copy.772 copy f32[64,128,30522]"),
    ("%jvp__.5 = (f32[1,8192]{1,0:T(1,128)}, f32[1,8192]{1,0:T(1,128)S(1)}) "
     "custom-call(f32[8192,30720]{1,0:T(8,128)} %pad.0)",
     "%jvp__.5 custom-call (f32[1,8192], f32[1,8192])"),
    ("jit_decode(123)", "jit_decode(123)"),
])
def test_short_names_keep_the_op_and_its_shape(text, want):
    assert trace.short(text) == want


def test_collectives_are_told_from_other_ops():
    assert trace._COLLECTIVE.search(
        "%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024] %x)")
    assert trace._COLLECTIVE.search("%ar = f32[8] all-reduce(f32[8] %g)")
    assert not trace._COLLECTIVE.search("%fusion.7 = bf16[8] fusion(%a)")


def host_only(spans):
    """A ``trace.Host`` that holds the benchmark's spans and nothing else."""
    host = trace.Host([])
    host.bench = list(spans)
    return host


@pytest.mark.parametrize("kind", ["requests", "batches"])
def test_a_trace_without_device_work_is_a_reading_only_for_a_server(kind):
    """A server whose backlog drained before the traced span idled all
    through it: idle 100%, every reader of device work finds nothing, and
    the gap carries the name of what the host did. A training run without a
    device operation is a broken run."""
    host = host_only([("idle_no_request", 10.0, 10.05),
                      ("idle_no_request", 10.05, 14.0)])
    if kind == "batches":
        with pytest.raises(ValueError, match="no operation ran on a device"):
            trace.Reduced([], host, may_be_empty=False)
        return
    reduced = trace.Reduced([], host, may_be_empty=True)
    assert reduced.idle_pct() == 100.0 and reduced.busy_s == 0.0
    assert reduced.window == (10.0, 14.0) and reduced.window_s == 4.0
    assert reduced.program_times("jit_decode") == []
    assert reduced.kernel_time(lambda n: True) == (0.0, 0)
    assert reduced.programs_summary() == {} == reduced.custom_calls()
    assert reduced.idle_gaps().tolist() == [[10.0, 14.0]]
    assert reduced.breakdown() == {
        "device_ops": [], "idle_gaps": [("idle_no_request", 4.0)]}
    bench = os.path.join(REPO, "benchmark")
    run = {"trace": reduced, "apex_spans": [], "peaks": {}, "cell": None,
           "counts": {"sizes": {"layers": 24}, "traced": (10.0, 14.0),
                      "first_delivery": {}, "prompt_tokens": [],
                      "buckets": [128], "mapped_positions": 0}}
    manifest = harness.load_json(REPO, "BENCHMARK.json")
    got = {m["name"]: harness.load_module("metrics", m["name"], bench).read(
        run) for m in manifest["per_layer"]
        if "gpt2_medium.prompt_backlog" in m["workloads"]
        and m["name"] != "sched_step_ms.serve"}
    assert got.pop("device_idle_pct.serve") == 100.0
    assert got and all(v is None for v in got.values()), got
    # not even a span of the benchmark's own: nothing was traced at all
    with pytest.raises(ValueError, match="no operation ran on a device"):
        trace.Reduced([], host_only([]), may_be_empty=True)


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_file(RECORDED)


def test_recorded_trace_is_small():
    assert os.path.getsize(RECORDED) < 1 << 20


def test_recorded_trace_reduces_to_busy_idle_and_programs(recorded):
    assert len(recorded.chips) == 1
    steps = recorded.program_times("jit_train_step")
    assert len(steps) >= 2 and all(t > 0 for t in steps)
    assert 0 < recorded.busy_s < recorded.window_s
    assert 0 < recorded.idle_pct() < 100
    # busy is the union of the operations' intervals: it cannot pass the
    # programs' own time by more than rounding
    assert recorded.busy_s <= sum(steps) * 1.001
    summary = recorded.programs_summary()
    assert summary["jit_train_step"]["calls"] == len(steps)
    # one chip, no collective
    assert recorded.collective_s() == 0.0
    assert recorded.collective_exposed_s() == 0.0


def test_recorded_trace_breakdown_names_ops_and_host_spans(recorded):
    b = recorded.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and 1 <= len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])
    assert [s for _, s in b["device_ops"]] == sorted(
        (s for _, s in b["device_ops"]), reverse=True)
    # the benchmark's own spans are in the trace, and the gaps carry them
    spans = {n for n, _, _ in recorded.host.bench}
    assert {"feed", "dispatch"} <= spans
    assert any(label.split("|")[0] in spans for label, _ in b["idle_gaps"])
    assert sum(s for _, s in b["idle_gaps"]) <= \
        recorded.window_s - recorded.busy_s + 1e-9


def test_readers_find_the_kernels_in_the_recorded_trace(recorded):
    """The tiny model has 2 layers: 6 LayerNorm calls forward and 6 backward
    a step, one cross-entropy each way, all Mosaic custom calls."""
    bench = os.path.join(REPO, "benchmark")
    cell = harness.Cell("bert_large.pretrain_s128")
    cfg = harness.rehearsal_view(cell.config)
    mix = harness.rehearsal_view(cell.traffic)
    sz = cell.reference().sizes_of(cfg)
    steps = len(recorded.program_times("jit_train_step"))
    run = {"trace": recorded, "cell": cell, "chips": 1,
           "peaks": cell.peaks("TPU v5 lite"),
           "counts": {"sizes": sz, "seq": mix["seq"],
                      "rows_per_chip": mix["rows_per_chip"],
                      "tokens_per_step_per_chip":
                          mix["rows_per_chip"] * mix["seq"]}}
    ln = harness.load_module("metrics", "ln_kernels_ms_per_step", bench)
    touches = harness.load_module("kernels", "xentropy", bench).touches_logits
    rows = mix["rows_per_chip"] * mix["seq"]
    _, ln_calls = recorded.mosaic_kernels(
        lambda n: not touches(n, rows, sz["vocab"]))
    assert ln_calls == 12 * steps
    _, xent_calls = recorded.mosaic_kernels(
        lambda n: touches(n, rows, sz["vocab"]))
    assert xent_calls == 2 * steps
    assert ln.read(run) > 0
    xent = harness.load_module("metrics", "xent_roofline_pct", bench)
    # at this tiny size the logits (1 MB) are staged on chip and the share
    # of the HBM roofline means nothing; at the cell's size (1 GB) it read
    # 84.9% (PERF.md). Here only: both kernels are found and timed.
    assert xent.read(run) > 0
    mfu = harness.load_module("metrics", "mfu_pct.train", bench)
    assert 0 < mfu.read(run) < 100
    idle = harness.load_module("metrics", "device_idle_pct.train", bench)
    assert idle.read(run) == recorded.idle_pct()
    # nothing to read for a one-chip trace: left out of the line
    ar = harness.load_module("metrics", "allreduce_exposed_ms", bench)
    assert ar.read(run) is None
