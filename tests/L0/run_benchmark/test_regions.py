"""The device regions and what the benchmark reads from them (PR 38): the
rule from an operation's path to its region on hand-written paths; the wire
reader against the recorded chip traces that were there (``small_bert`` has
the parent's two scopes, the two serving ones none) and against three
four recorded with the regions in them (the rehearsal sizes of
``bert_large.pretrain_s128``, ``gpt2_medium.prompt_backlog`` (at a row of
128 numbers and pages of 16, which its kernel wants on the chip),
``deepseek_v3.resident_context_decode`` (the experts) and
``olmo_hybrid_7b.long_prompt_decode`` (the mixer) on a TPU v5 lite, Python
tracer off, the traced span from t = 0, as the older ones were made; the
last two without their ``/host:metadata`` plane, the programs' HLO protos,
which no reader opens and which were two thirds of the files); the twenty
readers and their manifest entries; and what the manifest tests pinned
in ``tests/conftest.py`` (``PINNED_BY_PR_36``) check besides their pins."""

import gzip
import hashlib
import os
import types

import pytest

from benchmark import harness, regions, trace

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(HERE, "data")
OLD = ("small_bert", "small_gpt_serve", "small_hybrid_serve")
NEW = ("small_bert_regions", "small_gpt_serve_regions",
       "small_deepseek_serve_regions", "small_hybrid_serve_regions")

BERT = ["bert_large.pretrain_s128", "bert_large.pretrain_s128_dp4"]
GPT = ["gpt2_medium.offline_decode", "gpt2_medium.prompt_backlog"]
HYBRID = "olmo_hybrid_7b.long_prompt_decode"
NEMOTRON = "nemotron3_super_120b_a12b.many_slot_decode"
DEEPSEEK = "deepseek_v3.resident_context_decode"
SERVING = GPT + [HYBRID, NEMOTRON, DEEPSEEK]
PREFILL = [GPT[1], HYBRID, NEMOTRON]
#: the twenty entries, in the manifest's order: name -> (layer, cells)
ENTRIES = {
    **{f"train_step_ms.{g}": ("Training step", BERT) for g in (
        "embed", "attention", "mlp", "head_loss", "amp", "optimizer",
        "unscoped")},
    "train_step_ms.grad_sync": ("Parallel runtime", BERT[1:]),
    **{f"decode_ms.{g}": ("Serving device programs", SERVING) for g in (
        "attention", "mlp", "head", "unscoped")},
    "decode_ms.mixer": ("Serving device programs", [HYBRID, NEMOTRON]),
    "decode_ms.experts": ("Serving device programs", [NEMOTRON, DEEPSEEK]),
    **{f"prefill_ms_per_ktok.{g}": ("Serving device programs", PREFILL)
       for g in ("attention", "mlp", "head", "unscoped")},
    "prefill_ms_per_ktok.mixer": ("Serving device programs",
                                  [HYBRID, NEMOTRON]),
    "prefill_ms_per_ktok.experts": ("Serving device programs", [NEMOTRON]),
}


def path_of(name):
    return os.path.join(DATA, name + ".xplane.pb.gz")


def reader(name):
    return harness.load_module("metrics", name, BENCH).read


class _Tables(dict):
    """name -> the trace's table, read when first asked for."""

    def __missing__(self, name):
        self[name] = regions.load(path_of(name))
        return self[name]


@pytest.fixture(scope="module")
def tables():
    return _Tables()


# -- from a path to a region --------------------------------------------------

@pytest.mark.parametrize("tf_op,region", [
    ("jit(train_step)/jvp(layer0)/attention/bhqd,bhkd->bhqk/dot_general:",
     "attention"),
    ("jit(train_step)/transpose(jvp(layer0))/mlp/reduce_sum:", "mlp"),
    ("jit(train_step)/jvp(embed)/take:", "embed"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add:", "embed"),
    ("jit(train_step)/optimizer/FusedAdam.step/convert_element_type:",
     "optimizer"),
    ("jit(decode)/while/body/closed_call/attention/dot_general:",
     "attention"),
    # a kernel's own scope is no region: the region around its call counts
    ("jit(decode)/while/body/mixer/apex_ssd_decode_fwd/pallas_call:",
     "mixer"),
    ("jit(train_step)/jvp(loss)/apex_xentropy_fwd/pallas_call:", "loss"),
    # the first region on the path wins
    ("jit(decode)/attention/cache_write/scatter:", "attention"),
    ("jit(prefill)/cache_write/scatter:", "cache_write"),
    ("jit(train_step)/shard_map/grad_sync/psum:", "grad_sync"),
    # no region: a function's name is none, nor is a primitive's
    ("jit(train_step)/transpose(jvp())/pallas_call:", None),
    ("jit(mlp)/add:", None),
    ("jit(decode)/jit(attention)/mul:", None),
    ("jit(train_step)/convert_element_type:", None),
    ("jit(decode)/while/body/layer3/_take:", None),
    ("", None),
])
def test_region_of_a_path(tf_op, region):
    assert regions.region_of(tf_op) == region


def test_backward_is_the_transposed_half():
    assert regions.backward("jit(f)/transpose(jvp(layer0))/mlp/dot_general:")
    assert not regions.backward("jit(f)/jvp(layer0)/mlp/dot_general:")


def test_every_region_has_a_group_in_both_families():
    for family, groups in regions.GROUPS.items():
        assert set(regions.GROUP_OF[family]) == set(regions.REGIONS)
        assert set(regions.GROUP_OF[family].values()) | {"unscoped"} \
            == set(groups)
    assert regions.GROUP_OF["serve"]["cache_write"] == "attention"
    assert regions.GROUP_OF["serve"]["router"] == "experts"
    assert regions.GROUP_OF["serve"]["embed"] == "head"
    assert regions.GROUP_OF["train"]["loss"] == "head_loss"


# -- the wire reader against recorded traces ---------------------------------

@pytest.mark.parametrize("name", OLD + NEW)
def test_every_operation_is_found_in_the_metadata_and_times_add_up(
        name, tables):
    from jax.profiler import ProfileData

    with gzip.open(path_of(name), "rb") as f:
        raw = f.read()
    meta = regions.metadata(raw)
    data = ProfileData.from_serialized_xspace(raw)
    events = 0
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {n for _, n in meta[plane.name]}
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    events += 1
                    assert e.name in names, e.name
    assert events > 100
    assert any(tf_op for tf_op in next(iter(meta.values())).values())
    # what the table counts and what it leaves out is everything the chip
    # ran: the same sum ``trace.Chip.op_time`` holds
    table = tables[name]
    reduced = trace.reduce_file(path_of(name), may_be_empty=True)
    want = sum(sum(c.op_time.values()) for c in reduced.chips) \
        / len(reduced.chips)
    got = sum(op.seconds for op in table.ops.values()) + table.left_out_s
    assert got == pytest.approx(want, rel=1e-9)
    for program, runs in table.executions.items():
        assert runs == reduced.executions(program)
        ops = sum(op.seconds for (p, _), op in table.ops.items()
                  if p == program)
        assert ops <= table.module_s[program] * (1 + 1e-9)


def test_the_parents_bert_reads_two_regions_and_most_of_it_unscoped(tables):
    """``small_bert`` was recorded when BERT had ``attention`` and ``mlp``
    and nothing else (the parent's case, where the compile cache hands this
    PR the parent's executable too)."""
    table = tables["small_bert"]
    by = table.regions("jit_train_step")
    assert set(by) == {None, "attention", "mlp"}
    groups = table.groups("jit_train_step", "train")
    assert set(groups) == set(regions.GROUPS["train"])
    total = sum(groups.values())
    assert total == pytest.approx(sum(by.values()), rel=1e-12)
    assert groups["unscoped"] / total > 0.5
    assert groups["attention"] > 0 and groups["mlp"] > 0
    assert groups["optimizer"] == groups["embed"] == groups["amp"] == 0.0
    fwd_bwd = table.directions("jit_train_step")
    assert fwd_bwd[("mlp", "bwd")] > fwd_bwd[("mlp", "fwd")] > 0


@pytest.mark.parametrize("name", ["small_gpt_serve", "small_hybrid_serve"])
def test_a_program_without_a_region_reads_none_and_says_why(name, tables,
                                                            capsys):
    table = regions.load(path_of(name))
    for program in ("jit_decode", "jit_prefill"):
        assert table.executions[program] > 0
        assert table.regions(program) is None
        assert table.groups(program, "serve") is None
    assert table.regions("jit_train_step") is None
    err = capsys.readouterr().err
    assert "jit_decode carries no region scope" in err
    assert "jit_train_step did not run in the traced span" in err
    # said once a program, not once a reader
    table.groups("jit_decode", "serve")
    assert capsys.readouterr().err == ""
    run = {"apex_regions": table, "counts": {
        "traced": (0.0, 1.0), "first_delivery": {0: 0.5},
        "prompt_tokens": [10], "buckets": [16]}}
    for metric in ENTRIES:
        assert reader(metric)(run) is None, metric


PROGRAMS = {
    "small_bert_regions": {
        "jit_train_step": ("train", {"embed", "attention", "mlp", "head",
                                     "loss", "amp", "optimizer"})},
    "small_gpt_serve_regions": {
        "jit_decode": ("serve", {"embed", "attention", "mlp", "head",
                                 "cache_write"}),
        "jit_prefill": ("serve", {"embed", "attention", "mlp", "head",
                                  "cache_write"})},
    "small_deepseek_serve_regions": {
        "jit_decode": ("serve", {"embed", "attention", "mlp", "router",
                                 "experts", "head", "cache_write"}),
        "jit_prefill": ("serve", {"embed", "attention", "mlp", "router",
                                  "experts", "head", "cache_write"})},
    "small_hybrid_serve_regions": {
        "jit_decode": ("serve", {"embed", "attention", "mixer", "mlp",
                                 "head", "cache_write"}),
        "jit_prefill": ("serve", {"embed", "attention", "mixer", "mlp",
                                  "head", "cache_write"})},
}


@pytest.mark.parametrize("name,program", [
    (n, p) for n in NEW for p in PROGRAMS[n]])
def test_a_family_adds_up_and_what_a_scope_can_reach_is_scoped(name, program,
                                                              tables):
    table = tables[name]
    family, want = PROGRAMS[name][program]
    by = table.regions(program)
    assert set(by) - {None} == want
    groups = table.groups(program, family)
    ops = sum(op.seconds for (p, _), op in table.ops.items() if p == program)
    assert sum(groups.values()) == pytest.approx(ops, rel=1e-12)
    assert sum(by.values()) == pytest.approx(ops, rel=1e-12)
    assert all(v >= 0 for v in groups.values())
    # what a scope CAN reach is under one. The rest of ``unscoped`` either
    # carries no path at all (what the compiler adds, asynchronous copies
    # above all: at these tiny sizes, where every operand is staged on chip,
    # a quarter of a program) or lies on a path that holds NO scope of the program's,
    # only ``jit(..)`` and a scan's own ``while/body`` in front of the
    # primitive: the scan slicing its stacked operands and stacking its
    # results, outside its body, where no scope can be opened
    bare = [(key, op) for key, op in table.ops.items()
            if key[0] == program and op.region is None]
    for key, op in bare:
        if op.tf_op:
            parts = op.tf_op.split("/")[:-1]
            assert all(p in ("while", "body", "cond", "closed_call")
                       or p.startswith("jit(") for p in parts), op.tf_op
        else:       # the compiler's own: a copy, a buffer, never a kernel
            assert not key[1].startswith("%apex_"), key[1]
    assert sum(op.seconds for _, op in bare if op.tf_op) < 0.2 * ops
    # a kernel's time counts under the region around its call
    kernels = [(key, op) for key, op in table.ops.items()
               if key[0] == program and "/apex_" in op.tf_op]
    assert kernels and all(op.region is not None for _, op in kernels)


# -- the readers -------------------------------------------------------------

def _fake_table():
    op = regions.Op
    ops = {
        ("jit_train_step", "a"): op(0.040, 4, "jit(f)/jvp(embed)/take:",
                                    "embed"),
        ("jit_train_step", "b"): op(0.200, 4, "jit(f)/head/dot:", "head"),
        ("jit_train_step", "c"): op(0.100, 4, "jit(f)/jvp(loss)/x:", "loss"),
        ("jit_train_step", "d"): op(0.020, 4, "", None),
        ("jit_decode", "a"): op(0.030, 10, "jit(d)/attention/x:",
                                "attention"),
        ("jit_decode", "b"): op(0.010, 10, "jit(d)/cache_write/x:",
                                "cache_write"),
        ("jit_decode", "c"): op(0.005, 10, "jit(d)/router/x:", "router"),
        ("jit_decode", "d"): op(0.015, 10, "jit(d)/experts/x:", "experts"),
        ("jit_decode", "e"): op(0.002, 10, "jit(d)/embed/x:", "embed"),
        ("jit_prefill", "a"): op(0.060, 3, "jit(p)/mixer/x:", "mixer"),
        ("jit_prefill", "b"): op(0.012, 3, "jit(p)/copy:", None),
    }
    return regions.Table(ops, {"jit_train_step": 4, "jit_decode": 10,
                               "jit_prefill": 3},
                         {"jit_train_step": 0.37, "jit_decode": 0.07,
                          "jit_prefill": 0.08}, 0.0, 0.0)


def test_readers_arithmetic_on_a_made_up_table():
    run = {"apex_regions": _fake_table(), "counts": {
        "traced": (10.0, 14.0), "buckets": [128, 256],
        "prompt_tokens": [100, 200, 130, 90],
        # the last prompt's first token fell outside the traced span
        "first_delivery": {0: 10.5, 1: 11.0, 2: 13.9, 3: 14.2}}}
    got = {name: reader(name)(run) for name in ENTRIES}
    assert got["train_step_ms.embed"] == pytest.approx(10.0)
    assert got["train_step_ms.head_loss"] == pytest.approx(75.0)
    assert got["train_step_ms.unscoped"] == pytest.approx(5.0)
    assert got["train_step_ms.optimizer"] == got["train_step_ms.amp"] == 0.0
    assert got["train_step_ms.grad_sync"] == 0.0
    train = [v for k, v in got.items() if k.startswith("train_step_ms.")]
    assert sum(train) == pytest.approx(1e3 * 0.36 / 4)
    assert got["decode_ms.attention"] == pytest.approx(4.0)
    assert got["decode_ms.experts"] == pytest.approx(2.0)
    assert got["decode_ms.head"] == pytest.approx(0.2)
    assert got["decode_ms.mixer"] == got["decode_ms.unscoped"] == 0.0
    decode = [v for k, v in got.items() if k.startswith("decode_ms.")]
    assert sum(decode) == pytest.approx(1e3 * 0.062 / 10)
    # 128 + 256 + 256 bucket tokens were prefilled in the span
    assert got["prefill_ms_per_ktok.mixer"] == pytest.approx(60.0 / 0.64)
    assert got["prefill_ms_per_ktok.unscoped"] == pytest.approx(12.0 / 0.64)
    # no traced span, or no prompt delivered in it: nothing to divide by
    for counts in ({}, {**run["counts"], "first_delivery": {}}):
        assert reader("prefill_ms_per_ktok.mixer")(
            {"apex_regions": run["apex_regions"], "counts": counts}) is None
    # a run that names no cell has no trace file to read
    assert reader("decode_ms.mlp")({"counts": {}}) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_on_the_recorded_traces_with_regions(name, tables):
    table = tables[name]
    run = {"apex_regions": table, "counts": {
        "traced": (0.0, 1.0), "first_delivery": {0: 0.5, 1: 0.6},
        "prompt_tokens": [10, 20], "buckets": [16, 32]}}
    got = {m: reader(m)(run) for m in ENTRIES}
    for program, prefix in (("jit_train_step", "train_step_ms."),
                            ("jit_decode", "decode_ms."),
                            ("jit_prefill", "prefill_ms_per_ktok.")):
        family = [v for k, v in got.items() if k.startswith(prefix)]
        if program not in PROGRAMS[name]:
            assert all(v is None for v in family), program
            continue
        assert all(v is not None and v >= 0 for v in family), program
        ops = sum(op.seconds for (p, _), op in table.ops.items()
                  if p == program)
        per = table.executions[program] if program != "jit_prefill" \
            else 48 / 1e3
        assert sum(family) == pytest.approx(1e3 * ops / per, rel=1e-9)
    if name == "small_deepseek_serve_regions":
        assert got["decode_ms.experts"] > 0 == got["decode_ms.mixer"]
        assert got["prefill_ms_per_ktok.experts"] > 0
    if name == "small_hybrid_serve_regions":
        assert got["decode_ms.mixer"] > 0 == got["decode_ms.experts"]
        assert got["prefill_ms_per_ktok.mixer"] > 0
    if name == "small_bert_regions":
        assert got["train_step_ms.optimizer"] > 0
        assert got["train_step_ms.head_loss"] > 0


def test_the_trace_file_is_found_by_the_cells_name(tmp_path, capsys):
    """``of`` reads ``.bench_trace/<cell>/plugins/profile/*/*.xplane.pb``
    of the cell's checkout once and keeps the table on ``run``."""
    where = tmp_path / ".bench_trace" / "a.cell" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    with gzip.open(path_of("small_bert_regions"), "rb") as f:
        (where / "host.xplane.pb").write_bytes(f.read())
    run = {"cell": types.SimpleNamespace(root=str(tmp_path), name="a.cell"),
           "counts": {}}
    first = reader("train_step_ms.mlp")(run)
    assert first > 0 and "apex_regions" in run
    assert "read the trace's operation metadata in" in capsys.readouterr().err
    table = run["apex_regions"]
    assert reader("train_step_ms.attention")(run) > 0
    assert run["apex_regions"] is table
    assert table.read_s < 5.0


# -- the manifest ------------------------------------------------------------

def test_manifest_gains_exactly_the_twenty_entries_at_the_end():
    m = harness.load_json(REPO, "BENCHMARK.json")
    tail = m["per_layer"][-20:]
    assert [e["name"] for e in tail] == list(ENTRIES)
    for e in tail:
        layer, cells = ENTRIES[e["name"]]
        assert e == {"name": e["name"], "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "train_tokens_per_s_per_chip"
                     if layer != "Serving device programs"
                     else "serve_tokens_per_s", "workloads": cells}
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           e["name"] + ".py"))
    assert len(m["per_layer"]) == 36 + 20
    names = [e["name"] for e in m["per_layer"]]
    assert len(set(names)) == len(names)
    assert names[35] == "deepseek_moe_gmm_roofline_pct"
    # no prefill runs in resident_context_decode's traced span: no reader
    # of the prefill programs lists it
    assert all(DEEPSEEK not in e["workloads"] for e in m["per_layer"]
               if e["name"].startswith("prefill_"))
    # every cell a new entry lists reports the end-to-end metric it moves
    moved = {e["name"]: e["workloads"] for e in m["end_to_end"]
             if "workloads" in e}
    for e in tail:
        assert set(e["workloads"]) <= set(moved[e["moves"]])
    # what each cell's traced line gains: its own groups and no other's
    gains = {BERT[0]: 7, BERT[1]: 8, GPT[0]: 4, GPT[1]: 8, HYBRID: 10,
             NEMOTRON: 12, DEEPSEEK: 5}
    assert sum(gains.values()) == sum(len(c) for _, c in ENTRIES.values())
    for cell, gained in gains.items():
        mine = [e["name"] for e in harness.Cell(cell).per_layer
                if e["name"] in ENTRIES]
        assert len(mine) == gained, cell


def test_the_files_that_were_there_are_as_they_were():
    """``test_data_driven.py``'s promise for this PR: the recorded traces
    the older tests read are byte for byte what they were."""
    want = {
        "small_bert":
            "3db6024600fac51b75620aa050f1f41bfe039beef851ada8356aee6862175ac0",
        "small_gpt_serve":
            "02377a74f1c47c1cf7f634ea892574e7533493cba1aa9e70d87c636d2ba03753",
        "small_hybrid_serve":
            "306b36c20205af0d83849c828ac0d72757ff38aaa8e5b50ee4cd092882ba05e9"}
    for name, sha in want.items():
        with open(path_of(name), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == sha, name
    for name in NEW:
        assert os.path.getsize(path_of(name)) < 1 << 20, name


# -- what the tests pinned by this PR check besides their pins ---------------

def test_the_eight_readers_of_pr_24_return_none_without_step_spans():
    """``test_spans.py::test_readers_return_none_without_step_spans`` takes
    the manifest's LAST eight entries, which were PR 24's when it was
    written and are this PR's now (they pass it too: no cell, no table).
    PR 24's eight, by name."""
    import numpy as np

    fake = types.SimpleNamespace(
        window=(0.0, 30.0), idle_gaps=lambda: np.asarray([[1.0, 2.0]]),
        kernel_time=lambda match: (0.0, 0))
    run = {"trace": fake, "apex_spans": [],
           "counts": {"sizes": {"layers": 24}}}
    for name in ("tick_idle_ms.admit", "tick_idle_ms.build_inputs",
                 "tick_idle_ms.dispatch", "tick_idle_ms.accept",
                 "tick_idle_ms.commit_flush", "tick_idle_ms.unspanned",
                 "prefill_shared_pct", "flash_kernel_ms_per_prefill"):
        assert reader(name)(run) is None, name


def test_what_the_pinned_tests_of_pr_36_check_besides():
    """``test_deepseek_cell.py`` pins the DeepSeek cell's readers as ``NEW``
    + ``SHARED`` and the Nemotron cell's as sixteen; this PR appends five
    and twelve. Everything else those two tests check, word for word, with
    the pins as they stand now."""
    import json

    from test_deepseek_cell import (
        CELL, CELLS_BEFORE, CONFIG, CONFIGS_BEFORE, METRICS_BEFORE,
        NEW as DEEPSEEK_NEW, SHARED, config_file)

    m = harness.load_json(REPO, "BENCHMARK.json")
    configs = [c["name"] for c in m["configs"]]
    assert configs[:4] == CONFIGS_BEFORE and configs.index(CONFIG) >= 4
    config = m["configs"][configs.index(CONFIG)]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    body = config_file()
    assert body["source"] == config["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/"
        "config.json")
    assert body["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert body["runner"] == "deepseek_serve"
    for kind in ("runners/deepseek_serve", "reference/" + CONFIG,
                 "kernels/mla", "kernels/deepseek_decode_step"):
        assert os.path.exists(os.path.join(BENCH, kind + ".py")), kind
    cells = [w["name"] for w in m["workloads"]]
    assert cells[:6] == CELLS_BEFORE and cells.index(CELL) >= 6
    assert m["workloads"][cells.index(CELL)] == {
        **m["workloads"][cells.index(CELL)], "config": CONFIG,
        "traffic": "resident_context_decode", "chips": 1}
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    at = {e["name"]: i for i, e in enumerate(m["per_layer"])}
    new = [m["per_layer"][at[name]] for name in DEEPSEEK_NEW]
    assert all(e["workloads"] == [CELL] and e["moves"] ==
               "serve_tokens_per_s" and e["layer"] == "Kernels"
               and e["source"] == "device_trace" for e in new)
    assert all(at[name] >= METRICS_BEFORE for name in DEEPSEEK_NEW)
    assert [e["name"] for e in m["per_layer"][:METRICS_BEFORE]][-6:] == [
        "nemotron_decode_hbm_pct", "ssd_decode_kernel_ms_per_decode",
        "ssd_decode_roofline_pct", "moe_gmm_kernel_ms_per_decode",
        "moe_gmm_roofline_pct", "moe_load_max_over_mean"]
    cell = harness.Cell(CELL)
    assert [e["name"] for e in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    # the pin, as it stands: PR 38's five readers of the decode program
    mine = {n for n, (_, cells_) in ENTRIES.items() if CELL in cells_}
    assert mine == {"decode_ms.attention", "decode_ms.mlp", "decode_ms.head",
                    "decode_ms.unscoped", "decode_ms.experts"}
    assert {e["name"] for e in cell.per_layer} - set(DEEPSEEK_NEW) - mine \
        == SHARED
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if CELL in lists:
            assert lists.index(CELL) == len(lists) - 1 or all(
                c not in CELLS_BEFORE for c in lists[lists.index(CELL):])
            before = [c for c in lists if c in CELLS_BEFORE]
            assert before == sorted(before, key=CELLS_BEFORE.index)
    assert CELL not in m["per_layer"][at["prefill_device_ms_per_ktok"]][
        "workloads"]
    assert len(json.dumps(m)) < 64 << 10

    # ... and of test_what_the_pinned_tests_of_pr_33_check_besides
    config = {c["name"]: c for c in m["configs"]}[
        "nemotron3_super_120b_a12b"]
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    body = harness.load_json(REPO, config["file"])
    assert body["source"] == config["source"] and body["runner"] == \
        "nemotron_serve"
    by_cell = {w["name"]: w for w in m["workloads"]}
    assert by_cell[NEMOTRON] == {**by_cell[NEMOTRON],
                                 "config": "nemotron3_super_120b_a12b",
                                 "traffic": "many_slot_decode", "chips": 1}
    names = [e["name"] for e in m["per_layer"]]
    old = ("gdn_decode_kernel_ms_per_decode", "gdn_decode_roofline_pct",
           "gdn_chunk_kernel_ms_per_ktok", "gdn_chunk_roofline_pct",
           "hybrid_decode_hbm_pct", "hybrid_paged_attn_kernel_ms_per_decode",
           "hybrid_flash_kernel_ms_per_prefill")
    pr33 = ("nemotron_decode_hbm_pct", "ssd_decode_kernel_ms_per_decode",
            "ssd_decode_roofline_pct", "moe_gmm_kernel_ms_per_decode",
            "moe_gmm_roofline_pct", "moe_load_max_over_mean")
    first = names.index(old[0])
    assert tuple(names[first:first + 13]) == old + pr33
    by = {e["name"]: e for e in m["per_layer"]}
    assert all(by[n]["workloads"] == [HYBRID] for n in old)
    for n in pr33:
        assert by[n]["workloads"][0] == NEMOTRON
        assert by[n]["workloads"][1:] in ([], [CELL])
        assert by[n]["layer"] == "Kernels" and by[n]["moves"] == \
            "serve_tokens_per_s"
    assert m["per_layer"][first - 1] == {
        "name": "paged_attn_kernel_ms_per_decode", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": GPT}
    nemotron = harness.Cell(NEMOTRON)
    assert [e["name"] for e in nemotron.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    assert len(nemotron.per_layer) == 16 + 12     # the pin, as it stands
