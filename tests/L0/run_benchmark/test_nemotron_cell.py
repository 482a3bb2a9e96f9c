"""The cell ``nemotron3_super_120b_a12b.many_slot_decode`` (PR 33): its count
files by hand, its readers on traces without the new kernels (nothing, and no
raise) and on made-up runs (the arithmetic), its manifest entries and files,
the sizes its traffic offers, the run that has to come out not correct, and
what the tests pinned in ``conftest.py`` check besides their pins."""

import json
import os
import sys
import types

import pytest

from benchmark import harness, spans, trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIG = "nemotron3_super_120b_a12b"
CELL = CONFIG + ".many_slot_decode"
NEW = ("nemotron_decode_hbm_pct", "ssd_decode_kernel_ms_per_decode",
       "ssd_decode_roofline_pct", "moe_gmm_kernel_ms_per_decode",
       "moe_gmm_roofline_pct", "moe_load_max_over_mean")
SHARED = {"sched_step_ms.serve", "decode_device_ms", "device_idle_pct.serve",
          "prefill_device_ms_per_ktok", "tick_idle_ms.admit",
          "tick_idle_ms.build_inputs", "tick_idle_ms.dispatch",
          "tick_idle_ms.accept", "tick_idle_ms.commit_flush",
          "tick_idle_ms.unspanned"}
PEAKS = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
SERVING = ["gpt2_medium.offline_decode", "gpt2_medium.prompt_backlog",
           "olmo_hybrid_7b.long_prompt_decode", CELL]

sys.path.insert(0, BENCH)
try:
    import run as bench_run      # benchmark/run.py
finally:
    sys.path.remove(BENCH)


def config_file():
    return harness.load_json(BENCH, "configs", CONFIG + ".json")


def sizes(rehearsal=False):
    config = config_file()
    if rehearsal:
        config = harness.rehearsal_view(config)
    return harness.load_module("reference", CONFIG, BENCH).sizes_of(config)


def reader(name):
    return harness.load_module("metrics", name, BENCH).read


def kernel_counts(name):
    return harness.load_module("kernels", name, BENCH)


# -- the count files, by hand -------------------------------------------------

def test_ssd_decode_bytes_by_hand():
    # per slot: 128 heads x (the 64 x 128 state in and out, delta x, the
    # decay and the output, 64 each) and the B and C rows of 8 groups x 128;
    # float32
    assert kernel_counts("ssd").decode_bytes(sizes(), 128) == 4 * 128 * (
        128 * (2 * 64 * 128 + 3 * 64) + 2 * 8 * 128) == 1_087_373_312


def test_moe_counts_by_hand():
    moe = kernel_counts("moe")
    # 704 rows over 120 experts hit: rows in (bfloat16), each hit expert's
    # matrix once, rows out (bfloat16 after the first product, float32 after
    # the second)
    assert moe.gmm_bytes(704, 120, 1024, 2688, 2) == (
        704 * 1024 * 2 + 120 * 1024 * 2688 * 2 + 704 * 2688 * 2)
    assert moe.gmm_flops(704, 1024, 2688) == 2 * 704 * 1024 * 2688
    sz = sizes()
    assert moe.layer_bytes(sz, 704, 120) == (
        moe.gmm_bytes(704, 120, 1024, 2688, 2)
        + moe.gmm_bytes(704, 120, 2688, 1024, 4)) == 1_333_100_544
    assert moe.layer_flops(sz, 704) == 2 * 2 * 704 * 1024 * 2688
    # an expert with no row costs nothing; the experts held are not counted
    assert moe.layer_bytes(sz, 0, 0) == 0


def test_decode_step_counts_by_hand_and_the_files_arithmetic():
    step = kernel_counts("nemotron_decode_step")
    sz = sizes()
    said = config_file()["deployment"]["parameters"]
    mamba = 4096 * (8192 + 10240 + 128) + 8192 * 4096 + 4 * 10240
    assert step._mamba(sz) == (mamba, 4096 + 10240 + 3 * 128 + 8192)
    assert sum(step._mamba(sz)) == said["mamba_layer"] == 109_640_064
    attention = 4096 * (4096 + 2 * 256) + 4096 * 4096
    assert step._attention(sz) == (attention, 4096)
    assert sum(step._attention(sz)) == said["attention_layer"] == 35_655_680
    outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert step._experts_outside(sz) == (outside, 4096 + 512)
    assert sum(step._experts_outside(sz)) == said[
        "expert_layer_outside_experts"] == 54_530_560
    assert step.one_expert(sz) == said["one_expert"] == 2 * 1024 * 2688
    assert said["embedding_and_head"] == 2 * 32768 * 4096
    whole = (5 * said["mamba_layer"] + said["attention_layer"] + 5 * (
        said["expert_layer_outside_experts"] + 128 * said["one_expert"])
        + said["embedding_and_head"] + 4096)
    assert round(whole / 1e9, 2) == 4.65            # 9.30 GB in bfloat16
    # a step reads the head, not the embedding; only the experts hit
    matrices = 5 * mamba + attention + 5 * outside + 4096 * 32768
    small = 5 * 22912 + 4096 + 5 * 4608 + 4096
    assert step.weight_bytes(sz, 600) == 2 * (
        matrices + 600 * 5_505_024) + 4 * small
    assert step.weight_bytes(sz, 640) - step.weight_bytes(sz, 0) == (
        2 * 640 * 5_505_024)                        # 7.0 GB: half the step
    assert step.state_bytes(sz, 128) == 5 * 128 * 2 * 4 * (
        128 * 64 * 128 + 3 * 10240) == 5_525_995_520
    assert step.kv_bytes(sz, 64_000) == 2 * 1 * 256 * 2 * 64_000
    assert step.bytes_needed(sz, 64_000, 128, 600) == (
        step.weight_bytes(sz, 600) + 5_525_995_520 + 65_536_000)


# -- the readers -----------------------------------------------------------------

@pytest.mark.parametrize("recorded", ["small_gpt_serve", "small_hybrid_serve"])
def test_new_readers_give_nothing_on_traces_without_the_new_kernels(recorded):
    """The parent's programs (GPT, the Gated DeltaNet hybrid), with their own
    counts, with this cell's and with none: no reader raises, every one
    returns ``None``. ``cell`` has no checkout to find a trace file in."""
    path = os.path.join(DATA, recorded + ".xplane.pb.gz")
    cell = types.SimpleNamespace(bench_dir=BENCH)
    moe = {"load": [[3, 1], [2, 2]], "hit": [2, 2], "steps": 2}
    for counts in ({"sizes": {"layers": 2, "hidden": 64}, "slots": 3,
                    "mapped_positions": 40},
                   {"sizes": sizes(True), "mapped_positions": 40, "moe": moe},
                   {"sizes": sizes(True), "mapped_positions": 40,
                    "moe": None}, {}):
        run = {"trace": trace.reduce_file(path),
               "apex_spans": spans.load(path), "counts": counts,
               "peaks": PEAKS, "cell": cell}
        got = {name: reader(name)(run) for name in NEW
               if name != "moe_load_max_over_mean"}
        assert all(v is None for v in got.values()), got
    # the trace file itself holds no such call
    gmm = harness.load_module("metrics", "moe_gmm_kernel_ms_per_decode", BENCH)
    assert gmm.load(path) == {}


def made_up(ssd_calls=15, gmm=(0.030, 30), moe="default", slots=4):
    """A run of three decode executions at the full sizes' layer counts (5
    Mamba-2 and 5 expert layers), four held experts counted."""
    ssd_name = ("%apex_ssd_decode_fwd.10 = (f32[128,2,64,64], f32[5,128,128,"
                "64,128]) custom-call(s32[1] %a, s32[128] %b)")

    def kernel_time(match):
        return (0.006 * ssd_calls / 15, ssd_calls) \
            if match(ssd_name) and ssd_calls else (0.0, 0)

    if moe == "default":
        moe = {"load": [[30, 10, 0, 0]] * 5, "hit": [6] * 5, "steps": 3}
    span = spans.Span("exec", 1.0, 1.1, {"state_slots": slots}, -1)
    return {"trace": types.SimpleNamespace(
                kernel_time=kernel_time, window=(0.0, 2.0),
                program_times=lambda p: [0.02, 0.03, 0.04]
                if p == "jit_decode" else []),
            "apex_spans": [span], "moe_gmm_calls": {
                "jit_decode": gmm, "jit_prefill": (0.5, 20)},
            "counts": {"sizes": sizes(), "mapped_positions": 1000,
                       "moe": moe},
            "peaks": PEAKS, "cell": types.SimpleNamespace(bench_dir=BENCH)}


def test_ssd_readers_on_a_made_up_run():
    run = made_up()
    # 15 calls = 3 executions of 5 layers: 6 ms over 3
    assert reader("ssd_decode_kernel_ms_per_decode")(run) == pytest.approx(2.0)
    need = 5 * kernel_counts("ssd").decode_bytes(sizes(), 4)
    assert reader("ssd_decode_roofline_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.002)
    # an execution cut by the session, or no call at all: nothing
    for calls in (14, 0):
        cut = made_up(ssd_calls=calls)
        assert reader("ssd_decode_kernel_ms_per_decode")(cut) is None
        assert reader("ssd_decode_roofline_pct")(cut) is None


def test_moe_readers_on_a_made_up_run():
    run = made_up()
    # only the calls inside jit_decode: 30 calls = 3 executions x 5 layers x
    # 2 products, 30 ms over 3; the prefill's 0.5 s are not in it
    assert reader("moe_gmm_kernel_ms_per_decode")(run) == pytest.approx(10.0)
    # per step and layer: 40 rows over 3 steps, 2 experts hit
    moe = kernel_counts("moe")
    need = 5 * max(moe.layer_bytes(sizes(), 40 / 3, 2) / 819e9,
                   moe.layer_flops(sizes(), 40 / 3) / 197e12)
    assert reader("moe_gmm_roofline_pct")(run) == pytest.approx(
        100 * need / 0.010)
    # the fullest of four held experts got 30 of 40: three times the mean
    assert reader("moe_load_max_over_mean")(run) == pytest.approx(3.0)
    # calls that are no multiple of two per expert layer, none, or a program
    # that counted nothing: nothing
    assert reader("moe_gmm_kernel_ms_per_decode")(
        made_up(gmm=(0.03, 29))) is None
    assert reader("moe_gmm_roofline_pct")(made_up(gmm=(0.0, 0))) is None
    for name in ("moe_gmm_roofline_pct", "moe_load_max_over_mean",
                 "nemotron_decode_hbm_pct"):
        assert reader(name)(made_up(moe=None)) is None
        assert reader(name)(made_up(moe={"load": [], "hit": [],
                                         "steps": 0})) is None


def test_many_prompt_rows_an_expert_are_bound_by_operations_not_bytes():
    moe, sz = kernel_counts("moe"), sizes()
    by_bytes = lambda rows, hit: moe.layer_bytes(sz, rows, hit) / 819e9
    by_flops = lambda rows: moe.layer_flops(sz, rows) / 197e12
    assert by_bytes(704, 120) > 30 * by_flops(704)      # a decode tick
    assert by_flops(128 * 512) > by_bytes(128 * 512, 128)


def test_decode_hbm_pct_on_a_made_up_run():
    run = made_up()
    need = kernel_counts("nemotron_decode_step").bytes_needed(
        sizes(), 1000, 4, 5 * 2)            # 2 experts hit a layer a step
    assert reader("nemotron_decode_hbm_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.03)          # the median execution
    run["apex_spans"] = []                  # no exec span says the slots
    assert reader("nemotron_decode_hbm_pct")(run) is None


# -- the manifest and the files ---------------------------------------------------

def test_manifest_gains_the_cell_and_only_appends():
    m = harness.load_json(REPO, "BENCHMARK.json")
    config = m["configs"][-1]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["name"] == CONFIG
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    body = config_file()
    assert body["source"] == config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    assert body["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert body["runner"] == "nemotron_serve"
    for kind in ("runners/nemotron_serve", "reference/" + CONFIG):
        assert os.path.exists(os.path.join(BENCH, kind + ".py")), kind
    assert m["workloads"][-1] == {**m["workloads"][-1], "name": CELL,
                                  "config": CONFIG,
                                  "traffic": "many_slot_decode", "chips": 1}
    at = {e["name"]: i for i, e in enumerate(m["per_layer"])}
    new = [m["per_layer"][at[name]] for name in NEW]
    assert all(e["workloads"] == [CELL] and e["moves"] ==
               "serve_tokens_per_s" and e["layer"] == "Kernels" for e in new)
    assert {e["name"]: e["source"] for e in new} == {
        **{name: "device_trace" for name in NEW},
        "moe_load_max_over_mean": "program_counter"}
    # appended: every entry the benchmark had stands before the first of them
    assert sorted(at[name] for name in NEW) == list(range(
        len(at) - len(NEW), len(at)))
    cell = harness.Cell(CELL)
    assert [e["name"] for e in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {e["name"] for e in cell.per_layer} - set(NEW) == SHARED
    # wherever the cell was appended it stands last, after the cells before
    for e in m["end_to_end"] + m["per_layer"]:
        if CELL in e.get("workloads", []):
            assert e["workloads"][-1] == CELL


def test_what_the_pinned_tests_of_pr_27_check_besides():
    """``test_hybrid_cell.py``'s two manifest tests pin olmo_hybrid_7b's
    entries as the last; what else they check, for the entries as they stand
    now: the configuration before the last, the cell before the last, its
    seven readers right before this cell's six, its name appended after
    GPT's cells, and PR 25's metric entry word for word before them."""
    m = harness.load_json(REPO, "BENCHMARK.json")
    old = ("gdn_decode_kernel_ms_per_decode", "gdn_decode_roofline_pct",
           "gdn_chunk_kernel_ms_per_ktok", "gdn_chunk_roofline_pct",
           "hybrid_decode_hbm_pct", "hybrid_paged_attn_kernel_ms_per_decode",
           "hybrid_flash_kernel_ms_per_prefill")
    cell = "olmo_hybrid_7b.long_prompt_decode"
    config = m["configs"][-2]
    assert config["name"] == "olmo_hybrid_7b"
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    body = harness.load_json(REPO, config["file"])
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert m["workloads"][-2] == {**m["workloads"][-2], "name": cell,
                                  "config": "olmo_hybrid_7b",
                                  "traffic": "long_prompt_decode", "chips": 1}
    names = [e["name"] for e in m["per_layer"]]
    assert names[-len(NEW) - len(old):-len(NEW)] == list(old)
    assert all(e["workloads"] == [cell] for e in m["per_layer"]
               if e["name"] in old)
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if cell in lists and CELL in lists:
            assert lists[-2:] == [cell, CELL]
    assert m["per_layer"][-len(NEW) - len(old) - 1] == {
        "name": "paged_attn_kernel_ms_per_decode", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2_medium.offline_decode",
                      "gpt2_medium.prompt_backlog"]}


def test_the_backlog_mixes_are_the_four_serving_cells():
    mixes = sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if harness.load_json(BENCH, "traffic", f).get("arrivals", {}).get(
            "process") == "backlog")
    assert mixes == ["long_prompt_decode", "many_slot_decode",
                     "offline_decode", "prompt_backlog"]
    m = harness.load_json(REPO, "BENCHMARK.json")
    assert sorted(w["traffic"] for w in m["workloads"]
                  if w["name"] in SERVING) == mixes


def test_configuration_file_holds_the_published_widths_and_its_cut():
    published = {
        "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
        "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "expand": 2,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "moe_latent_size": 1024,
        "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376,
        "num_experts_per_tok": 22, "routed_scaling_factor": 5, "n_group": 1,
        "topk_group": 1, "norm_eps": 1e-05, "mlp_hidden_act": "relu2",
        "max_position_embeddings": 262144, "rope_theta": 10000}
    config = config_file()
    assert {k: config[k] for k in published} == published
    cut = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 128),
           "vocab_size": (131072, 32768), "num_nextn_predict_layers": (1, 0)}
    for key, (was, now) in cut.items():
        assert (config["published"][key], config[key]) == (was, now)
    whole = config["published"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"),
            whole.count("*")) == (88, 40, 40, 8)
    # layers 27-37 of the published pattern, counted from 0
    assert whole[27:38] == config["hybrid_override_pattern"] == "MEMEMEMEM*E"
    assert config["expert_offset"] == 0
    assert set(config) >= {"assumed", "deployment", "left_out", "serving",
                           "correct", "rehearsal"}
    assert config["deployment"]["chips_per_layer"] == 4
    assert set(config["assumed"]) == {
        "positions", "block", "mamba", "experts", "state", "weights", "eos",
        "dropout", "activations"}
    serving = config["serving"]
    assert (serving["slots"], serving["page_size"], serving["max_len"]) == (
        128, 16, 1024)
    sz = sizes()
    assert (sz["layers"], sz["mamba_layers"], sz["expert_layers"],
            sz["attention_layers"]) == (11, 5, 5, 1)
    assert (sz["router_experts"], sz["experts_held"], sz["vocab"]) == (
        512, 128, 32768)
    limits = config["correct"]["limits"]
    assert set(limits) == {"logit_gap_max", "logit_gap_mean"}
    assert set(config["correct"]["reasons"]) >= set(limits)


@pytest.mark.parametrize("rehearsal", [False, True])
def test_traffic_is_the_issues_and_every_seed_offers_the_same_work(rehearsal):
    from benchmark import traffic
    mix = harness.load_json(BENCH, "traffic", "many_slot_decode.json")
    assert mix["arrivals"] == {"process": "backlog", "requests": 768}
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 64, "hi": 512}
    assert mix["max_new_tokens"] == {"dist": "uniform", "lo": 128, "hi": 512}
    assert "shared_prefix" not in mix and mix["temperatures"] == [0.0, 0.8]
    assert (mix["sizes_seed"], mix["trace_start_s"], mix["trace_seconds"]) \
        == (3301, 8.0, 6.0)
    if rehearsal:
        mix = harness.rehearsal_view(mix)
    a, b, c = (traffic.requests(mix, seed, 30.0, 32768, 1024)
               for seed in (1, 2, 2 ** 31 + 7))
    work = lambda rs: [(len(r.prompt), r.max_new_tokens, r.temperature)
                       for r in rs]
    assert work(a) == work(b) == work(c)
    assert len(a) == mix["arrivals"]["requests"]
    assert all(r.due_s == 0.0 for r in a)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(0 <= t < 32768 for r in a[:20] for t in r.prompt)
    assert all(len(r.prompt) + r.max_new_tokens <= 1024 for r in a)
    assert {r.prompt for r in a}.isdisjoint(r.prompt for r in b)
    if not rehearsal:
        # the first wave fills the 128 slots with every bucket the mix hits
        first = sorted({min(b for b in (128, 256, 512) if b >= len(r.prompt))
                        for r in a[:128]})
        assert first == [128, 256, 512]


# -- the rehearsal: the window line, and the run that has to fail -----------------

def rehearse(capsys, workload, *extra, stderr=None):
    rc = bench_run.main(["--workload", workload, "--seed", str(2 ** 31 + 7),
                         "--seconds", "2", "--trace", "0", "--cpu-rehearsal",
                         *extra])
    captured = capsys.readouterr()
    if stderr is not None:
        stderr.append(captured.err)
    lines = [json.loads(l) for l in captured.out.splitlines()
             if l.startswith("{")]
    assert rc == 0 and lines[-1]["stage"] == "rehearsal_result"
    return json.loads(lines[-1]["would_be"]), lines


@pytest.mark.parametrize("workload", SERVING)
def test_window_line_of_every_serving_cell(capsys, workload):
    """``test_rehearsal.py::test_window_line_says_what_is_left_of_the_
    backlog`` with the serving cells the benchmark has now: ``backlog_left``
    always; where the window drained the backlog also ``drained_at_s`` and a
    line on standard error that names the traffic file."""
    m = harness.load_json(REPO, "BENCHMARK.json")
    assert SERVING == [w["name"] for w in m["workloads"] if w["chips"] == 1
                       and harness.Cell(w["name"]).traffic["kind"]
                       == "requests"]
    said = []
    result, lines = rehearse(capsys, workload, stderr=said)
    [window] = [l for l in lines if l.get("stage") == "window"]
    cell = harness.Cell(workload)
    offered = cell.traffic["rehearsal"]["arrivals"]["requests"]
    assert window["requests_submitted"] == offered
    assert 0 <= window["backlog_left"] <= offered - window[
        "requests_finished"]
    [err] = said
    if window["backlog_left"]:
        assert "drained_at_s" not in window and "drained" not in err
    else:
        assert 0.0 < window["drained_at_s"] <= 2.0
        assert (f"benchmark/traffic/{cell.traffic_name}.json needs more "
                f"than {offered} arrivals.requests") in err
    assert result["correct"] is True and window["compiles_in_window"] == 0
    if workload == CELL:
        # the program's counters, read before and after the window
        assert window["moe_steps"] == window["steps"] > 0
        assert len(window["moe_rows_per_step"]) == 2
        hit, held = window["moe_hit_per_step_of_held"]
        assert held == 8 and all(0 < h <= held for h in hit)
        [correct] = [l for l in lines if l.get("stage") == "correct"]
        assert 0.5 < correct["routes_agree"] <= 1.0


def test_served_tokens_altered_where_they_are_staged_are_not_correct(capsys):
    result, lines = rehearse(capsys, CELL, "--option", "break_tokens=1")
    assert result["correct"] is False
    bad = {n["number"] for l in lines if l.get("stage") == "correct"
           for n in l["numbers"] if not n["ok"]}
    assert bad == {"served_logit_gap_max", "served_logit_gap_mean"}


def test_the_control_keeps_the_recurrent_state_in_bfloat16():
    """The control's arithmetic: the reference with its Mamba-2 state and
    scan in bfloat16 reads further from the float32 reference than rounding,
    at the rehearsal sizes, on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = harness.load_module("reference", CONFIG, BENCH)
    sz = sizes(True)
    params = ref.served_weights(sz, 7)
    ids = jnp.asarray(np.random.RandomState(0).randint(2, sz["vocab"], 200))
    at = jnp.arange(150, 200)
    with jax.default_matmul_precision("highest"):
        sound = ref.logits_at(params, sz, ids, at)
        again = ref.logits_at(params, sz, ids, at, "float32")
        low = ref.logits_at(params, sz, ids, at, "bfloat16_state")
    assert float(jnp.abs(sound - again).max()) == 0.0
    assert float(jnp.abs(sound - low).max()) > 1e-3
    with pytest.raises(ValueError):
        ref.logits_at(params, sz, ids, at, "float16")
