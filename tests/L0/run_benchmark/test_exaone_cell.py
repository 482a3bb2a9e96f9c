"""The cell ``k_exaone_236b_a23b.long_context_reasoning`` (PR 40): the cut's
parameter count term by term, the count files by hand, its readers on traces
without the new kernel (nothing, and no raise) and on made-up runs (the
arithmetic), the readers that were there on this cell's counts, its manifest
entries and files (found BY NAME: this file pins nothing as the last entry of
a list and no list's length), the sizes its traffic offers, the window line
of every serving cell the manifest has, the runs that have to come out not
correct, and what the tests pinned in ``tests/conftest.py`` by this PR check
besides their pins."""

import json
import os
import sys
import types

import pytest

from benchmark import harness, spans, trace, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIG = "k_exaone_236b_a23b"
MIX = "long_context_reasoning"
CELL = CONFIG + "." + MIX
NEW = ("window_attn_kernel_ms_per_decode", "window_attn_roofline_pct",
       "gqa_paged_attn_roofline_pct", "exaone_decode_hbm_pct",
       "exaone_moe_gmm_roofline_pct")
# the readers that were there and read this cell as they are
SHARED = {"sched_step_ms.serve", "decode_device_ms", "device_idle_pct.serve",
          "tick_idle_ms.admit", "tick_idle_ms.build_inputs",
          "tick_idle_ms.dispatch", "tick_idle_ms.accept",
          "tick_idle_ms.commit_flush", "tick_idle_ms.unspanned",
          "decode_ms.attention", "decode_ms.mlp", "decode_ms.experts",
          "decode_ms.head", "decode_ms.unscoped",
          "hybrid_paged_attn_kernel_ms_per_decode",
          "moe_gmm_kernel_ms_per_decode", "moe_load_max_over_mean"}
PEAKS = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
MANIFEST = harness.load_json(REPO, "BENCHMARK.json")
SERVING = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1
           and harness.Cell(w["name"]).traffic["kind"] == "requests"]
EXPERT_CELLS = ("nemotron3_super_120b_a12b.many_slot_decode",
                "deepseek_v3.resident_context_decode", CELL)

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


# -- the cut, term by term ----------------------------------------------------

def test_the_cut_holds_3_711_959_040_matrix_parameters_term_by_term():
    """ISSUE 40's arithmetic against ``kernels/exaone_decode_step.py`` and
    the configuration file's ``deployment.parameters``; the whole model by
    the same terms agrees with its name."""
    step, sz = kernel_counts("exaone_decode_step"), sizes()
    said = config_file()["deployment"]["parameters"]
    assert step.attention(sz) == (6144 * 8192 + 2 * 6144 * 1024
                                  + 8192 * 6144, 6144 + 2 * 128)
    assert step.attention(sz)[0] == said["attention_layer"] == 113_246_208
    assert step.dense_mlp(sz) == (3 * 6144 * 18432, 6144)
    assert step.dense_mlp(sz)[0] == said["dense_mlp"] == 339_738_624
    assert step.one_expert(sz) == said["one_expert"] == 37_748_736
    outside = step.experts_outside(sz)
    assert outside == (786_432 + 37_748_736, 6144 + 128)
    assert outside[0] == said["expert_layer_outside_attention_and_experts"]
    # an expert layer outside its routed experts: the catalog's "about 152 M"
    assert step.attention(sz)[0] + outside[0] == 151_781_376
    assert 2 * 19200 * 6144 == said["embedding_and_head"] == 235_929_600
    matrices, small = step.parameters(sz)
    assert matrices == said["matrices"] == 3_711_959_040 == (
        5 * 113_246_208 + 339_738_624 + 4 * (38_535_168 + 16 * 37_748_736)
        + 235_929_600)
    # norms (a layer: 2 x 6144 + 2 x 128; the final one) and router biases
    assert small == 5 * (2 * 6144 + 256) + 6144 + 4 * 128 == 69_376
    assert round((2 * matrices + 4 * small) / 1e9, 2) == 7.42
    assert config_file()["deployment"]["chips_per_layer"] == 8
    # the published model by the same terms
    whole = {**sz, "layers": 48, "expert_layers": 47, "vocab": 153600}
    total = step.parameters(whole, experts=128)[0]
    assert round(total / 1e9, 1) == 236.6
    active = total - 47 * 120 * step.one_expert(sz)
    assert active == 23_667_671_040     # with embedding AND head; the
    assert round((active - 153600 * 6144) / 1e9, 1) == 22.7   # lookup less


def test_the_program_holds_what_the_count_says():
    """The served tree's shapes at the cell's sizes, traced and not made."""
    import jax

    sz = sizes()
    ref = harness.load_module("reference", CONFIG, BENCH)
    tree = jax.eval_shape(lambda k: ref.make_weights(sz, k),
                          jax.random.PRNGKey(0))
    by_dtype = {}
    for leaf in jax.tree.leaves(tree):
        by_dtype[str(leaf.dtype)] = by_dtype.get(str(leaf.dtype), 0) \
            + leaf.size
    assert by_dtype == {"bfloat16": 3_711_959_040, "float32": 69_376}
    cfg = harness.load_module("runners", "exaone_serve",
                              BENCH).model_config(config_file(), sz)
    from apex_tpu.models import exaone_moe

    mine = jax.eval_shape(lambda k: exaone_moe.init(k, cfg),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, mine) \
        == jax.tree.map(lambda a: a.shape, tree)
    assert (cfg.window, cfg.kv_layers, cfg.window_layers, cfg.pattern) == (
        128, 1, 4, (True, True, False, True))


def test_count_files_by_hand():
    sz = sizes()
    window, step = (kernel_counts(k) for k in ("window_attention",
                                               "exaone_decode_step"))
    # a position's K and V rows: 2 x 1024 bfloat16 = 4 KB; 64 heads take a
    # score and a value update of 128 multiply-adds each
    assert window.decode_bytes(sz, 1) == 4096
    assert window.decode_flops(sz, 1) == 4 * 64 * 128 == 32_768
    # far under the ridge (240 operations a byte): the bytes are the bound
    assert window.seconds_needed(sz, 1000, PEAKS) == 4096e3 / 819e9
    # weights of a step with every held expert hit: the whole share less
    # the embedding, which is looked up by row
    all_hit = step.weight_bytes(sz, 4 * 16)
    assert all_hit == 2 * (3_711_959_040 - 19200 * 6144) + 4 * 69_376
    assert step.weight_bytes(sz, 0) == all_hit - 2 * 64 * 37_748_736
    # the caches: the ONE full layer at the positions mapped, the FOUR
    # sliding layers at what their windows leave
    assert step.cache_bytes(sz, 300_000, 8192) == 4096 * (300_000
                                                          + 4 * 8192)
    assert step.bytes_needed(sz, 300_000, 8192, 62.8) == pytest.approx(
        step.weight_bytes(sz, 62.8) + 4096 * 332_768)
    # ISSUE 40's reckoning of a step: 8.5 GB, 10.4 ms at 819 GB/s
    assert step.bytes_needed(sz, 64 * 5300, 64 * 128, 4 * 15.7) / 819e9 \
        == pytest.approx(10.4e-3, rel=0.02)
    moe = kernel_counts("moe")
    assert step.gmm_layer_bytes(sz, 64, 15.7) == pytest.approx(
        moe.gmm_bytes(64, 15.7, 6144, 4096)
        + moe.gmm_bytes(64, 15.7, 2048, 6144))
    assert step.gmm_layer_flops(sz, 64) == 2 * 64 * 3 * 6144 * 2048


# -- the readers ------------------------------------------------------------------

@pytest.mark.parametrize("recorded", ["small_gpt_serve", "small_hybrid_serve"])
def test_new_readers_give_nothing_on_traces_without_the_new_kernel(recorded):
    """The parent's programs (GPT, the Gated DeltaNet hybrid), with their own
    counts, with this cell's and with none: no reader raises, every one
    returns ``None``."""
    path = os.path.join(DATA, recorded + ".xplane.pb.gz")
    cell = types.SimpleNamespace(bench_dir=BENCH)
    moe = {"load": [[3, 1], [2, 2]], "hit": [2, 2], "steps": 2}
    for counts in ({"sizes": {"layers": 2, "hidden": 64}, "slots": 3,
                    "mapped_positions": 40},
                   {"sizes": {"layers": 16, "full_layers": 4, "heads": 30},
                    "mapped_positions": 40},
                   {"sizes": sizes(True), "mapped_positions": 40,
                    "window_positions": 24, "moe": moe},
                   {"sizes": sizes(True), "mapped_positions": 40,
                    "moe": None}, {}):
        run = {"trace": trace.reduce_file(path),
               "apex_spans": spans.load(path), "counts": counts,
               "peaks": PEAKS, "cell": cell}
        got = {name: reader(name)(run) for name in NEW}
        assert all(v is None for v in got.values()), got


def made_up(window_calls=12, full_calls=3, gmm=(0.024, 24), moe="default",
            positions=300_000, window_positions=8192):
    """A run of three decode executions at the full sizes' layer counts (four
    bounded calls and one full one a step, 4 expert layers), four held
    experts counted."""
    names = {"window": ("%apex_paged_window_decode_fwd.3 = f32[64,64,128]"
                        "{2,1,0} custom-call(s32[64,9] %a, s32[64] %b)",
                        0.0015, window_calls),
             "full": ("%apex_paged_decode_fwd.2 = f32[64,64,128]{2,1,0} "
                      "custom-call(s32[64,712] %a, s32[64] %b)", 0.0066,
                      full_calls)}

    def kernel_time(match):
        for name, seconds, calls in names.values():
            if match(name) and calls:
                return seconds, calls
        return 0.0, 0

    if moe == "default":
        moe = {"load": [[150, 42, 0, 0]] * 4, "hit": [6] * 4, "steps": 3}
    return {"trace": types.SimpleNamespace(
                kernel_time=kernel_time, window=(0.0, 2.0),
                idle_pct=lambda: 12.5,
                program_times=lambda p: [0.012, 0.014, 0.016]
                if p == "jit_decode" else []),
            "apex_spans": [], "moe_gmm_calls": {
                "jit_decode": gmm, "jit_prefill": (0.5, 16)},
            "counts": {"sizes": sizes(), "mapped_positions": positions,
                       "window_positions": window_positions, "moe": moe,
                       "step_walls": [(0.0, 0.015), (1.0, 0.016),
                                      (2.0, 0.017)]},
            "peaks": PEAKS, "cell": types.SimpleNamespace(bench_dir=BENCH)}


def test_window_and_full_attention_readers_on_a_made_up_run():
    run = made_up()
    # 12 bounded calls = 3 executions of 4 sliding layers: 1.5 ms over 3
    assert reader("window_attn_kernel_ms_per_decode")(run) \
        == pytest.approx(0.5)
    assert reader("window_attn_roofline_pct")(run) == pytest.approx(
        100 * 4 * 8192 * 4096 / 819e9 / 0.0005)
    # 3 full calls = 3 executions of the one full layer: 6.6 ms over 3
    assert reader("hybrid_paged_attn_kernel_ms_per_decode")(run) \
        == pytest.approx(2.2)
    assert reader("gqa_paged_attn_roofline_pct")(run) == pytest.approx(
        100 * 300_000 * 4096 / 819e9 / 0.0022)
    assert reader("gqa_paged_attn_roofline_pct")(run) < 100
    # an execution cut by the session, no call at all, no position counted
    for cut in (made_up(window_calls=11), made_up(window_calls=0)):
        assert reader("window_attn_kernel_ms_per_decode")(cut) is None
        assert reader("window_attn_roofline_pct")(cut) is None
        assert reader("exaone_decode_hbm_pct")(cut) is None
    assert reader("window_attn_roofline_pct")(
        made_up(window_positions=0)) is None
    assert reader("gqa_paged_attn_roofline_pct")(
        made_up(full_calls=0)) is None
    assert reader("gqa_paged_attn_roofline_pct")(
        made_up(positions=0)) is None
    # ... nor without the bounded call in the program (the hybrid's trace
    # under this cell's sizes)
    assert reader("gqa_paged_attn_roofline_pct")(
        made_up(window_calls=0)) is None
    # the two names are two kernels to every reader: the bounded calls are
    # no part of the full layer's reading, nor the full call of theirs
    both = made_up(full_calls=0)
    assert reader("hybrid_paged_attn_kernel_ms_per_decode")(both) is None
    # another model's sizes (no window_layers): the kernels' names alone do
    # not make these readers speak
    other = made_up()
    other["counts"]["sizes"] = {"layers": 16, "full_layers": 4,
                                "expert_layers": 4}
    assert all(reader(name)(other) is None for name in NEW)


def test_decode_hbm_and_gmm_roofline_on_a_made_up_run():
    run = made_up()
    step = kernel_counts("exaone_decode_step")
    need = step.bytes_needed(sizes(), 300_000, 8192, 4 * 2)   # 2 hit a layer
    assert reader("exaone_decode_hbm_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.014)                     # the median execution
    assert reader("exaone_decode_hbm_pct")(run) < 100
    # 24 calls = 3 executions x 4 layers x 2 products: 8 ms a step; per step
    # and layer 192 rows over 3 steps, 2 experts hit
    per_layer = max(step.gmm_layer_bytes(sizes(), 64, 2) / 819e9,
                    step.gmm_layer_flops(sizes(), 64) / 197e12)
    assert reader("exaone_moe_gmm_roofline_pct")(run) == pytest.approx(
        100 * 4 * per_layer / 0.008)
    for name in ("exaone_decode_hbm_pct", "exaone_moe_gmm_roofline_pct"):
        assert reader(name)(made_up(moe=None)) is None
        assert reader(name)(made_up(moe={"load": [], "hit": [],
                                         "steps": 0})) is None
    assert reader("exaone_moe_gmm_roofline_pct")(
        made_up(gmm=(0.024, 23))) is None
    # the latent family's reader of the same quantity says nothing here
    assert reader("deepseek_moe_gmm_roofline_pct")(run) is None
    assert reader("deepseek_decode_hbm_pct")(run) is None


def test_the_readers_that_were_there_read_this_cell_as_they_are():
    run = made_up()
    assert reader("moe_gmm_kernel_ms_per_decode")(run) == pytest.approx(8.0)
    assert reader("moe_load_max_over_mean")(run) == pytest.approx(
        150 * 4 / 192)
    assert reader("decode_device_ms")(run) == pytest.approx(14.0)
    assert reader("sched_step_ms.serve")(run) == pytest.approx(16.0)
    assert reader("device_idle_pct.serve")(run) == 12.5
    path = os.path.join(DATA, "small_gpt_serve.xplane.pb.gz")
    recorded = {"trace": trace.reduce_file(path),
                "apex_spans": spans.load(path),
                "counts": {"sizes": sizes(), "mapped_positions": 300_000},
                "peaks": PEAKS,
                "cell": types.SimpleNamespace(bench_dir=BENCH)}
    ticks = {name: reader(name)(recorded) for name in SHARED
             if name.startswith("tick_idle_ms.")}
    assert len(ticks) == 6 and all(v is not None and v >= 0
                                   for v in ticks.values())


# -- the manifest and the files ---------------------------------------------------

def test_manifest_holds_the_configuration_the_cell_and_its_readers_by_name():
    m = MANIFEST
    config = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    body = config_file()
    assert body["source"] == config["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/"
        "config.json")
    assert body["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "sliding_windows", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert body["runner"] == "exaone_serve"
    for kind in ("runners/exaone_serve", "reference/" + CONFIG,
                 "kernels/window_attention", "kernels/exaone_decode_step"):
        assert os.path.exists(os.path.join(BENCH, kind + ".py")), kind
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert len(cell["why"]) <= 200 and "8x its share" in cell["why"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)
    by = {e["name"]: e for e in m["per_layer"]}
    assert all(by[name] == {
        "name": name, "unit": by[name]["unit"], "better": by[name]["better"],
        "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]} for name in NEW)
    assert [by[n]["unit"] for n in NEW] == ["ms", "%", "%", "%", "%"]
    assert [by[n]["better"] for n in NEW] == ["lower"] + ["higher"] * 4
    mine = harness.Cell(CELL)
    assert [e["name"] for e in mine.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {e["name"] for e in mine.per_layer} == set(NEW) | SHARED
    # appended: wherever a list names this cell, it names it LAST, and the
    # cells before it are the ones the list had (the manifest's order)
    order = [w["name"] for w in m["workloads"]]
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if CELL in lists:
            assert lists[-1] == CELL and lists.count(CELL) == 1
            rest = [c for c in lists[:-1]]
            assert rest == sorted(rest, key=order.index) or set(rest) <= {
                "bert_large.pretrain_s128", "bert_large.pretrain_s128_dp4"}
    # no prefill runs in the traced span (no resident finishes inside the
    # window): no reader of the prompt programs lists the cell
    assert all(CELL not in e.get("workloads", []) for e in m["per_layer"]
               if e["name"].startswith(("prefill_", "flash_", "hybrid_flash"))
               or e["name"] == "itl_ms_p95")
    assert len(json.dumps(m)) < 64 << 10
    names = [e["name"] for e in m["per_layer"]]
    assert len(set(names)) == len(names)
    for e in m["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           e["name"] + ".py")), e["name"]


def test_configuration_file_holds_the_published_widths_and_its_cut():
    """Every number of the catalog row's ``config`` under the same key,
    except the keys under ``reduced``; no width among those."""
    row = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"K-EXAONE-236B-A23B"' in line] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    body = config_file()
    for published in row:
        assert body["source"] == published["source_url"]
        for key, value in published["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    assert (body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["intermediate_size"], body["moe_intermediate_size"],
            body["num_experts_per_tok"], body["sliding_window"],
            body["rope_parameters"]["rope_theta"]) == (
        6144, 64, 8, 128, 18432, 2048, 8, 128, 1000000)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in body["reduced"])
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"], body["num_nextn_predict_layers"]) == (
        5, 16, 19200, 0)
    assert body["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert body["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert body["sliding_windows"] == [128, 128, 128, 0, 128]
    published = body["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"],
            published["num_nextn_predict_layers"]) == (48, 128, 153600, 1)
    # what the config does not say: one key each, the one convention that
    # program and reference write down (the reference refuses another)
    assumed = body["assumed"]
    assert {k: assumed[k] for k in ("sublayer_norm", "qk_norm", "rope_layers",
                                    "window_counts_self")} == {
        "sublayer_norm": "output", "qk_norm": True, "rope_layers": "sliding",
        "window_counts_self": True}
    assert all(k in assumed["why"] for k in (
        "sublayer_norm", "qk_norm", "rope_layers", "window_counts_self",
        "router_bias", "eos", "max_len"))
    assert "multi-token-prediction" in body["left_out"]
    serving = body["serving"]
    assert (serving["slots"], serving["page_size"], serving["max_len"],
            serving["prefill_buckets"], serving["window"],
            serving["ring_pages"]) == (64, 16, 11392, [2048, 4096, 8192],
                                       128, 9)
    sz = sizes()
    assert (sz["full_layers"], sz["window_layers"], sz["window"],
            sz["heads"], sz["kv_heads"], sz["head_dim"], sz["row_width"],
            sz["router_experts"], sz["experts_held"]) == (
        1, 4, 128, 64, 8, 128, 1024, 128, 16)
    correct = body["correct"]
    assert correct["sample_requests"] == 6
    assert correct["min_tokens_judged"] >= 6 * 200
    assert set(correct["limits"]) == {"logit_gap_max", "logit_gap_mean"}
    assert set(correct["reasons"]) >= set(correct["limits"])


@pytest.mark.parametrize("rehearsal", [False, True])
def test_traffic_is_the_issues_and_every_seed_offers_the_same_work(rehearsal):
    mix = harness.load_json(BENCH, "traffic", MIX + ".json")
    assert (mix["arrivals"], mix["resident"], mix["prompt_tokens"],
            mix["max_new_tokens"], mix["temperatures"], mix["trace_start_s"],
            mix["trace_seconds"]) == (
        {"process": "backlog", "requests": 128}, 64,
        {"dist": "loguniform", "lo": 2048, "hi": 8192},
        {"dist": "loguniform", "lo": 2048, "hi": 3072}, [0.0, 0.8], 4.0, 6.0)
    assert "shared_prefix" not in mix
    if rehearsal:
        mix = harness.rehearsal_view(mix)
    sz = sizes(rehearsal)
    a, b = (traffic.requests(mix, seed, 30.0, sz["vocab"], sz["positions"])
            for seed in (1, 2 ** 31 + 7))
    assert [(len(r.prompt), r.max_new_tokens, r.temperature) for r in a] \
        == [(len(r.prompt), r.max_new_tokens, r.temperature) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(r.due_s == 0.0 for r in a)
    assert all(2 <= t < sz["vocab"] for r in a for t in r.prompt)
    assert all(len(r.prompt) + r.max_new_tokens <= sz["positions"]
               for r in a)
    if not rehearsal:
        first = a[:64]
        assert sum(len(r.prompt) for r in first) == 265_464
        assert min(r.max_new_tokens for r in a) >= 2048
        # every judged token lies more than a window into its stream
        assert min(len(r.prompt) for r in a) >= 2048 > sz["window"]
        # both pools hold the resident wave: 712 pages a slot are never short
        assert max(len(r.prompt) + r.max_new_tokens for r in a) <= 712 * 16


def test_window_positions_count_what_the_sliding_layers_read():
    runner = harness.load_module("runners", "exaone_serve", BENCH)
    arrivals = [traffic.Arrival(0.0, (5,) * n, new, 0.0, 1, None)
                for n, new in ((3, 10), (50, 10), (200, 10), (300, 2))]
    clock = {"submitted": 4, "rid_of": {i: 10 + i for i in range(4)}}
    deliveries = {10: [(1.0, 1), (2.0, 1)], 11: [(1.5, 1)],
                  12: [(9.0, 1)], 13: [(1.0, 1), (1.2, 1)]}
    # at t = 3: request 0 holds 3 + 2 rows and its new one, request 1 is
    # past the window, request 2 has no token yet, request 3 has finished
    assert runner.window_positions(arrivals, clock, deliveries, 3.0, 128) \
        == 6 + 52
    assert runner.window_positions(arrivals, clock, deliveries, 3.0, 8) \
        == 6 + 8
    assert set(runner.CONTROLS) == {"bfloat16_activations", "full_window"}


# -- the pinned tests' substance, by name -------------------------------------------

def test_the_backlog_mixes_are_the_serving_cells_traffic():
    """``test_deepseek_cell.py::test_the_backlog_mixes_are_the_five_serving
    _cells`` without its pin: every backlog mix under ``traffic/`` is some
    serving cell's, and the other way round; this PR's is among them."""
    mixes = sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if harness.load_json(BENCH, "traffic", f).get("arrivals", {}).get(
            "process") == "backlog")
    assert MIX in mixes and {
        "long_prompt_decode", "many_slot_decode", "offline_decode",
        "prompt_backlog", "resident_context_decode"} <= set(mixes)
    assert sorted(w["traffic"] for w in MANIFEST["workloads"]
                  if w["name"] in SERVING) == mixes
    assert CELL in SERVING and len(SERVING) == len(set(SERVING))


def test_what_the_two_pinned_tests_of_test_regions_check_besides():
    """``test_regions.py::test_manifest_gains_exactly_the_twenty_entries_at
    _the_end`` pins PR 38's twenty readers as the manifest's LAST and each
    ``decode_ms.*`` list as it left it, and ``...pr_36_check_besides`` pins
    ``hybrid_paged_attn_kernel_ms_per_decode`` to the hybrid cell alone; this
    PR appends five readers and its cell to six of those lists. Everything
    else they check, with the entries found by name."""
    from test_regions import (BERT, DEEPSEEK, ENTRIES, GPT, HYBRID, NEMOTRON)

    m = MANIFEST
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(next(iter(ENTRIES)))
    assert names[first:first + 20] == list(ENTRIES)     # together, in order
    assert names[first - 1] == "deepseek_moe_gmm_roofline_pct"
    by = {e["name"]: e for e in m["per_layer"]}
    moved = {e["name"]: e["workloads"] for e in m["end_to_end"]
             if "workloads" in e}
    for name, (layer, cells) in ENTRIES.items():
        e = by[name]
        assert {k: v for k, v in e.items() if k != "workloads"} == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": layer,
            "moves": "train_tokens_per_s_per_chip"
            if layer != "Serving device programs" else "serve_tokens_per_s"}
        # as PR 38 left the list, and after it nothing but this PR's cell
        assert e["workloads"][:len(cells)] == cells
        assert e["workloads"][len(cells):] in ([], [CELL])
        assert set(e["workloads"]) <= set(moved[e["moves"]])
    assert sorted(n for n in ENTRIES if CELL in by[n]["workloads"]) == [
        "decode_ms.attention", "decode_ms.experts", "decode_ms.head",
        "decode_ms.mlp", "decode_ms.unscoped"]
    assert all(DEEPSEEK not in e["workloads"] and CELL not in e["workloads"]
               for e in m["per_layer"] if e["name"].startswith("prefill_"))
    gains = {BERT[0]: 7, BERT[1]: 8, GPT[0]: 4, GPT[1]: 8, HYBRID: 10,
             NEMOTRON: 12, DEEPSEEK: 5, CELL: 5}
    for cell, gained in gains.items():
        mine = [e["name"] for e in harness.Cell(cell).per_layer
                if e["name"] in ENTRIES]
        assert len(mine) == gained, cell
    # ... and of test_what_the_pinned_tests_of_pr_36_check_besides, from the
    # line that fails on: the hybrid's seven readers, PR 33's six behind them
    old = ("gdn_decode_kernel_ms_per_decode", "gdn_decode_roofline_pct",
           "gdn_chunk_kernel_ms_per_ktok", "gdn_chunk_roofline_pct",
           "hybrid_decode_hbm_pct", "hybrid_paged_attn_kernel_ms_per_decode",
           "hybrid_flash_kernel_ms_per_prefill")
    pr33 = ("nemotron_decode_hbm_pct", "ssd_decode_kernel_ms_per_decode",
            "ssd_decode_roofline_pct", "moe_gmm_kernel_ms_per_decode",
            "moe_gmm_roofline_pct", "moe_load_max_over_mean")
    at = names.index(old[0])
    assert tuple(names[at:at + 13]) == old + pr33
    for n in old:
        assert by[n]["workloads"] == [HYBRID] + (
            [CELL] if n == "hybrid_paged_attn_kernel_ms_per_decode" else [])
    for n in pr33:
        assert by[n]["workloads"][0] == NEMOTRON
        assert by[n]["workloads"][1:] in ([], [DEEPSEEK], [DEEPSEEK, CELL])
        assert by[n]["layer"] == "Kernels" and by[n]["moves"] == \
            "serve_tokens_per_s"
    assert by["paged_attn_kernel_ms_per_decode"] == {
        "name": "paged_attn_kernel_ms_per_decode", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": GPT}
    nemotron = harness.Cell(NEMOTRON)
    assert [e["name"] for e in nemotron.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    assert len(nemotron.per_layer) == 16 + 12
    assert len(harness.Cell(DEEPSEEK).per_layer) == 4 + 11 + 5


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
    """``test_deepseek_cell.py::test_window_line_of_every_serving_cell`` with
    the serving cells the manifest has, whichever they are: ``backlog_left``
    always; where the window drained the backlog also ``drained_at_s`` and a
    line on standard error that names the traffic file."""
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
        # the window closes with the tick in flight at its end
        assert 0.0 < window["drained_at_s"] <= window["window_s"]
        assert (f"benchmark/traffic/{cell.traffic_name}.json needs more "
                f"than {offered} arrivals.requests") in err
    assert result["correct"] is True and window["compiles_in_window"] == 0
    if workload in EXPERT_CELLS:
        # the program's counters, read before and after the window
        assert window["moe_steps"] == window["steps"] > 0
        hit, held = window["moe_hit_per_step_of_held"]
        assert len(window["moe_rows_per_step"]) == len(hit) in (2, 4)
        assert held == 8 and all(0 < h <= held for h in hit)
        [correct] = [l for l in lines if l.get("stage") == "correct"]
        assert 0.5 < correct["routes_agree"] <= 1.0
    if "resident" in cell.traffic:
        [resident] = [l for l in lines if l.get("stage") == "resident"]
        assert resident["requests"] == window["resident"] == 3
        assert resident["compile_events"] == [
            l for l in lines if l.get("stage") == "warm"][0]["compile_events"]
        [mapped] = [l for l in lines if l.get("stage") == "mapped"]
        assert mapped["mapped_positions"] >= 0
    if workload == CELL:
        [built] = [l for l in lines if l.get("stage") == "built"]
        # a sliding layer's bytes a slot: 3 pages of 4 rows, K and V
        assert built["window_bytes_per_slot_per_layer"] == 2 * 3 * 4 * 32 * 2
        assert 0 <= mapped["window_positions"] <= 3 * 8
        assert window["block_table_uploads"] <= window["steps"]
        assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_served_tokens_altered_where_they_are_staged_are_not_correct(capsys):
    result, lines = rehearse(capsys, CELL, "--option", "break_tokens=1")
    assert result["correct"] is False
    bad = {n["number"] for l in lines if l.get("stage") == "correct"
           for n in l["numbers"] if not n["ok"]}
    assert bad == {"served_logit_gap_max", "served_logit_gap_mean"}


def test_the_full_window_control_is_refused_and_says_what_it_is(capsys):
    result, lines = rehearse(capsys, CELL, "--control", "1", "--option",
                             "control=full_window")
    assert result["correct"] is True
    [control] = [l for l in lines if l.get("stage") == "control"]
    assert control["precision"] == "full_window"
    assert "NO band" in control["what"]
    bad = {n["number"] for n in control["numbers"] if not n["ok"]}
    assert "served_logit_gap_mean" in bad
