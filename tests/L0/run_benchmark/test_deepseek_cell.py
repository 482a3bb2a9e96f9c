"""The cell ``deepseek_v3.resident_context_decode`` (PR 36): its count files
by hand, its readers on traces without the new kernel (nothing, and no raise)
and on made-up runs (the arithmetic), the shared readers on this cell's
counts, its manifest entries and files (found BY NAME: this file pins nothing
as the last entry, only that each new entry stands after every name the
benchmark had before), the sizes its traffic offers, the resident phase's
clock, the run that has to come out not correct, the control's arithmetic,
and what the tests pinned in ``tests/conftest.py`` check besides their pins."""

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
CONFIG = "deepseek_v3"
CELL = CONFIG + ".resident_context_decode"
NEW = ("mla_decode_kernel_ms_per_decode", "mla_decode_roofline_pct",
       "deepseek_decode_hbm_pct", "deepseek_moe_gmm_roofline_pct")
# the readers that were there and read this cell as they are
SHARED = {"sched_step_ms.serve", "decode_device_ms", "device_idle_pct.serve",
          "tick_idle_ms.admit", "tick_idle_ms.build_inputs",
          "tick_idle_ms.dispatch", "tick_idle_ms.accept",
          "tick_idle_ms.commit_flush", "tick_idle_ms.unspanned",
          "moe_gmm_kernel_ms_per_decode", "moe_load_max_over_mean"}
PEAKS = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
NEMOTRON = "nemotron3_super_120b_a12b.many_slot_decode"
SERVING = ["gpt2_medium.offline_decode", "gpt2_medium.prompt_backlog",
           "olmo_hybrid_7b.long_prompt_decode", NEMOTRON, CELL]
# every name the benchmark had at PR 34
CONFIGS_BEFORE = ["bert_large", "gpt2_medium", "olmo_hybrid_7b",
                  "nemotron3_super_120b_a12b"]
CELLS_BEFORE = ["bert_large.pretrain_s128", "gpt2_medium.offline_decode",
                "gpt2_medium.prompt_backlog", "bert_large.pretrain_s128_dp4",
                "olmo_hybrid_7b.long_prompt_decode", NEMOTRON]
METRICS_BEFORE = 32

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

def test_mla_counts_by_hand():
    mla, sz = kernel_counts("mla"), sizes()
    # a mapped position is one row of 512 + 64 numbers, read once for 128
    # heads: a score against all of it, a value update with its first 512
    assert mla.decode_flops(sz, 1) == 128 * 2 * (576 + 512) == 278_528
    assert mla.decode_bytes(sz, 1) == 1_152
    # the chip's ridge: 242 operations a byte against 197 T / 819 G = 240.5
    ridge = PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"]
    assert 240 < ridge < 241 < mla.decode_flops(sz, 1) / mla.decode_bytes(
        sz, 1) < 242
    assert mla.decode_flops(sz, 150_000) == 150_000 * 278_528


def test_decode_step_counts_by_hand_and_the_files_arithmetic():
    step, sz = kernel_counts("deepseek_decode_step"), sizes()
    said = config_file()["deployment"]["parameters"]
    attention = (7168 * (1536 + 576) + 1536 * 128 * 192 + 512 * 128 * 256
                 + 128 * 128 * 7168)
    assert step._attention(sz) == (attention, 7168 + 1536 + 512)
    assert sum(step._attention(sz)) == said["attention_layer"] == 187_114_496
    assert step._dense_mlp(sz) == (3 * 7168 * 18432, 7168)
    assert sum(step._dense_mlp(sz)) == said["dense_mlp"] == 396_368_896
    outside = 7168 * 256 + 3 * 7168 * 2048
    assert step._experts_outside(sz) == (outside, 7168 + 256)
    assert sum(step._experts_outside(sz)) == said[
        "expert_layer_outside_attention_and_experts"] == 45_882_624
    assert step.one_expert(sz) == said["one_expert"] == 3 * 7168 * 2048
    assert said["embedding_and_head"] == 2 * 16160 * 7168
    whole = (5 * said["attention_layer"] + said["dense_mlp"] + 4 * (
        said["expert_layer_outside_attention_and_experts"]
        + 16 * said["one_expert"]) + said["embedding_and_head"] + 7168)
    assert whole == 4_565_721_088               # 9.13 GB in bfloat16
    # a step reads the head, not the embedding; only the experts hit
    matrices = 5 * attention + 3 * 7168 * 18432 + 4 * outside + 7168 * 16160
    small = 5 * 9216 + 7168 + 4 * 7424 + 7168
    assert step.weight_bytes(sz, 55) == 2 * (
        matrices + 55 * 44_040_192) + 4 * small
    assert step.weight_bytes(sz, 64) - step.weight_bytes(sz, 0) == (
        2 * 64 * 44_040_192)                    # 5.6 GB: the 64 held, all hit
    # 576 numbers a position a layer, whatever the row is padded to
    assert step.latent_bytes(sz, 150_000) == 5 * 1152 * 150_000
    assert step.bytes_needed(sz, 150_000, 55) == (
        step.weight_bytes(sz, 55) + 864_000_000)
    # the SwiGLU expert's two products: the fused gate and up, and down
    moe = kernel_counts("moe")
    assert step.gmm_layer_bytes(sz, 32, 14) == (
        moe.gmm_bytes(32, 14, 7168, 4096) + moe.gmm_bytes(32, 14, 2048, 7168))
    assert step.gmm_layer_flops(sz, 32) == 2 * 32 * 3 * 7168 * 2048
    assert step.gmm_layer_bytes(sz, 0, 0) == 0


# -- the readers -----------------------------------------------------------------

@pytest.mark.parametrize("recorded", ["small_gpt_serve", "small_hybrid_serve"])
def test_new_readers_give_nothing_on_traces_without_the_new_kernel(recorded):
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
        got = {name: reader(name)(run) for name in NEW}
        assert all(v is None for v in got.values()), got


def made_up(mla_calls=15, gmm=(0.024, 24), moe="default", positions=150_000):
    """A run of three decode executions at the full sizes' layer counts (5
    layers of latent attention, 4 expert layers), four held experts
    counted."""
    name = ("%apex_mla_decode_fwd.9 = f32[64,128,512]{2,1,0} custom-call("
            "s32[64,400] %a, s32[64] %b)")

    def kernel_time(match):
        return (0.009 * mla_calls / 15, mla_calls) \
            if match(name) and mla_calls else (0.0, 0)

    if moe == "default":
        moe = {"load": [[30, 10, 0, 0]] * 4, "hit": [6] * 4, "steps": 3}
    return {"trace": types.SimpleNamespace(
                kernel_time=kernel_time, window=(0.0, 2.0),
                idle_pct=lambda: 12.5,
                program_times=lambda p: [0.02, 0.03, 0.04]
                if p == "jit_decode" else []),
            "apex_spans": [], "moe_gmm_calls": {
                "jit_decode": gmm, "jit_prefill": (0.5, 16)},
            "counts": {"sizes": sizes(), "mapped_positions": positions,
                       "moe": moe,
                       "step_walls": [(0.0, 0.02), (1.0, 0.03), (2.0, 0.04)]},
            "peaks": PEAKS, "cell": types.SimpleNamespace(bench_dir=BENCH)}


def test_mla_readers_on_a_made_up_run():
    run = made_up()
    # 15 calls = 3 executions of 5 layers: 9 ms over 3
    assert reader("mla_decode_kernel_ms_per_decode")(run) == pytest.approx(3.0)
    mla = kernel_counts("mla")
    need = 5 * max(mla.decode_flops(sizes(), 150_000) / 197e12,
                   mla.decode_bytes(sizes(), 150_000) / 819e9)
    assert reader("mla_decode_roofline_pct")(run) == pytest.approx(
        100 * need / 0.003)
    # at the ridge the operations are the (slightly) larger bound
    assert need == pytest.approx(5 * 150_000 * 278_528 / 197e12)
    # an execution cut by the session, no call at all, or no position mapped
    for cut in (made_up(mla_calls=14), made_up(mla_calls=0)):
        assert reader("mla_decode_kernel_ms_per_decode")(cut) is None
        assert reader("mla_decode_roofline_pct")(cut) is None
        assert reader("deepseek_decode_hbm_pct")(cut) is None
    assert reader("mla_decode_roofline_pct")(made_up(positions=0)) is None
    # another model's sizes (no latent_width): the kernel's name alone does
    # not make these readers speak
    other = made_up()
    other["counts"]["sizes"] = {"layers": 5, "expert_layers": 4}
    assert all(reader(name)(other) is None for name in NEW)


def test_decode_hbm_and_gmm_roofline_on_a_made_up_run():
    run = made_up()
    step = kernel_counts("deepseek_decode_step")
    need = step.bytes_needed(sizes(), 150_000, 4 * 2)   # 2 hit a layer a step
    assert reader("deepseek_decode_hbm_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.03)                      # the median execution
    # 24 calls = 3 executions x 4 layers x 2 products: 8 ms a step; per step
    # and layer 40 rows over 3 steps, 2 experts hit
    per_layer = max(step.gmm_layer_bytes(sizes(), 40 / 3, 2) / 819e9,
                    step.gmm_layer_flops(sizes(), 40 / 3) / 197e12)
    assert reader("deepseek_moe_gmm_roofline_pct")(run) == pytest.approx(
        100 * 4 * per_layer / 0.008)
    for name in ("deepseek_decode_hbm_pct", "deepseek_moe_gmm_roofline_pct"):
        assert reader(name)(made_up(moe=None)) is None
        assert reader(name)(made_up(moe={"load": [], "hit": [],
                                         "steps": 0})) is None
    assert reader("deepseek_moe_gmm_roofline_pct")(
        made_up(gmm=(0.024, 23))) is None


def test_the_readers_that_were_there_read_this_cell_as_they_are():
    """On this configuration's counts, unedited: the expert product's time
    (two calls an expert layer, inside ``jit_decode`` only), the load's
    spread, the step's device and wall time, the idle share; and on a
    recorded trace with this cell's counts the six ``tick_idle_ms.*``."""
    run = made_up()
    assert reader("moe_gmm_kernel_ms_per_decode")(run) == pytest.approx(8.0)
    assert reader("moe_load_max_over_mean")(run) == pytest.approx(3.0)
    assert reader("decode_device_ms")(run) == pytest.approx(30.0)
    assert reader("sched_step_ms.serve")(run) == pytest.approx(30.0)
    assert reader("device_idle_pct.serve")(run) == 12.5
    # three calls an expert layer (gate and up apart) would not be read:
    # the model keeps gate and up fused, two calls a layer
    assert reader("moe_gmm_kernel_ms_per_decode")(
        made_up(gmm=(0.036, 36))) is None
    path = os.path.join(DATA, "small_gpt_serve.xplane.pb.gz")
    recorded = {"trace": trace.reduce_file(path),
                "apex_spans": spans.load(path),
                "counts": {"sizes": sizes(), "mapped_positions": 150_000},
                "peaks": PEAKS,
                "cell": types.SimpleNamespace(bench_dir=BENCH)}
    ticks = {name: reader(name)(recorded) for name in SHARED
             if name.startswith("tick_idle_ms.")}
    assert len(ticks) == 6 and all(v is not None and v >= 0
                                   for v in ticks.values())


# -- the manifest and the files ---------------------------------------------------

def test_manifest_gains_the_cell_after_every_entry_that_was_there():
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
    # one four-chip cell of seven: the quarter rule still allows it
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    at = {e["name"]: i for i, e in enumerate(m["per_layer"])}
    new = [m["per_layer"][at[name]] for name in NEW]
    assert all(e["workloads"] == [CELL] and e["moves"] ==
               "serve_tokens_per_s" and e["layer"] == "Kernels"
               and e["source"] == "device_trace" for e in new)
    assert all(at[name] >= METRICS_BEFORE for name in NEW)
    assert [e["name"] for e in m["per_layer"][:METRICS_BEFORE]][-6:] == [
        "nemotron_decode_hbm_pct", "ssd_decode_kernel_ms_per_decode",
        "ssd_decode_roofline_pct", "moe_gmm_kernel_ms_per_decode",
        "moe_gmm_roofline_pct", "moe_load_max_over_mean"]
    cell = harness.Cell(CELL)
    assert [e["name"] for e in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {e["name"] for e in cell.per_layer} - set(NEW) == SHARED
    # wherever the cell was appended it stands after the cells that were
    # there, which keep their order
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if CELL in lists:
            assert lists.index(CELL) == len(lists) - 1 or all(
                c not in CELLS_BEFORE for c in lists[lists.index(CELL):])
            before = [c for c in lists if c in CELLS_BEFORE]
            assert before == sorted(before, key=CELLS_BEFORE.index)
    # no prefill runs in this cell's traced span (the first request to
    # finish needs 768 ticks): its reader would find nothing to read
    assert CELL not in m["per_layer"][at["prefill_device_ms_per_ktok"]][
        "workloads"]
    assert len(json.dumps(m)) < 64 << 10


def test_what_the_pinned_tests_of_pr_33_check_besides():
    """``test_nemotron_cell.py``'s manifest tests pin PR 33's entries as the
    last; what else they check, for the entries as they stand now (found by
    name): the Nemotron configuration, cell and six readers word for word,
    the olmo entries before them, PR 25's metric entry before those."""
    m = harness.load_json(REPO, "BENCHMARK.json")
    config = {c["name"]: c for c in m["configs"]}[
        "nemotron3_super_120b_a12b"]
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    body = harness.load_json(REPO, config["file"])
    assert body["source"] == config["source"] and body["runner"] == \
        "nemotron_serve"
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells[NEMOTRON] == {**cells[NEMOTRON],
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
    assert all(by[n]["workloads"] == ["olmo_hybrid_7b.long_prompt_decode"]
               for n in old)
    for n in pr33:
        assert by[n]["workloads"][0] == NEMOTRON
        assert by[n]["workloads"][1:] in ([], [CELL])
        assert by[n]["layer"] == "Kernels" and by[n]["moves"] == \
            "serve_tokens_per_s"
    assert m["per_layer"][first - 1] == {
        "name": "paged_attn_kernel_ms_per_decode", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2_medium.offline_decode",
                      "gpt2_medium.prompt_backlog"]}
    nemotron = harness.Cell(NEMOTRON)
    assert [e["name"] for e in nemotron.end_to_end] == [
        "serve_tokens_per_s", "setup_s"]
    assert len(nemotron.per_layer) == 16


def test_the_backlog_mixes_are_the_five_serving_cells():
    mixes = sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if harness.load_json(BENCH, "traffic", f).get("arrivals", {}).get(
            "process") == "backlog")
    assert mixes == ["long_prompt_decode", "many_slot_decode",
                     "offline_decode", "prompt_backlog",
                     "resident_context_decode"]
    m = harness.load_json(REPO, "BENCHMARK.json")
    assert sorted(w["traffic"] for w in m["workloads"]
                  if w["name"] in SERVING) == mixes


def test_configuration_file_holds_the_published_widths_and_its_cut():
    published = {
        "hidden_size": 7168, "num_attention_heads": 128,
        "num_key_value_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 8,
        "topk_group": 4, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "moe_layer_freq": 1, "ep_size": 1,
        "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "max_position_embeddings": 163840, "attention_bias": False,
        "tie_word_embeddings": False, "model_type": "deepseek_v3",
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"}}
    config = config_file()
    assert {k: config[k] for k in published} == published
    cut = {"num_hidden_layers": (61, 5), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 16), "vocab_size": (129280, 16160),
           "num_nextn_predict_layers": (1, 0)}
    assert set(cut) == set(config["reduced"])
    for key, (was, now) in cut.items():
        assert (config["published"][key], config[key]) == (was, now)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["expert_offset"] == 0
    assert set(config) >= {"assumed", "deployment", "left_out", "serving",
                           "correct", "rehearsal"}
    assert config["deployment"]["chips_per_layer"] == 16
    assert set(config["assumed"]) == {
        "block", "attention", "rotary", "experts", "router_bias", "weights",
        "eos", "max_len", "dropout", "activations"}
    serving = config["serving"]
    assert (serving["slots"], serving["page_size"], serving["max_len"],
            serving["row_width"], serving["prefill_buckets"]) == (
        64, 16, 6400, 640, [1024, 2048, 4096])
    sz = sizes()
    assert (sz["layers"], sz["dense_layers"], sz["expert_layers"]) == (
        5, 1, 4)
    assert (sz["router_experts"], sz["experts_held"], sz["vocab"],
            sz["latent_width"]) == (256, 16, 16160, 576)
    limits = config["correct"]["limits"]
    assert set(limits) == {"logit_gap_max", "logit_gap_mean"}
    assert set(config["correct"]["reasons"]) >= set(limits)
    # the program's config object from these keys: the widths and the row
    from apex_tpu.models.deepseek import deepseek_v3

    runner = harness.load_module("runners", "deepseek_serve", BENCH)
    cfg = runner.model_config(config, sz)
    whole = deepseek_v3()
    assert {f: getattr(cfg, f) for f in (
        "hidden_size", "num_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "ffn_size",
        "moe_ffn_size", "num_experts", "experts_per_token", "n_group",
        "topk_group", "routed_scaling_factor", "rms_norm_eps", "rope_theta",
        "rope_factor", "rope_original_positions", "max_position_embeddings")
            } == {f: getattr(whole, f) for f in (
        "hidden_size", "num_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "ffn_size",
        "moe_ffn_size", "num_experts", "experts_per_token", "n_group",
        "topk_group", "routed_scaling_factor", "rms_norm_eps", "rope_theta",
        "rope_factor", "rope_original_positions", "max_position_embeddings")}
    assert (cfg.num_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.vocab_size, cfg.kv_row_width) == (5, 1, 16, 16160, 640)


@pytest.mark.parametrize("rehearsal", [False, True])
def test_traffic_is_the_issues_and_every_seed_offers_the_same_work(rehearsal):
    from benchmark import traffic
    mix = harness.load_json(BENCH, "traffic", "resident_context_decode.json")
    assert mix["arrivals"] == {"process": "backlog", "requests": 192}
    assert mix["resident"] == 64
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 1024,
                                    "hi": 4096}
    assert mix["max_new_tokens"] == {"dist": "loguniform", "lo": 768,
                                     "hi": 2048}
    assert "shared_prefix" not in mix and mix["temperatures"] == [0.0, 0.8]
    assert (mix["trace_start_s"], mix["trace_seconds"]) == (4.0, 6.0)
    max_len, vocab = 6400, 16160
    if rehearsal:
        mix, max_len, vocab = harness.rehearsal_view(mix), 256, 512
    a, b, c = (traffic.requests(mix, seed, 30.0, vocab, max_len)
               for seed in (1, 2, 2 ** 31 + 7))
    work = lambda rs: [(len(r.prompt), r.max_new_tokens, r.temperature)
                       for r in rs]
    assert work(a) == work(b) == work(c)
    assert len(a) == mix["arrivals"]["requests"]
    assert all(r.due_s == 0.0 for r in a)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert all(2 <= t < vocab for r in a[:20] for t in r.prompt)
    assert all(len(r.prompt) + r.max_new_tokens <= max_len for r in a)
    assert {r.prompt for r in a}.isdisjoint(r.prompt for r in b)
    if not rehearsal:
        resident = a[:mix["resident"]]
        # the resident wave hits both buckets a prompt of more than 1024
        # tokens can (the 1024 bucket takes a prompt of exactly 1024), and
        # holds what the issue says
        assert sorted({min(b for b in (1024, 2048, 4096)
                           if b >= len(r.prompt)) for r in resident}) == [
            2048, 4096]
        assert 130_000 < sum(len(r.prompt) for r in resident) < 160_000
        assert 2_000 < sum(len(r.prompt) for r in a) / len(a) < 2_400
        assert min(r.max_new_tokens for r in a) >= 768


def test_the_resident_requests_join_the_clock_and_set_up_tokens_do_not_count():
    runner = harness.load_module("runners", "deepseek_serve", BENCH)
    clock = {"t0": 100.0, "t1": 130.0, "end": 130.0, "submitted": 2,
             "rid_of": {0: 12, 1: 13}, "submitted_at": {0: 100.1, 1: 100.2},
             "step_walls": [], "queue_depth": [2], "backlog_left": 0,
             "drained_at_s": None}
    whole = runner.with_resident(clock, [10, 11])
    assert whole["rid_of"] == {0: 10, 1: 11, 2: 12, 3: 13}
    assert whole["submitted_at"] == {0: 100.0, 1: 100.0, 2: 100.1, 3: 100.2}
    assert whole["submitted"] == 4 and whole["t0"] == 100.0
    assert clock["rid_of"] == {0: 12, 1: 13}        # the original stands
    # gpt_serve.measures over that clock: a token stamped before t0 is not
    # the window's; mapped_positions (all deliveries) still holds it
    from benchmark import traffic
    gpt = harness.load_module("runners", "gpt_serve", BENCH)
    arrivals = [traffic.Arrival(0.0, (5,) * 10, 6, 0.0, i, None)
                for i in range(4)]
    deliveries = {10: [(99.0, 1), (99.5, 1), (101.0, 1), (102.0, 1)],
                  11: [(99.2, 1), (101.0, 1)], 12: [(103.0, 1)]}
    in_window = {rid: [(t, k) for t, k in got if t >= 100.0]
                 for rid, got in deliveries.items()}
    sched = types.SimpleNamespace(outcomes={})
    ctx = types.SimpleNamespace(seconds=30.0, t_start=0.0)
    mix = {"arrivals": {"process": "backlog"}}
    values, counts, failed, finished = gpt.measures(
        ctx, arrivals, whole, in_window, sched, mix)
    assert counts["tokens_delivered"] == 4 and failed == 0
    assert values["serve_tokens_per_s"] == pytest.approx(4 / 30.0)
    assert counts["requests_attempted"] == 3        # request 3 still queued
    assert gpt.mapped_positions(arrivals, whole, deliveries, 101.5) == (
        10 + 3) + (10 + 2)


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
    """``test_nemotron_cell.py::test_window_line_of_every_serving_cell`` with
    the serving cells the benchmark has now: ``backlog_left`` always; where
    the window drained the backlog also ``drained_at_s`` and a line on
    standard error that names the traffic file."""
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
    if workload in (CELL, NEMOTRON):
        # the program's counters, read before and after the window
        assert window["moe_steps"] == window["steps"] > 0
        assert len(window["moe_rows_per_step"]) == 2
        hit, held = window["moe_hit_per_step_of_held"]
        assert held == 8 and all(0 < h <= held for h in hit)
        [correct] = [l for l in lines if l.get("stage") == "correct"]
        assert 0.5 < correct["routes_agree"] <= 1.0
    if workload == CELL:
        [resident] = [l for l in lines if l.get("stage") == "resident"]
        assert resident["requests"] == window["resident"] == 3
        assert resident["compile_events"] == [
            l for l in lines if l.get("stage") == "warm"][0]["compile_events"]
        # the positions held at the middle of the window include what the
        # resident requests were served in set-up
        [mapped] = [l for l in lines if l.get("stage") == "mapped"]
        assert mapped["mapped_positions"] >= 0


def test_served_tokens_altered_where_they_are_staged_are_not_correct(capsys):
    result, lines = rehearse(capsys, CELL, "--option", "break_tokens=1")
    assert result["correct"] is False
    bad = {n["number"] for l in lines if l.get("stage") == "correct"
           for n in l["numbers"] if not n["ok"]}
    assert bad == {"served_logit_gap_max", "served_logit_gap_mean"}


def test_the_control_keeps_activations_latents_and_attention_in_bfloat16():
    """The control's arithmetic: the reference with one bfloat16 term into
    every product and its latents and attention in bfloat16 reads further
    from the float32 reference than rounding, at the rehearsal sizes, on the
    same weights, and is not its attention's part alone (which of the two
    reads further at hidden 64 is a matter of which routes flip)."""
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
        part = ref.logits_at(params, sz, ids, at, "bfloat16_attention")
        low = ref.logits_at(params, sz, ids, at, "bfloat16_activations")
    assert float(jnp.abs(sound - again).max()) == 0.0
    assert float(jnp.abs(sound - part).max()) > 1e-3
    assert float(jnp.abs(sound - low).max()) > 1e-3
    assert float(jnp.abs(part - low).max()) > 1e-3
    with pytest.raises(ValueError):
        ref.logits_at(params, sz, ids, at, "float16")
    # the seeded bias changes choices: it is not zero, and small
    bias = np.asarray(params["moe"]["router_bias"])
    assert bias.dtype == np.float32 and 0.005 < bias.std() < 0.02
