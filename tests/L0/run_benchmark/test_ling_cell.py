"""The cell ``ling3_flash_vl.many_stream_reasoning`` (PR 42): the cut's
parameter count term by term, the count files by hand, its readers on traces
without the new kernels (nothing, and no raise) and on made-up runs (the
arithmetic), the readers that were there on this cell's counts, its manifest
entries and files (found BY NAME: this file pins nothing as the last entry of
a list and no list's length), the sizes its traffic offers, the rehearsal of
the cell with both controls refused and altered tokens not ``correct``, and
what the tests pinned in ``tests/conftest.py`` by this PR check besides
their pins."""

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
CONFIG = "ling3_flash_vl"
MIX = "many_stream_reasoning"
CELL = CONFIG + "." + MIX
EXAONE = "k_exaone_236b_a23b.long_context_reasoning"
NEW = ("kda_decode_kernel_ms_per_decode", "kda_decode_roofline_pct",
       "ling_moe_gmm_roofline_pct", "ling_decode_hbm_pct")
# the readers that were there and read this cell as they are
SHARED = {"sched_step_ms.serve", "decode_device_ms", "device_idle_pct.serve",
          "tick_idle_ms.admit", "tick_idle_ms.build_inputs",
          "tick_idle_ms.dispatch", "tick_idle_ms.accept",
          "tick_idle_ms.commit_flush", "tick_idle_ms.unspanned",
          "decode_ms.attention", "decode_ms.mixer", "decode_ms.mlp",
          "decode_ms.experts", "decode_ms.head", "decode_ms.unscoped",
          "moe_gmm_kernel_ms_per_decode", "moe_load_max_over_mean",
          "mla_decode_kernel_ms_per_decode", "mla_decode_roofline_pct"}
PEAKS = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
MANIFEST = harness.load_json(REPO, "BENCHMARK.json")
SERVING = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1
           and harness.Cell(w["name"]).traffic["kind"] == "requests"]

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

def test_the_cut_holds_2_865_905_664_matrix_parameters_term_by_term():
    """ISSUE 42's arithmetic against ``kernels/ling_decode_step.py`` and the
    configuration file's ``deployment.parameters``; the whole model by the
    same terms agrees with its name (125B-A5.5B)."""
    step, sz = kernel_counts("ling_decode_step"), sizes()
    said = config_file()["deployment"]["parameters"]
    assert step.kda_layer(sz) == (
        2560 * (3 * 4096 + 4096 + 4096 + 32) + 4096 * 2560, 4 * 12288,
        2560 + 32 + 4096 + 128)
    assert step.kda_layer(sz)[0] == said["kda_layer"] == 62_996_480
    assert step.mla_layer(sz) == (
        2560 * (32 * 192 + 576 + 32) + 512 * 32 * 256 + 4096 * 2560,
        2560 + 512)
    assert step.mla_layer(sz)[0] == said["mla_layer"] == 31_965_184
    assert step.dense_mlp(sz) == said["dense_mlp"] == 47_185_920
    assert step.shared_expert(sz) == said["shared_expert"] == 5_898_240
    assert step.router(sz) == said["router"] == 1_310_720
    assert step.one_expert(sz) == said["one_expert"] == 5_898_240
    assert step.vocabulary(sz) == said["embedding_and_head"] \
        == 2 * 19648 * 2560
    assert step.matrix_parameters(sz) == said["matrix_sum"] \
        == 2_865_905_664 == (
            6 * 62_996_480 + 31_965_184 + 47_185_920
            + 6 * (5_898_240 + 1_310_720 + 64 * 5_898_240)
            + 2 * 19648 * 2560)
    assert round(2 * step.matrix_parameters(sz) / 1e9, 2) == 5.73
    assert config_file()["deployment"]["chips_per_layer"] == 8
    # the published model by the same terms
    whole = {**sz, "kda_layers": 35, "mla_layers": 7, "dense_layers": 2,
             "expert_layers": 40, "experts_held": 512, "vocab": 157184}
    total = step.matrix_parameters(whole)
    assert round(total / 1e9, 1) == 124.4
    # a token's share, embedding AND head counted as the family's figure does
    active = total - 40 * (512 - 8) * step.one_expert(sz)
    assert round(active / 1e9, 2) == 5.50
    # a gate per head in the KDA layers would give the other figure
    assert round((active - 35 * 2560 * (4096 - 32)) / 1e9, 2) == 5.14


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
    step = kernel_counts("ling_decode_step")
    small = 6 * step.kda_layer(sz)[2] + step.mla_layer(sz)[1] \
        + 7 * 2560 + 6 * 512 + 2560
    assert by_dtype == {"bfloat16": 2_865_905_664 + 6 * 4 * 12288,
                        "float32": small}
    cfg = harness.load_module("runners", "ling_serve",
                              BENCH).model_config(config_file(), sz)
    from apex_tpu.models import bailing_hybrid

    mine = jax.eval_shape(lambda k: bailing_hybrid.init(k, cfg),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, mine) \
        == jax.tree.map(lambda a: a.shape, tree)
    assert (cfg.kda_layers, cfg.kv_layers, cfg.moe_layers, cfg.num_heads,
            cfg.kv_row_width) == (6, 1, 6, 32, 640)
    assert cfg.layer_types == ("kda",) * 4 + ("mla",) + ("kda",) * 2
    assert cfg.state_shapes(256) == ((6, 256, 32, 128, 128),
                                     (6, 256, 3, 12288))
    # 2 MiB a slot a layer; 12.6 MB a slot here, 73 MB at the published depth
    assert 4 * 32 * 128 * 128 == 2 << 20
    assert round(cfg.state_bytes_per_slot() / 1e6, 1) == 13.5
    assert round(6 * (2 << 20) / 1e6, 1) == 12.6
    assert round(35 * (2 << 20) / 1e6) == 73


def test_count_files_by_hand():
    sz = sizes()
    kda, step, mla = (kernel_counts(k) for k in ("kda", "ling_decode_step",
                                                 "mla"))
    # a slot's state in one layer read and written: 4 MiB, and 6 rows of 128
    # a head beside it
    assert kda.decode_bytes(sz, 1) == 4 * 32 * (2 * 128 * 128 + 6 * 128)
    assert kda.decode_bytes(sz, 256) == 256 * (4 * 2 ** 20 + 98_304)
    # the chunk walk: three products with the state and one inside the chunk
    assert kda.chunk_flops(sz, 64) == 32 * 64 * (6 * 128 * 128 + 2 * 64 * 128)
    assert kda.chunk_bytes(sz, 4096, calls=1) == 4 * 32 * (
        4096 * (5 * 128 + 64) + 64 * 128 + 128 * 128)
    assert kda.chunk_bytes(sz, 4096, calls=2) - kda.chunk_bytes(
        sz, 4096, calls=1) == 4 * 32 * 128 * 128
    # the MLA kernel at 32 heads: 69,632 operations against 1,152 bytes a
    # position, 60 an operation a byte against the chip's 240
    readers_sz = {**sz, "layers": sz["mla_layers"]}
    assert mla.decode_flops(readers_sz, 1) == 69_632
    assert mla.decode_bytes(readers_sz, 1) == 1_152
    assert round(69_632 / 1_152) == 60
    # weights of a step with every held expert hit: the whole share less the
    # embedding (looked up by row), the taps and the float32 leaves
    all_hit = step.weight_bytes(sz, 6 * 64)
    small = 6 * step.kda_layer(sz)[2] + step.mla_layer(sz)[1] \
        + 7 * 2560 + 6 * 512 + 2560
    assert all_hit == 2 * (2_865_905_664 - 19648 * 2560 + 6 * 4 * 12288) \
        + 4 * small
    assert step.weight_bytes(sz, 0) == all_hit - 2 * 384 * 5_898_240
    assert step.state_bytes(sz, 256) == 6 * 256 * (2 << 20) == 3_221_225_472
    assert step.latent_bytes(sz, 1000) == 1_152_000
    assert step.bytes_needed(sz, 700_000, 6 * 62.8, 256) == pytest.approx(
        step.weight_bytes(sz, 376.8) + 2 * 3_221_225_472 + 1_152 * 700_000)
    # ISSUE 42's reckoning of a step: 12.7-12.9 GB, 15.5-15.8 ms at 819 GB/s
    for positions, ms in ((256 * 2400, 15.5), (256 * 3100, 15.8)):
        assert step.bytes_needed(sz, positions, 6 * 62.8, 256) / 819e9 \
            == pytest.approx(ms * 1e-3, rel=0.02)
    moe = kernel_counts("moe")
    count = kernel_counts("deepseek_decode_step")
    assert count.gmm_layer_bytes(sz, 256, 62.8) == pytest.approx(
        moe.gmm_bytes(256, 62.8, 2560, 1536)
        + moe.gmm_bytes(256, 62.8, 768, 2560))
    assert count.gmm_layer_flops(sz, 256) == 2 * 256 * 3 * 2560 * 768


# -- the readers ------------------------------------------------------------------

@pytest.mark.parametrize("recorded", ["small_gpt_serve", "small_hybrid_serve"])
def test_new_readers_give_nothing_on_traces_without_the_new_kernels(recorded):
    """The parent's programs (GPT, the Gated DeltaNet hybrid), with their own
    counts, with this cell's and with none: no reader raises, every one
    returns ``None`` (``apex_gdn_decode_fwd`` is not ``apex_kda_decode_fwd``:
    the scalar rule's calls are no reading of the per-channel kernel)."""
    path = os.path.join(DATA, recorded + ".xplane.pb.gz")
    cell = types.SimpleNamespace(bench_dir=BENCH)
    moe = {"load": [[3, 1], [2, 2]], "hit": [2, 2], "steps": 2}
    mine = {**sizes(True), "layers": 1}
    for counts in ({"sizes": {"layers": 24}},
                   {"sizes": {"layers": 2, "hidden": 64}, "slots": 3,
                    "mapped_positions": 40},
                   {"sizes": {"layers": 16, "full_layers": 4, "heads": 30,
                              "linear_layers": 3},
                    "mapped_positions": 40},
                   {"sizes": mine, "mapped_positions": 40, "moe": moe},
                   {"sizes": mine, "mapped_positions": 40, "moe": None}, {}):
        run = {"trace": trace.reduce_file(path),
               "apex_spans": spans.load(path), "counts": counts,
               "peaks": PEAKS, "cell": cell}
        got = {name: reader(name)(run) for name in NEW}
        assert all(v is None for v in got.values()), got


def made_up(kda_calls=18, mla_calls=3, gmm=(0.018, 36), moe="default",
            positions=700_000, slots=256):
    """A run of three decode executions at the full sizes' layer counts (six
    KDA calls and one MLA call a step, 6 expert layers), four held experts
    counted."""
    names = {"kda": ("%apex_kda_decode_fwd.6 = (f32[256,4,8,128]{3,2,1,0}, "
                     "f32[6,256,32,128,128]{4,3,2,1,0}) custom-call("
                     "s32[1] %a, s32[256] %b)", 0.036, kda_calls),
             "mla": ("%apex_mla_decode_fwd.1 = f32[256,32,512]{2,1,0} "
                     "custom-call(s32[256,512] %a, s32[256] %b)", 0.009,
                     mla_calls)}

    def kernel_time(match):
        for name, seconds, calls in names.values():
            if match(name) and calls:
                return seconds, calls
        return 0.0, 0

    if moe == "default":
        moe = {"load": [[600, 168, 0, 0]] * 6, "hit": [6] * 6, "steps": 3}
    execs = [spans.Span("exec", 0.1 * i, 0.1 * i + 0.03,
                        {"kind": "decode", "state_slots": slots}, -1)
             for i in range(3)] if slots else []
    return {"trace": types.SimpleNamespace(
                kernel_time=kernel_time, window=(0.0, 2.0),
                idle_pct=lambda: 12.5,
                program_times=lambda p: [0.028, 0.030, 0.032]
                if p == "jit_decode" else []),
            "apex_spans": execs, "moe_gmm_calls": {
                "jit_decode": gmm, "jit_prefill": (0.5, 24)},
            "counts": {"sizes": {**sizes(), "layers": 1},
                       "mapped_positions": positions, "moe": moe,
                       "step_walls": [(0.0, 0.033), (1.0, 0.034),
                                      (2.0, 0.035)]},
            "peaks": PEAKS, "cell": types.SimpleNamespace(bench_dir=BENCH)}


def test_kda_readers_on_a_made_up_run():
    run = made_up()
    # 18 calls = 3 executions of 6 KDA layers: 36 ms over 3
    assert reader("kda_decode_kernel_ms_per_decode")(run) \
        == pytest.approx(12.0)
    need = 6 * kernel_counts("kda").decode_bytes(sizes(), 256)
    assert reader("kda_decode_roofline_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.012)
    assert 0 < reader("kda_decode_roofline_pct")(run) < 100
    # an execution cut by the session, no call at all, no exec span that
    # says how many slots were stepped
    for cut in (made_up(kda_calls=17), made_up(kda_calls=0)):
        assert all(reader(n)(cut) is None for n in (
            "kda_decode_kernel_ms_per_decode", "kda_decode_roofline_pct",
            "ling_decode_hbm_pct"))
    silent = made_up(slots=0)
    assert reader("kda_decode_kernel_ms_per_decode")(silent) \
        == pytest.approx(12.0)
    assert reader("kda_decode_roofline_pct")(silent) is None
    assert reader("ling_decode_hbm_pct")(silent) is None
    # another model's sizes (no kda_layers): the kernel's name alone does
    # not make these readers speak
    other = made_up()
    other["counts"]["sizes"] = {"layers": 16, "linear_layers": 12,
                                "expert_layers": 6}
    assert all(reader(name)(other) is None for name in NEW)


def test_decode_hbm_and_gmm_roofline_on_a_made_up_run():
    run = made_up()
    step = kernel_counts("ling_decode_step")
    need = step.bytes_needed(sizes(), 700_000, 6 * 2, 256)  # 2 hit a layer
    assert reader("ling_decode_hbm_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.030)                     # the median execution
    assert 0 < reader("ling_decode_hbm_pct")(run) < 100
    # 36 calls = 3 executions x 6 layers x 2 products: 6 ms a step; per step
    # and layer 768 rows over 3 steps, 2 experts hit
    count = kernel_counts("deepseek_decode_step")
    per_layer = max(count.gmm_layer_bytes(sizes(), 256, 2) / 819e9,
                    count.gmm_layer_flops(sizes(), 256) / 197e12)
    assert reader("ling_moe_gmm_roofline_pct")(run) == pytest.approx(
        100 * 6 * per_layer / 0.006)
    for name in ("ling_decode_hbm_pct", "ling_moe_gmm_roofline_pct"):
        assert reader(name)(made_up(moe=None)) is None
        assert reader(name)(made_up(moe={"load": [], "hit": [],
                                         "steps": 0})) is None
    assert reader("ling_moe_gmm_roofline_pct")(
        made_up(gmm=(0.018, 35))) is None
    # the window family's readers of the same quantities say nothing here
    assert reader("exaone_moe_gmm_roofline_pct")(run) is None
    assert reader("exaone_decode_hbm_pct")(run) is None


def test_the_readers_that_were_there_read_this_cell_as_they_are():
    run = made_up()
    assert reader("moe_gmm_kernel_ms_per_decode")(run) == pytest.approx(6.0)
    assert reader("moe_load_max_over_mean")(run) == pytest.approx(
        600 * 4 / 768)
    # ONE MLA call a step: ``layers`` of the readers' sizes is the MLA layers
    assert reader("mla_decode_kernel_ms_per_decode")(run) \
        == pytest.approx(3.0)
    assert reader("mla_decode_roofline_pct")(run) == pytest.approx(
        100 * 700_000 * 1_152 / 819e9 / 0.003)      # the bytes are the bound
    assert 700_000 * 69_632 / 197e12 < 700_000 * 1_152 / 819e9
    assert reader("decode_device_ms")(run) == pytest.approx(30.0)
    assert reader("sched_step_ms.serve")(run) == pytest.approx(34.0)
    assert reader("device_idle_pct.serve")(run) == 12.5
    path = os.path.join(DATA, "small_gpt_serve.xplane.pb.gz")
    recorded = {"trace": trace.reduce_file(path),
                "apex_spans": spans.load(path),
                "counts": {"sizes": sizes(), "mapped_positions": 700_000},
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
        "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
        "config.json")
    assert body["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"]
    assert body["runner"] == "ling_serve"
    for kind in ("runners/ling_serve", "reference/" + CONFIG, "kernels/kda",
                 "kernels/ling_decode_step", "traffic/" + MIX):
        assert os.path.exists(os.path.join(
            BENCH, kind + (".json" if kind.startswith("traffic") else ".py"))
        ), kind
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert len(cell["why"]) <= 200 and (
        "256 slots = 32 a chip of 8: mixers and MLA see 8x their share, 4 "
        "rows an expert; 7 of 42 layers: more host") in cell["why"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)
    by = {e["name"]: e for e in m["per_layer"]}
    assert all(by[name] == {
        "name": name, "unit": by[name]["unit"], "better": by[name]["better"],
        "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]} for name in NEW)
    assert [by[n]["unit"] for n in NEW] == ["ms", "%", "%", "%"]
    assert [by[n]["better"] for n in NEW] == ["lower"] + ["higher"] * 3
    mine = harness.Cell(CELL)
    assert [e["name"] for e in mine.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {e["name"] for e in mine.per_layer} == set(NEW) | SHARED
    # appended: wherever a list names this cell, the cells in front of it
    # stand in the manifest's order and this one once
    order = [w["name"] for w in m["workloads"]]
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if CELL in lists:
            assert lists.count(CELL) == 1
            assert lists == sorted(lists, key=order.index) or set(lists) <= {
                "bert_large.pretrain_s128", "bert_large.pretrain_s128_dp4"}
    # no prefill runs in the traced span (no resident finishes inside the
    # window): no reader of the prompt programs lists the cell
    assert all(CELL not in e.get("workloads", []) for e in m["per_layer"]
               if e["name"].startswith(("prefill_", "flash_", "hybrid_flash",
                                        "gdn_chunk"))
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
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(line) for line in open(catalog)
            if '"Ling-3.0-flash-VL"' in line] if os.path.exists(catalog) \
        else []
    body = config_file()
    for published in rows:
        assert body["source"] == published["source_url"]
        for key, value in published["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    assert (body["hidden_size"], body["num_attention_heads"],
            body["num_key_value_heads"], body["head_dim"],
            body["kv_lora_rank"], body["qk_nope_head_dim"],
            body["qk_rope_head_dim"], body["v_head_dim"],
            body["moe_intermediate_size"],
            body["moe_shared_expert_intermediate_size"],
            body["intermediate_size"], body["num_experts_per_tok"],
            body["n_group"], body["topk_group"],
            body["short_conv_kernel_size"], body["kda_lower_bound"],
            body["layer_group_size"], body["q_lora_rank"],
            body["rope_theta"]) == (
        2560, 32, 32, 128, 512, 128, 64, 128, 768, 768, 6144, 8, 8, 4, 4, -5,
        6, None, 6000000)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in body["reduced"])
    assert (body["num_hidden_layers"], body["first_k_dense_replace"],
            body["num_experts"], body["vocab_size"],
            body["first_layer_held"]) == (7, 1, 64, 19648, 1)
    assert body["expert_swiglu_limit_list"] == [0] * 7 \
        == body["share_expert_swiglu_limit_list"]
    published = body["published"]
    assert (published["num_hidden_layers"],
            published["first_k_dense_replace"], published["num_experts"],
            published["vocab_size"]) == (42, 2, 512, 157184)
    # the published lists clamp layers 35-41 / 34-41 only: no held layer
    assert published["expert_swiglu_limit_list"][1:8] == [0] * 7 \
        == published["share_expert_swiglu_limit_list"][1:8]
    assert len(published["expert_swiglu_limit_list"]) == 42
    assumed = body["assumed"]
    fields = {k: v[0] for k, v in assumed.items() if isinstance(v, list)}
    assert fields == harness.load_module("reference", CONFIG, BENCH).ASSUMED
    assert all(isinstance(v, list) and len(v) == 2 or isinstance(v, str)
               for v in assumed.values())
    assert {"attention", "seeded", "eos", "max_len"} <= set(assumed)
    assert set(body["left_out"]) == {"vision_tower", "mtp", "swiglu_clamp"}
    serving = body["serving"]
    assert (serving["slots"], serving["page_size"], serving["max_len"],
            serving["prefill_buckets"], serving["row_width"],
            serving["cache_dtype"]) == (256, 16, 8192, [1024, 2048, 4096],
                                        640, "bfloat16")
    sz = sizes()
    assert (sz["kda_layers"], sz["mla_layers"], sz["heads"], sz["kv_rank"],
            sz["latent_width"], sz["experts_held"], sz["expert_width"],
            sz["router_experts"], sz["dense_layers"], sz["expert_layers"]) \
        == (6, 1, 32, 512, 576, 64, 768, 512, 1, 6)
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
        {"process": "backlog", "requests": 320}, 256,
        {"dist": "loguniform", "lo": 1024, "hi": 4096},
        {"dist": "loguniform", "lo": 2048, "hi": 4096}, [0.0, 0.8], 4.0, 6.0)
    assert "shared_prefix" not in mix and "sizes_seed" in mix
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
        first = a[:256]
        assert sum(len(r.prompt) for r in first) == 563_938
        assert [sum(1 for r in first if lo < len(r.prompt) <= hi)
                for lo, hi in ((0, 1024), (1024, 2048), (2048, 4096))] \
            == [0, 127, 129]
        assert sum(r.temperature == 0.0 for r in first) == 128
        # no resident finishes inside 30 s unless a tick falls under 14.6 ms
        assert min(r.max_new_tokens for r in a) == 2048
        assert 30.0 / 2048 == pytest.approx(14.6e-3, rel=0.01)
        # the pool holds every slot at its longest: 512 pages a slot
        assert max(len(r.prompt) + r.max_new_tokens for r in a) <= 512 * 16


# -- the rehearsal of the cell ----------------------------------------------------

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


def test_the_cell_rehearses_and_both_controls_are_refused(capsys):
    """What ``test_rehearsal.py::test_window_line_says_what_is_left_of_the
    _backlog[ling3_flash_vl.many_stream_reasoning]`` checks besides its pin
    (``SERVING ==`` three cells), and the two controls: each says what it is
    and is refused by ``served_logit_gap_mean``."""
    said = []
    result, lines = rehearse(capsys, CELL, "--control", "1", stderr=said)
    [window] = [l for l in lines if l.get("stage") == "window"]
    cell = harness.Cell(CELL)
    offered = cell.traffic["rehearsal"]["arrivals"]["requests"]
    assert window["requests_submitted"] == offered
    assert 0 <= window["backlog_left"] <= offered - window[
        "requests_finished"]
    [err] = said
    if window["backlog_left"]:
        assert "drained_at_s" not in window and "drained" not in err
    else:
        assert 0.0 < window["drained_at_s"] <= window["window_s"]
        assert (f"benchmark/traffic/{cell.traffic_name}.json needs more "
                f"than {offered} arrivals.requests") in err
    assert result["correct"] is True and window["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # the program's counters, read before and after the window
    assert window["moe_steps"] == window["steps"] > 0
    hit, held = window["moe_hit_per_step_of_held"]
    assert len(window["moe_rows_per_step"]) == len(hit) == 6
    assert held == 8 and all(0 < h <= held for h in hit)
    assert window["block_table_uploads"] <= window["steps"] + 1
    [correct] = [l for l in lines if l.get("stage") == "correct"]
    assert 0.5 < correct["routes_agree"] <= 1.0
    assert len(correct["routes_agree_by_layer"]) == 6
    [resident] = [l for l in lines if l.get("stage") == "resident"]
    assert resident["requests"] == window["resident"] == 3
    assert resident["compile_events"] == [
        l for l in lines if l.get("stage") == "warm"][0]["compile_events"]
    [built] = [l for l in lines if l.get("stage") == "built"]
    # 6 KDA layers x 3 slots x (4 heads x 16 x 16 + 3 x 192) float32
    assert built["state_bytes"] + built["tail_bytes"] \
        == 3 * built["state_bytes_per_slot"] == 3 * 6 * 4 * (1024 + 576)
    assert built["row_bytes"] == 128 * 2
    controls = {l["precision"]: l for l in lines
                if l.get("stage") == "control"}
    assert set(controls) == {"bfloat16_activations", "scalar_gate"}
    assert "mean over the channels" in controls["scalar_gate"]["what"]
    assert "bfloat16" in controls["bfloat16_activations"]["what"]
    for control in controls.values():
        bad = {n["number"] for n in control["numbers"] if not n["ok"]}
        assert "served_logit_gap_mean" in bad


def test_served_tokens_altered_where_they_are_staged_are_not_correct(capsys):
    result, lines = rehearse(capsys, CELL, "--option", "break_tokens=1")
    assert result["correct"] is False
    bad = {n["number"] for l in lines if l.get("stage") == "correct"
           for n in l["numbers"] if not n["ok"]}
    assert bad == {"served_logit_gap_max", "served_logit_gap_mean"}


# -- the pinned tests' substance, by name -------------------------------------------

def test_the_backlog_mixes_are_the_serving_cells_traffic():
    mixes = sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if harness.load_json(BENCH, "traffic", f).get("arrivals", {}).get(
            "process") == "backlog")
    assert MIX in mixes
    assert sorted(w["traffic"] for w in MANIFEST["workloads"]
                  if w["name"] in SERVING) == mixes
    assert CELL in SERVING and len(SERVING) == len(set(SERVING))


def test_what_the_two_pinned_tests_of_test_exaone_cell_check_besides():
    """``test_exaone_cell.py::test_manifest_holds_the_configuration_the_cell
    _and_its_readers_by_name`` pins PR 40's cell as the LAST name of every
    list that names it, and ``...test_regions_check_besides`` pins what may
    stand behind PR 38's cells in its twenty lists as nothing or PR 40's cell
    alone; this PR appends its cell to sixteen lists that name PR 40's.
    Everything else they check, with the entries found by name."""
    from test_exaone_cell import NEW as EXAONE_NEW
    from test_exaone_cell import SHARED as EXAONE_SHARED
    from test_regions import DEEPSEEK, ENTRIES, HYBRID, NEMOTRON

    m = MANIFEST
    by = {e["name"]: e for e in m["per_layer"]}
    order = [w["name"] for w in m["workloads"]]
    exaone = harness.Cell(EXAONE)
    assert {e["name"] for e in exaone.per_layer} \
        == set(EXAONE_NEW) | EXAONE_SHARED
    assert all(by[n]["workloads"] == [EXAONE] for n in EXAONE_NEW)
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if EXAONE in lists:
            # PR 40's cell once, and behind it nothing but this PR's
            assert lists.count(EXAONE) == 1
            assert lists[lists.index(EXAONE) + 1:] in ([], [CELL])
            assert lists == sorted(lists, key=order.index)
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(next(iter(ENTRIES)))
    assert names[first:first + 20] == list(ENTRIES)     # together, in order
    for name, (layer, cells) in ENTRIES.items():
        e = by[name]
        assert e["layer"] == layer
        assert e["workloads"][:len(cells)] == cells
        assert e["workloads"][len(cells):] in ([], [EXAONE], [CELL],
                                               [EXAONE, CELL])
    assert sorted(n for n in ENTRIES if CELL in by[n]["workloads"]) == [
        "decode_ms.attention", "decode_ms.experts", "decode_ms.head",
        "decode_ms.mixer", "decode_ms.mlp", "decode_ms.unscoped"]
    assert by["decode_ms.mixer"]["workloads"] == [HYBRID, NEMOTRON, CELL]
    for n in ("moe_gmm_kernel_ms_per_decode", "moe_load_max_over_mean"):
        assert by[n]["workloads"] == [NEMOTRON, DEEPSEEK, EXAONE, CELL]
    for n in ("mla_decode_kernel_ms_per_decode", "mla_decode_roofline_pct"):
        assert by[n]["workloads"] == [DEEPSEEK, CELL]
    assert by["hybrid_paged_attn_kernel_ms_per_decode"]["workloads"] \
        == [HYBRID, EXAONE]
    assert len(harness.Cell(NEMOTRON).per_layer) == 16 + 12
    assert len(harness.Cell(DEEPSEEK).per_layer) == 4 + 11 + 5
