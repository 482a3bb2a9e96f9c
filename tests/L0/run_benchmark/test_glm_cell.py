"""The cell ``glm_5_3_flash.long_resident_sparse_decode`` (PR 47): the cut's
parameter count term by term, the count files by hand, its readers on traces
without the new kernels (nothing, and no raise) and on made-up runs (the
arithmetic; ``dsa_attend_roofline_pct`` under 100 where
``mla_decode_roofline_pct``'s count would read over it), the readers that were
there on this cell's counts, its manifest entries and files (found BY NAME:
this file pins nothing as the last entry of a list and no list's length), the
sizes its traffic offers, the rehearsal of the cell with all three controls
refused and altered tokens not ``correct``, and what the test pinned in
``tests/conftest.py`` by this PR checks besides its pins."""

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
CONFIG = "glm_5_3_flash"
MIX = "long_resident_sparse_decode"
CELL = CONFIG + "." + MIX
LING = "ling3_flash_vl.many_stream_reasoning"
NEW = ("dsa_index_kernel_ms_per_decode", "dsa_index_roofline_pct",
       "dsa_attend_roofline_pct", "dsa_rows_read_pct",
       "glm_moe_gmm_roofline_pct", "glm_decode_hbm_pct")
# the readers that were there and read this cell as they are
SHARED = {"sched_step_ms.serve", "decode_device_ms", "device_idle_pct.serve",
          "tick_idle_ms.admit", "tick_idle_ms.build_inputs",
          "tick_idle_ms.dispatch", "tick_idle_ms.accept",
          "tick_idle_ms.commit_flush", "tick_idle_ms.unspanned",
          "decode_ms.attention", "decode_ms.mixer", "decode_ms.mlp",
          "decode_ms.experts", "decode_ms.head", "decode_ms.unscoped",
          "moe_gmm_kernel_ms_per_decode", "moe_load_max_over_mean",
          "mla_decode_kernel_ms_per_decode",
          "kda_decode_kernel_ms_per_decode", "kda_decode_roofline_pct"}
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


# -- the count, term by term --------------------------------------------------------

def test_the_cut_holds_4_717_674_496_matrix_parameters_term_by_term():
    sz, step = sizes(), kernel_counts("glm_decode_step")
    kda = 4096 * (3 * 8192 + 128 + 128 + 64) + 2 * 128 * 8192 + 8192 * 4096
    assert step.kda_layer(sz)[0] == kda == 137_625_600
    indexer = 1536 * 32 * 128 + 4096 * (128 + 32)
    assert step.indexer(sz) == indexer == 6_946_816
    sparse = 4096 * (1536 + 512) + 1536 * 64 * 256 + 512 * 64 * 512 \
        + 64 * 256 * 4096 + indexer
    assert step.sparse_layer(sz)[0] == sparse == 124_387_328
    assert step.dense_mlp(sz) == 3 * 4096 * 12288 == 150_994_944
    assert step.shared_expert(sz) == step.one_expert(sz) == 25_165_824
    assert step.router(sz) == 4096 * 288 == 1_179_648
    assert step.vocabulary(sz) == 2 * 19360 * 4096 == 158_597_120
    hyper = 16384 * 24
    layer_2 = kda + 150_994_944 + 2 * hyper
    assert layer_2 == 289_406_976
    later = sparse + 3 * kda + 4 * (1_179_648 + 25_165_824
                                    + 36 * 25_165_824 + 2 * hyper)
    assert later == 537_264_128 + 3_732_406_272
    total = layer_2 + later + 158_597_120
    said = config_file()["deployment"]
    assert step.matrix_parameters(sz) == total == 4_717_674_496 \
        == said["parameters"]["matrix_sum"]
    assert said["chips_per_layer"] == 8
    assert (said["parameters"]["kda_layer"],
            said["parameters"]["sparse_attention_layer"],
            said["parameters"]["of_it_indexer"]) == (kda, sparse, indexer)
    assert round(2 * total / 1e9, 2) == 9.44
    # the whole model by the same terms: the family's "320B-A18B"
    whole = 34 * kda + 11 * sparse + 90 * hyper + 3 * 150_994_944 \
        + 42 * (1_179_648 + 289 * 25_165_824) + 2 * 154880 * 4096
    assert round(whole / 1e9, 1) == 313.3
    a_token = 34 * kda + 11 * sparse + 90 * hyper + 3 * 150_994_944 \
        + 42 * (1_179_648 + 9 * 25_165_824) + 154880 * 4096
    assert round(a_token / 1e9, 1) == 16.7


def test_the_program_holds_what_the_count_says():
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import glm_next

    config = config_file()
    runner = harness.load_module("runners", "glm_serve", BENCH)
    cfg = runner.model_config(config, sizes())
    shapes = jax.eval_shape(lambda k: glm_next.init(k, cfg, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    matrices = sum(a.size for a in jax.tree.leaves(shapes)
                   if a.ndim >= 2 and a.shape[-1] > 4) - 4 * 4 * 24576
    assert matrices == 4_717_674_496
    assert cfg.state_shapes(64) == ((4, 64, 64, 128, 128), (4, 64, 3, 24576))
    assert cfg.index_shapes(64, 65538, 16) == ((1, 65538, 4, 128),
                                               (1, 64, 3, 128))
    assert cfg.kv_row_width == 512 and cfg.top_groups == 512
    # the reference draws the same tree, leaf for leaf
    ref = harness.load_module("reference", CONFIG, BENCH)
    theirs = jax.eval_shape(lambda k: ref.make_weights(sizes(), k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(theirs) == jax.tree.structure(shapes)
    assert jax.tree.map(lambda a: a.shape, theirs) \
        == jax.tree.map(lambda a: a.shape, shapes)


def test_count_files_by_hand():
    sz, dsa, step = sizes(), kernel_counts("dsa"), \
        kernel_counts("glm_decode_step")
    # one pooled key of 256 bytes a whole group of 4 positions, 32 heads of
    # 128 against it: 8,192 operations over 256 bytes
    assert dsa.index_bytes(sz, 4000) == 1000 * 256
    assert dsa.index_flops(sz, 4000) == 1000 * 2 * 32 * 128
    # an attended row: 1,024 bytes, 64 heads x 2 x (512 + 512) operations
    assert dsa.attend_bytes(sz, 1000) == 1000 * 1024
    assert dsa.attend_flops(sz, 1000) == 1000 * 131_072
    assert 131_072 / 1024 == 128 < 240 and 8192 / 256 == 32     # memory-bound
    assert step.state_bytes(sz, 64) == 4 * 64 * 4 * 2 ** 20
    assert step.index_bytes(sz, 64 * 8000) == 64 * 2000 * 256
    assert step.latent_bytes(sz, 64 * 2052) == 64 * 2052 * 1024
    weights = step.weight_bytes(sz, 4 * 30)
    rest = 4_717_674_496 - 4 * 36 * 25_165_824 - 19360 * 4096
    assert weights == pytest.approx(2 * (rest + 120 * 25_165_824
                                         + 4 * 4 * 24576), rel=2e-3)
    need = step.bytes_needed(sz, 64 * 8500, 64 * 2052, 4 * 30, 64)
    assert need == weights + 2 * step.state_bytes(sz, 64) \
        + step.index_bytes(sz, 64 * 8500) + step.latent_bytes(sz, 64 * 2052)
    assert 10.2e9 < need < 10.6e9           # the issue's 10.4 GB a tick


# -- the readers --------------------------------------------------------------------

@pytest.mark.parametrize("recorded", ["small_gpt_serve", "small_hybrid_serve"])
def test_new_readers_give_nothing_on_traces_without_the_new_kernels(recorded):
    """The parent's programs, with their own counts, with this cell's and
    with none: no reader raises, every one returns ``None``."""
    path = os.path.join(DATA, recorded + ".xplane.pb.gz")
    cell = types.SimpleNamespace(bench_dir=BENCH)
    moe = {"load": [[3, 1], [2, 2]], "hit": [2, 2], "steps": 2}
    mine = {**sizes(True), "layers": 1}
    for counts in ({"sizes": {"layers": 24}},
                   {"sizes": {"layers": 2, "hidden": 64}, "slots": 3,
                    "mapped_positions": 40},
                   {"sizes": mine, "mapped_positions": 40, "moe": moe,
                    "attended_positions": 20},
                   {"sizes": mine, "mapped_positions": 40, "moe": None,
                    "dsa": None}, {}):
        run = {"trace": trace.reduce_file(path),
               "apex_spans": spans.load(path), "counts": counts,
               "peaks": PEAKS, "cell": cell}
        got = {name: reader(name)(run) for name in NEW}
        assert all(v is None for v in got.values()), got


def made_up(index_calls=3, mla_calls=3, kda_calls=12, gmm=(0.027, 24),
            moe="default", dsa="default", positions=64 * 8500, slots=64):
    """A run of three decode executions at the full sizes' layer counts (four
    KDA calls, one index call and one MLA call a step, 4 expert layers)."""
    names = {"index": ("%apex_dsa_index_fwd.1 = f32[64,1,4096]{2,1,0} "
                       "custom-call(s32[64,1024] %a, s32[64] %b)", 0.0018,
                       index_calls),
             "mla": ("%apex_mla_decode_fwd.1 = f32[64,64,512]{2,1,0} "
                     "custom-call(s32[64,129] %a, s32[64] %b)", 0.0015,
                     mla_calls),
             "kda": ("%apex_kda_decode_fwd.4 = (f32[64,8,8,128]{3,2,1,0}, "
                     "f32[4,64,64,128,128]{4,3,2,1,0}) custom-call("
                     "s32[1] %a, s32[64] %b)", 0.0135, kda_calls)}

    def kernel_time(match):
        for name, seconds, calls in names.values():
            if match(name) and calls:
                return seconds, calls
        return 0.0, 0

    if moe == "default":
        moe = {"load": [[100, 92, 0, 0]] * 4, "hit": [30 * 3] * 4, "steps": 3}
    if dsa == "default":
        dsa = {"rows_read": 3 * 64 * 2051, "rows_mapped": 3 * 64 * 8500}
    execs = [spans.Span("exec", 0.1 * i, 0.1 * i + 0.03,
                        {"kind": "decode", "state_slots": slots}, -1)
             for i in range(3)] if slots else []
    counts = {"sizes": {**sizes(), "layers": 1},
              "mapped_positions": positions, "moe": moe, "dsa": dsa,
              "step_walls": [(0.0, 0.025), (1.0, 0.026), (2.0, 0.027)]}
    if dsa and dsa["rows_mapped"]:
        counts["attended_positions"] = int(round(
            positions * dsa["rows_read"] / dsa["rows_mapped"]))
    return {"trace": types.SimpleNamespace(
                kernel_time=kernel_time, window=(0.0, 2.0),
                idle_pct=lambda: 12.5,
                program_times=lambda p: [0.020, 0.022, 0.024]
                if p == "jit_decode" else []),
            "apex_spans": execs, "moe_gmm_calls": {
                "jit_decode": gmm, "jit_prefill": (0.5, 16)},
            "counts": counts, "peaks": PEAKS,
            "cell": types.SimpleNamespace(bench_dir=BENCH)}


def test_the_sparse_layers_readers_on_a_made_up_run():
    run = made_up()
    dsa = kernel_counts("dsa")
    # 3 calls = 3 executions of one sparse layer: 1.8 ms over 3
    assert reader("dsa_index_kernel_ms_per_decode")(run) \
        == pytest.approx(0.6)
    assert reader("dsa_index_roofline_pct")(run) == pytest.approx(
        100 * dsa.index_bytes(sizes(), 64 * 8500) / 819e9 / 0.0006)
    assert reader("dsa_rows_read_pct")(run) == pytest.approx(
        100 * 2051 / 8500)
    attended = run["counts"]["attended_positions"]
    assert attended == 64 * 2051
    assert reader("dsa_attend_roofline_pct")(run) == pytest.approx(
        100 * attended * 1024 / 819e9 / 0.0005)
    # the bytes are the bound of both
    assert dsa.index_flops(sizes(), 1e6) / 197e12 \
        < dsa.index_bytes(sizes(), 1e6) / 819e9
    assert dsa.attend_flops(sizes(), 1e6) / 197e12 \
        < dsa.attend_bytes(sizes(), 1e6) / 819e9
    for name in NEW:
        assert 0 < reader(name)(run) < 100, name
    # the reader of every MAPPED position would read over 100 here: it counts
    # four times the rows the kernel is given (why the cell is not on its list)
    assert reader("mla_decode_roofline_pct")(run) > 100
    by = {e["name"]: e for e in MANIFEST["per_layer"]}
    assert CELL not in by["mla_decode_roofline_pct"]["workloads"]
    # an execution cut by the session, no call at all, no counters
    for cut in (made_up(index_calls=0), made_up(dsa=None)):
        assert reader("glm_decode_hbm_pct")(cut) is None
    assert reader("dsa_index_kernel_ms_per_decode")(
        made_up(index_calls=0)) is None
    assert reader("dsa_index_roofline_pct")(made_up(index_calls=0)) is None
    assert reader("dsa_attend_roofline_pct")(made_up(mla_calls=0)) is None
    assert reader("dsa_attend_roofline_pct")(made_up(dsa=None)) is None
    assert reader("dsa_rows_read_pct")(made_up(dsa=None)) is None
    assert reader("dsa_rows_read_pct")(made_up(
        dsa={"rows_read": 0, "rows_mapped": 0})) is None
    assert reader("glm_decode_hbm_pct")(made_up(slots=0)) is None
    # another model's sizes: the kernels' names alone do not make them speak
    other = made_up()
    other["counts"]["sizes"] = {"layers": 1, "kda_layers": 6,
                                "latent_width": 576, "expert_layers": 6}
    assert all(reader(name)(other) is None for name in NEW
               if name != "dsa_rows_read_pct")


def test_decode_hbm_and_gmm_roofline_on_a_made_up_run():
    run = made_up()
    step = kernel_counts("glm_decode_step")
    need = step.bytes_needed(sizes(), 64 * 8500, 64 * 2051, 4 * 30, 64)
    assert reader("glm_decode_hbm_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.022)                     # the median execution
    # 24 calls = 3 executions x 4 layers x 2 products: 9 ms a step; per step
    # and layer 64 rows, 30 experts hit
    count = kernel_counts("deepseek_decode_step")
    per_layer = max(count.gmm_layer_bytes(sizes(), 64, 30) / 819e9,
                    count.gmm_layer_flops(sizes(), 64) / 197e12)
    assert reader("glm_moe_gmm_roofline_pct")(run) == pytest.approx(
        100 * 4 * per_layer / 0.009)
    for name in ("glm_decode_hbm_pct", "glm_moe_gmm_roofline_pct"):
        assert reader(name)(made_up(moe=None)) is None
    assert reader("glm_moe_gmm_roofline_pct")(made_up(gmm=(0.027, 23))) \
        is None
    # the other families' readers of the same quantities say nothing here
    assert reader("exaone_moe_gmm_roofline_pct")(run) is None
    assert reader("exaone_decode_hbm_pct")(run) is None


def test_the_readers_that_were_there_read_this_cell_as_they_are():
    run = made_up()
    assert reader("moe_gmm_kernel_ms_per_decode")(run) == pytest.approx(9.0)
    assert reader("moe_load_max_over_mean")(run) == pytest.approx(
        100 * 4 / 192)
    assert reader("mla_decode_kernel_ms_per_decode")(run) \
        == pytest.approx(0.5)
    assert reader("kda_decode_kernel_ms_per_decode")(run) \
        == pytest.approx(4.5)
    need = 4 * kernel_counts("kda").decode_bytes(sizes(), 64)
    assert reader("kda_decode_roofline_pct")(run) == pytest.approx(
        100 * need / 819e9 / 0.0045)
    assert 0 < reader("kda_decode_roofline_pct")(run) < 100
    assert reader("decode_device_ms")(run) == pytest.approx(22.0)
    assert reader("sched_step_ms.serve")(run) == pytest.approx(26.0)
    assert reader("device_idle_pct.serve")(run) == 12.5
    # Ling's readers of its own step say nothing on these sizes' run ...
    assert reader("ling_moe_gmm_roofline_pct")(run) is not None
    # ... except where they key on kda_layers alone, which is why the cell
    # stands on neither of their lists
    by = {e["name"]: e for e in MANIFEST["per_layer"]}
    assert by["ling_moe_gmm_roofline_pct"]["workloads"] == [LING]
    assert by["ling_decode_hbm_pct"]["workloads"] == [LING]


# -- the manifest and the files -----------------------------------------------------

def test_manifest_holds_the_configuration_the_cell_and_its_readers_by_name():
    m = MANIFEST
    config = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    body = config_file()
    assert body["source"] == config["source"] == (
        "https://huggingface.co/zai-org/GLM-5.3-Flash/blob/main/config.json")
    assert body["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "layer_types",
        "mlp_layer_types", "indexer_types", "linear_attn_config",
        "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert body["runner"] == "glm_serve" and len(config["why"]) <= 200
    for kind in ("runners/glm_serve", "reference/" + CONFIG, "kernels/dsa",
                 "kernels/glm_decode_step", "traffic/" + MIX):
        assert os.path.exists(os.path.join(
            BENCH, kind + (".json" if kind.startswith("traffic") else ".py"))
        ), kind
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": MIX, "chips": 1}
    assert len(cell["why"]) <= 200 and "64 slots = 8 a chip of 8" \
        in cell["why"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) \
        <= max(1, len(m["workloads"]) // 4)
    by = {e["name"]: e for e in m["per_layer"]}
    assert all(by[name] == {
        "name": name, "unit": by[name]["unit"], "better": by[name]["better"],
        "source": by[name]["source"], "layer": "Kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]} for name in NEW)
    assert [by[n]["unit"] for n in NEW] == ["ms", "%", "%", "%", "%", "%"]
    assert [by[n]["better"] for n in NEW] == [
        "lower", "higher", "higher", "lower", "higher", "higher"]
    assert [by[n]["source"] for n in NEW] == [
        "device_trace"] * 3 + ["program_counter"] + ["device_trace"] * 2
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
            assert lists == sorted(lists, key=order.index)
    # no prefill runs in the traced span: no reader of the prompt programs
    # lists the cell; nor does the reader that counts every mapped row
    assert all(CELL not in e.get("workloads", []) for e in m["per_layer"]
               if e["name"].startswith(("prefill_", "flash_", "hybrid_flash",
                                        "gdn_chunk", "ln_kernels"))
               or e["name"] in ("itl_ms_p95", "mla_decode_roofline_pct"))
    assert len(json.dumps(m)) < 64 << 10
    names = [e["name"] for e in m["per_layer"]]
    assert len(set(names)) == len(names) <= 128
    for e in m["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           e["name"] + ".py")), e["name"]


def test_configuration_file_holds_the_published_widths_and_its_cut():
    """Every number of the catalog row's ``config`` under the same key,
    except the keys under ``reduced``; no width among those."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(line) for line in open(catalog)
            if '"GLM-5.3-Flash"' in line] if os.path.exists(catalog) else []
    body = config_file()
    for published in rows:
        assert body["source"] == published["source_url"]
        for key, value in published["config"].items():
            if key not in body["reduced"]:
                assert body[key] == value, key
        kda = published["config"]["linear_attn_config"]
        assert {k: v for k, v in body["linear_attn_config"].items()
                if k not in ("kda_layers", "full_attn_layers")} \
            == {k: v for k, v in kda.items()
                if k not in ("kda_layers", "full_attn_layers")}
    assert (body["hidden_size"], body["num_attention_heads"],
            body["linear_attn_config"]["num_heads"],
            body["linear_attn_config"]["head_dim"], body["q_lora_rank"],
            body["kv_lora_rank"], body["qk_nope_head_dim"],
            body["qk_rope_head_dim"], body["v_head_dim"],
            body["index_n_heads"], body["index_head_dim"],
            body["index_topk"], body["index_kpool"], body["hc_mult"],
            body["hc_sinkhorn_iters"], body["moe_intermediate_size"],
            body["intermediate_size"], body["num_experts_per_tok"],
            body["swiglu_limit"]) == (
        4096, 64, 64, 128, 1536, 512, 256, 0, 256, 32, 128, 2048, 4, 4, 20,
        2048, 12288, 8, 10)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in body["reduced"])
    assert (body["num_hidden_layers"], body["first_k_dense_replace"],
            body["n_routed_experts"], body["vocab_size"],
            body["first_layer_held"], body["num_nextn_predict_layers"]) \
        == (5, 1, 36, 19360, 2, 0)
    assert body["layer_types"] == [
        "linear_attention", "deepseek_sparse_attention"] \
        + ["linear_attention"] * 3
    assert body["linear_attn_config"]["kda_layers"] == [2, 4, 5, 6]
    assert body["linear_attn_config"]["full_attn_layers"] == [3]
    published = body["published"]
    assert (published["num_hidden_layers"],
            published["first_k_dense_replace"], published["n_routed_experts"],
            published["vocab_size"], published["num_nextn_predict_layers"]) \
        == (45, 3, 288, 154880, 1)
    assert len(published["layer_types"]) == 45
    assert set(published) == set(body["reduced"])
    fields = {k: v[0] for k, v in body["assumed"].items()}
    assert fields == harness.load_module("reference", CONFIG, BENCH).ASSUMED
    assert all(isinstance(v, list) and len(v) == 2
               for v in body["assumed"].values())
    assert set(body["left_out"]) >= {"vision_tower", "mtp"}
    serving = body["serving"]
    assert (serving["slots"], serving["page_size"], serving["max_len"],
            serving["prefill_buckets"], serving["row_width"],
            serving["cache_dtype"]) == (64, 16, 16384, [12288],
                                        512, "bfloat16")
    sz = sizes()
    assert (sz["kda_layers"], sz["mla_layers"], sz["heads"], sz["head_dim"],
            sz["kv_rank"], sz["latent_width"], sz["index_heads"],
            sz["index_width"], sz["index_pool"], sz["index_topk"],
            sz["streams"], sz["experts_held"], sz["expert_width"],
            sz["router_experts"], sz["dense_layers"], sz["expert_layers"]) \
        == (4, 1, 64, 128, 512, 512, 32, 128, 4, 2048, 4, 36, 2048, 288, 1, 4)
    correct = body["correct"]
    assert correct["sample_requests"] == 6
    assert correct["min_tokens_judged"] >= 6 * 200
    assert correct["tokens_per_request"] >= 200
    assert set(correct["limits"]) == {"logit_gap_max", "logit_gap_mean"}
    assert set(correct["reasons"]) >= set(correct["limits"])


@pytest.mark.parametrize("rehearsal", [False, True])
def test_traffic_is_the_issues_and_every_seed_offers_the_same_work(rehearsal):
    mix = harness.load_json(BENCH, "traffic", MIX + ".json")
    assert (mix["arrivals"], mix["resident"], mix["prompt_tokens"],
            mix["max_new_tokens"], mix["temperatures"], mix["trace_start_s"],
            mix["trace_seconds"]) == (
        {"process": "backlog", "requests": 96}, 64,
        {"dist": "loguniform", "lo": 4096, "hi": 12288},
        {"dist": "loguniform", "lo": 2048, "hi": 3072}, [0.0, 0.8], 4.0, 6.0)
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
        first = a[:64]
        # every judged context selects: over index_topk from its first token
        assert min(len(r.prompt) for r in a) >= 4096 > 2 * 2048 - 1
        assert max(len(r.prompt) for r in a) <= 12288
        assert 6_000 < sum(len(r.prompt) for r in first) / 64 < 9_000
        assert sum(r.temperature == 0.0 for r in first) == 32
        # no resident finishes inside 30 s unless a tick falls under 14.6 ms
        assert min(r.max_new_tokens for r in a) >= 2048
        assert max(len(r.prompt) + r.max_new_tokens for r in a) <= 1024 * 16


# -- the rehearsal of the cell ------------------------------------------------------

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


def test_the_cell_rehearses_and_all_three_controls_are_refused(capsys):
    result, lines = rehearse(capsys, CELL, "--control", "1")
    [window] = [l for l in lines if l.get("stage") == "window"]
    cell = harness.Cell(CELL)
    offered = cell.traffic["rehearsal"]["arrivals"]["requests"]
    assert window["requests_submitted"] == offered
    assert result["correct"] is True and window["compiles_in_window"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # the program's counters, read before and after the window
    assert window["moe_steps"] == window["steps"] > 0
    hit, held = window["moe_hit_per_step_of_held"]
    assert len(window["moe_rows_per_step"]) == len(hit) == 4
    assert held == 8 and all(0 < h <= held for h in hit)
    rows = window["dsa_rows"]
    assert 0 < rows["rows_read"] < rows["rows_mapped"]
    [mapped] = [l for l in lines if l.get("stage") == "mapped"]
    assert 0 < mapped["attended_per_slot"] <= 16 + 4
    [correct] = [l for l in lines if l.get("stage") == "correct"]
    assert 0.5 < correct["routes_agree"] <= 1.0
    assert len(correct["routes_agree_by_layer"]) == 4
    assert 0.9 < correct["picks_agree"] <= 1.0
    [resident] = [l for l in lines if l.get("stage") == "resident"]
    assert resident["requests"] == window["resident"] == 3
    [built] = [l for l in lines if l.get("stage") == "built"]
    # 4 KDA layers x 3 slots x (4 heads x 16 x 16 + 3 x 192) float32, and the
    # index tail's 3 x 16 a slot
    assert built["state_bytes"] + built["tail_bytes"] \
        + built["index_tail_bytes"] == 3 * built["state_bytes_per_slot"] \
        == 3 * 4 * (4 * (1024 + 576) + 48)
    assert built["row_bytes"] == 128 * 2
    assert built["index_bytes"] == built["num_pages"] * 16 * 2
    controls = {l["precision"]: l for l in lines
                if l.get("stage") == "control"}
    assert set(controls) == {"bfloat16_activations", "dense_attention",
                             "single_stream"}
    assert "every position" in controls["dense_attention"]["what"]
    assert "residual maps" in controls["single_stream"]["what"]
    for control in controls.values():
        bad = {n["number"] for n in control["numbers"] if not n["ok"]}
        assert "served_logit_gap_mean" in bad


def test_served_tokens_altered_where_they_are_staged_are_not_correct(capsys):
    result, lines = rehearse(capsys, CELL, "--option", "break_tokens=1")
    assert result["correct"] is False
    # a run the driver makes (no ``--control``) reads the routes and the
    # picks too, and compiles nothing between its warm-up and its window's end
    [correct] = [l for l in lines if l.get("stage") == "correct"]
    assert 0.5 < correct["routes_agree"] <= 1.0
    assert 0.9 < correct["picks_agree"] <= 1.0
    events = [l["compile_events"] for l in lines
              if l.get("stage") in ("warm", "resident")]
    assert len(events) == 2 and events[0] == events[1]


def test_the_judges_program_is_compiled_for_the_requests_it_will_be_handed():
    """``judged_ahead`` names, before the window, the sequences that
    ``check_outputs`` hands the reference after it (no request of this cell
    finishes inside the window), and every one of them is padded to the ONE
    length the configuration states."""
    import numpy as np

    runner = harness.load_module("runners", "glm_serve", BENCH)
    config, mix = config_file(), harness.load_json(BENCH, "traffic",
                                                   MIX + ".json")
    sz = sizes()
    seed = 2 ** 31 + 11
    arrivals = traffic.requests(mix, seed, 30.0, sz["vocab"],
                                sz["positions"])
    ahead = runner.judged_ahead(config, arrivals[:mix["resident"]], seed, sz)
    handed = []

    class Scorer:
        def __init__(self, sz, seed):
            pass

        def gaps(self, prompt, served):
            served = list(served)[:sz["judged_tokens"]]
            handed.append(len(prompt) + len(served))
            return np.zeros((len(served),)), None

    rows, info, _ = runner.gpt.check_outputs(
        types.SimpleNamespace(seed=seed, control=False), config,
        types.SimpleNamespace(Scorer=Scorer), sz, arrivals,
        {"submitted": len(arrivals), "rid_of": {i: i for i in range(96)}},
        [], {i: [7] * 1200 for i in range(mix["resident"])})
    assert ahead == handed and len(handed) == 6 == info["requests_judged"]
    assert all(4096 + 256 <= n <= 12288 + 256 for n in handed)
    greedy = [a for a in arrivals[:mix["resident"]] if a.temperature <= 0]
    assert handed[0] == max(len(a.prompt) for a in greedy) + 256
    judge = object.__new__(harness.load_module("reference", CONFIG,
                                               BENCH).Scorer)
    judge.sz = sz
    assert {len(judge._padded([0] * n)) for n in handed} == {12800} \
        == {config["correct"]["padded_positions"]}


# -- the pinned test's substance, by name -------------------------------------------

def test_the_backlog_mixes_are_the_serving_cells_traffic():
    mixes = sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if harness.load_json(BENCH, "traffic", f).get("arrivals", {}).get(
            "process") == "backlog")
    assert MIX in mixes
    assert sorted(w["traffic"] for w in MANIFEST["workloads"]
                  if w["name"] in SERVING) == mixes
    assert CELL in SERVING and len(SERVING) == len(set(SERVING))


def test_what_the_pinned_tests_of_test_ling_cell_check_besides():
    """``test_ling_cell.py::test_manifest_holds_the_configuration_the_cell_and
    _its_readers_by_name`` pins PR 42's four readers as its cell's alone, and
    ``...test_exaone_cell_check_besides`` what may stand behind PR 40's cell
    in a list as nothing or PR 42's cell; this PR appends its cell to the two
    KDA readers and to fifteen lists that name both cells. Everything else
    they check, with the entries found by name."""
    from test_exaone_cell import NEW as EXAONE_NEW
    from test_exaone_cell import SHARED as EXAONE_SHARED
    from test_ling_cell import EXAONE
    from test_ling_cell import NEW as LING_NEW
    from test_ling_cell import SHARED as LING_SHARED
    from test_regions import DEEPSEEK, ENTRIES, HYBRID, NEMOTRON

    m = MANIFEST
    by = {e["name"]: e for e in m["per_layer"]}
    order = [w["name"] for w in m["workloads"]]
    ling = harness.Cell(LING)
    assert {e["name"] for e in ling.per_layer} == set(LING_NEW) | LING_SHARED
    assert [e["name"] for e in ling.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    for name in LING_NEW:
        e = by[name]
        assert (e["source"], e["layer"], e["moves"]) == (
            "device_trace", "Kernels", "serve_tokens_per_s")
        assert e["workloads"] == ([LING, CELL] if name.startswith("kda_")
                                  else [LING])
    assert [by[n]["unit"] for n in LING_NEW] == ["ms", "%", "%", "%"]
    cell = {w["name"]: w for w in m["workloads"]}[LING]
    assert cell == {**cell, "config": "ling3_flash_vl",
                    "traffic": "many_stream_reasoning", "chips": 1}
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    exaone = harness.Cell(EXAONE)
    assert {e["name"] for e in exaone.per_layer} \
        == set(EXAONE_NEW) | EXAONE_SHARED
    assert all(by[n]["workloads"] == [EXAONE] for n in EXAONE_NEW)
    for e in m["end_to_end"] + m["per_layer"]:
        lists = e.get("workloads", [])
        if EXAONE in lists:
            assert lists.count(EXAONE) == 1
            assert lists == sorted(lists, key=order.index)
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(next(iter(ENTRIES)))
    assert names[first:first + 20] == list(ENTRIES)     # together, in order
    for name, (layer, cells) in ENTRIES.items():
        e = by[name]
        assert e["layer"] == layer
        assert e["workloads"][:len(cells)] == cells
        assert set(e["workloads"][len(cells):]) <= {EXAONE, LING, CELL}
    assert sorted(n for n in ENTRIES if CELL in by[n]["workloads"]) == [
        "decode_ms.attention", "decode_ms.experts", "decode_ms.head",
        "decode_ms.mixer", "decode_ms.mlp", "decode_ms.unscoped"]
    assert by["decode_ms.mixer"]["workloads"] == [HYBRID, NEMOTRON, LING,
                                                  CELL]
    for n in ("moe_gmm_kernel_ms_per_decode", "moe_load_max_over_mean"):
        assert by[n]["workloads"] == [NEMOTRON, DEEPSEEK, EXAONE, LING, CELL]
    assert by["mla_decode_kernel_ms_per_decode"]["workloads"] \
        == [DEEPSEEK, LING, CELL]
    assert by["mla_decode_roofline_pct"]["workloads"] == [DEEPSEEK, LING]
    assert by["hybrid_paged_attn_kernel_ms_per_decode"]["workloads"] \
        == [HYBRID, EXAONE]
    assert len(harness.Cell(NEMOTRON).per_layer) == 16 + 12
    assert len(harness.Cell(DEEPSEEK).per_layer) == 4 + 11 + 5
