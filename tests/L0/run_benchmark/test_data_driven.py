"""A later PR adds a cell, a configuration, a traffic mix, a runner and a
per-layer metric as NEW files and new entries, and edits no file that is
there: proved on a temporary copy of the benchmark."""

import json
import os
import shutil

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def test_a_dummy_cell_and_metric_added_as_files_are_found_and_run(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "dummy.json"), "w") as f:
        json.dump({"source": "https://example.org/dummy", "reduced": [],
                   "runner": "dummy_runner", "width": 7}, f)
    with open(os.path.join(bench, "traffic", "dummy_mix.json"), "w") as f:
        json.dump({"kind": "requests", "n": 3}, f)
    with open(os.path.join(bench, "runners", "dummy_runner.py"), "w") as f:
        f.write("def run(ctx):\n"
                "    n = ctx.cell.traffic['n'] * ctx.cell.config['width']\n"
                "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
                "            'values': {'setup_s': 1.0, 'dummy_rate': 2.0},\n"
                "            'memory_peak_bytes': 1, 'counts': {'n': n}}\n")
    with open(os.path.join(bench, "reference", "dummy.py"), "w") as f:
        f.write("ANSWER = 42\n")
    with open(os.path.join(bench, "metrics", "dummy_share.x.py"), "w") as f:
        f.write("def read(run):\n    return 100.0 / run['counts']['n']\n")
    with open(os.path.join(bench, "metrics", "nothing_to_read.py"), "w") as f:
        f.write("def read(run):\n    return None\n")

    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["configs"].append({
        "name": "dummy", "source": "https://example.org/dummy",
        "file": "benchmark/configs/dummy.json", "reduced": [], "why": "t"})
    manifest["workloads"].append({
        "name": "dummy.mix", "config": "dummy", "traffic": "dummy_mix",
        "chips": 1, "why": "t"})
    manifest["end_to_end"].append({
        "name": "dummy_rate", "unit": "x/s", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["dummy.mix"]})
    for name in ("dummy_share.x", "nothing_to_read"):
        manifest["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "Dummy",
            "moves": "dummy_rate", "workloads": ["dummy.mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    cell = harness.Cell("dummy.mix", root=root)
    assert sorted(m["name"] for m in cell.end_to_end) == ["dummy_rate",
                                                          "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["dummy_share.x",
                                                   "nothing_to_read"]
    assert cell.reference().ANSWER == 42
    ctx = type("Ctx", (), {"cell": cell})
    out = cell.runner().run(ctx)
    assert out["attempted"] == 21
    run = {"counts": out["counts"]}
    got = {m["name"]: harness.load_module("metrics", m["name"],
                                          cell.bench_dir).read(run)
           for m in cell.per_layer}
    # a reader that finds nothing returns nothing and is left out
    assert got == {"dummy_share.x": 100.0 / 21, "nothing_to_read": None}
    # an old cell still resolves in the copy, and no file that was there
    # has changed
    assert harness.Cell("bert_large.pretrain_s128", root=root).chips == 1
    for p, body in before.items():
        assert open(p, "rb").read() == body, p
