"""``BENCHMARK.json`` against the contract's character and consistency rules,
and the files its entries name."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark", "tests/L0/run_benchmark"]
    assert MANIFEST["command"][1].startswith("benchmark/")
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_obeys_the_rules(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in MANIFEST["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = {m["name"]: m for m in MANIFEST["end_to_end"]}[metric["moves"]]
        # every cell that reads it reports the metric it should move
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
        reader = os.path.join(REPO, "benchmark", "metrics",
                              metric["name"] + ".py")
        assert os.path.exists(reader), reader


def test_metric_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_its_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"].startswith("benchmark/configs/")
    body = json.load(open(os.path.join(REPO, config["file"])))
    assert body["source"] == config["source"]
    # a configuration cut in depth says so in both places, in the same words
    assert body["reduced"] == config["reduced"]
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(key) and key in body for key in config["reduced"])
    for kind in ("runners/" + body["runner"], "reference/" + config["name"]):
        assert os.path.exists(os.path.join(REPO, "benchmark", kind + ".py"))
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "traffic", cell["traffic"] + ".json"))
    reports = [m["name"] for m in MANIFEST["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in reports and len(reports) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in MANIFEST["per_layer"])


def test_cells_are_unique_and_at_most_a_quarter_take_four_chips():
    cells = MANIFEST["workloads"]
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_the_full_check_fits_with_24_cells():
    rs = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_result_line_holds_exactly_the_contract_keys():
    from benchmark import harness

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123, "busy_s": 1.5, "window_s": 2.0}
    line = json.loads(harness.result_line(
        correct=True, attempted=4, failed=0,
        metrics={"setup_s": {"value": 1, "unit": "s", "extra": "dropped"}},
        device=device,
        breakdown={"device_ops": [["a", 1.0]] * 12, "idle_gaps": []}))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    assert line["compared"] == {}
    assert line["metrics"] == {"setup_s": {"value": 1.0, "unit": "s"}}
    assert line["device"] == device
    assert len(line["breakdown"]["device_ops"]) == 10
    plain = json.loads(harness.result_line(
        correct=False, attempted=1, failed=1, metrics={},
        device={k: device[k] for k in harness.DEVICE_KEYS}))
    assert list(plain) == [*harness.RESULT_KEYS, "compared"]
    with pytest.raises(harness.BenchmarkError):
        harness.result_line(correct=True, attempted=1, failed=0, metrics={},
                            device={"platform": "tpu"})


def test_each_number_compared_stands_beside_its_limit(capsys):
    """In the result line under a key of its own that comes last, and as the
    last lines of standard error."""
    from benchmark import harness

    ok, numbers = harness.comparison([("gap_max", 0.25, 0.1),
                                      ("compiles_in_window", 0, 0),
                                      ("gap_mean", float("nan"), 1.0)])
    assert not ok and [n["ok"] for n in numbers] == [False, True, False]
    line = harness.result_line(
        correct=ok, attempted=3, failed=0, metrics={}, numbers=numbers[:2],
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1})
    assert list(json.loads(line))[-1] == "compared"
    assert json.loads(line)["compared"] == {
        "gap_max": {"value": 0.25, "limit": 0.1},
        "compiles_in_window": {"value": 0, "limit": 0}}
    harness.say_compared(numbers[:2])
    assert capsys.readouterr().err.splitlines() == [
        "compared gap_max = 0.25 (limit 0.1) NOT CORRECT",
        "compared compiles_in_window = 0 (limit 0)"]
