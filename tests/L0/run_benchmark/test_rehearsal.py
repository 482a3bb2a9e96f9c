"""Each runner end to end at the tiny ``rehearsal`` sizes on the CPU backend:
every line labelled, no result line; the same run with the timed path broken
underneath comes out not correct; and without a TPU the command refuses."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
try:
    import run as bench_run      # benchmark/run.py
finally:
    sys.path.remove(os.path.join(REPO, "benchmark"))

CELLS = [w["name"] for w in json.load(open(
    os.path.join(REPO, "BENCHMARK.json")))["workloads"] if w["chips"] == 1]


def rehearse(capsys, workload, *extra, stderr=None):
    rc = bench_run.main(["--workload", workload, "--seed", str(2 ** 31 + 7),
                         "--seconds", "2", "--trace", "0", "--cpu-rehearsal",
                         *extra])
    captured = capsys.readouterr()
    if stderr is not None:
        stderr.append(captured.err)
    lines = [json.loads(l) for l in captured.out.splitlines()
             if l.startswith("{")]
    assert rc == 0
    # every line says it is a rehearsal on the CPU, and none is a result
    assert all(l["rehearsal"] is True and l["platform"] == "cpu"
               for l in lines)
    assert not any("correct" in l and "metrics" in l for l in lines)
    last = lines[-1]
    assert last["stage"] == "rehearsal_result"
    return json.loads(last["would_be"]), lines


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_end_to_end(capsys, workload):
    result, lines = rehearse(capsys, workload)
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    cell = bench_run.harness.Cell(workload)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    numbers = [l for l in lines if l.get("stage") == "correct"][0]["numbers"]
    # each number compared is printed beside its limit, and stands in the
    # result under a key of its own that comes last
    assert all({"number", "value", "limit", "ok"} <= set(n) for n in numbers)
    assert {"compiles_in_window"} <= {n["number"] for n in numbers}
    assert list(result)[-1] == "compared" and result["compared"] == {
        n["number"]: {"value": n["value"], "limit": n["limit"]}
        for n in numbers}


SERVING = [w for w in CELLS if bench_run.harness.Cell(w).traffic["kind"]
           == "requests"]


@pytest.mark.parametrize("workload", SERVING)
def test_window_line_says_what_is_left_of_the_backlog(capsys, workload):
    """``backlog_left`` always; where the window drained the backlog (the
    tiny rehearsal backlogs of the two GPT cells; the real ones outlast the
    window twice over) also ``drained_at_s``, and a line on standard error
    that names the traffic file to give more requests."""
    said = []
    result, lines = rehearse(capsys, workload, stderr=said)
    assert SERVING == ["gpt2_medium.offline_decode",
                       "gpt2_medium.prompt_backlog",
                       "olmo_hybrid_7b.long_prompt_decode"]
    [window] = [l for l in lines if l.get("stage") == "window"]
    cell = bench_run.harness.Cell(workload)
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


def test_training_step_that_returns_its_state_unchanged_is_not_correct(
        capsys):
    result, lines = rehearse(capsys, "bert_large.pretrain_s128",
                             "--option", "break_step=1")
    assert result["correct"] is False
    bad = {n["number"] for l in lines if l.get("stage") == "correct"
           for n in l["numbers"] if not n["ok"]}
    assert "update_norm_gap_worst_leaf" in bad


def test_served_tokens_altered_where_they_are_produced_are_not_correct(
        capsys):
    result, lines = rehearse(capsys, "gpt2_medium.offline_decode",
                             "--option", "break_tokens=1")
    assert result["correct"] is False
    bad = {n["number"] for l in lines if l.get("stage") == "correct"
           for n in l["numbers"] if not n["ok"]}
    assert "served_logit_gap_max" in bad


def test_open_loop_mix_kept_for_a_later_cell_still_runs(capsys, tmp_path):
    """``traffic/serve_prompts.json`` (Poisson arrivals on the wall clock) is
    in no cell today: its tails spread too widely at the window the budget
    allows (PERF.md). A later PR adds the cell as an entry only; here the
    entry is added to a copy of the manifest and the runner driven."""
    import argparse
    import shutil

    import jax

    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["workloads"].append({
        "name": "gpt2_medium.serve_prompts", "config": "gpt2_medium",
        "traffic": "serve_prompts", "chips": 1, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    harness = bench_run.harness
    cell = harness.Cell("gpt2_medium.serve_prompts", root=str(tmp_path))
    args = argparse.Namespace(seed=5, seconds=3.0, trace=0,
                              cpu_rehearsal=True, rehearsal_on_chip=False,
                              control=0, option=[])
    ctx = bench_run.Ctx(cell, args, jax.devices()[:1],
                        harness.CompileCounter())
    out = cell.runner().run(ctx)
    capsys.readouterr()
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == round(
        3.0 * cell.traffic["rehearsal"]["arrivals"]["rate_per_s"])
    assert {"ttft_ms_p95", "itl_ms_p95", "setup_s"} <= set(out["values"])
    assert out["counts"]["gen_late_ms"] and out["counts"]["first_delivery"]


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode not in (0, None)
    assert "no CPU fallback" in r.stderr
    # nothing that parses as a result
    assert not any(l.startswith('{"correct"') for l in r.stdout.splitlines())


def test_refuses_to_run_outside_a_checkout(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env=dict(env, JAX_PLATFORMS="cpu"))
    assert r.returncode not in (0, None)
    assert "apex_tpu" in r.stderr
    assert not any(l.startswith('{"correct"') for l in r.stdout.splitlines())
