"""Contract smoke for bench.py's PARENT mode — the orchestration layer
(config ORDER, per-config subprocesses, budget handling, headline
re-emission, exit codes) — pinned on the CPU. Every child is held to the
CPU with ``JAX_PLATFORMS``: this pytest process has a backend of its own,
and a chip belongs to one process at a time."""

import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


def _bench(*argv, timeout, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *argv],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


def test_parent_runs_headline_first_and_reemits_it_last():
    # test timeout exceeds the parent's budget + caps so a hung child
    # surfaces as the parent's own cap/skip lines, not TimeoutExpired
    r = _bench(timeout=450, JAX_NUM_CPU_DEVICES="8",
               BENCH_ONLY="headline,layer_norm", BENCH_BUDGET_S="300")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = _json_lines(r.stdout)
    metrics = [d.get("metric") for d in lines]
    # the headline config emits its per-(batch, state-mode) sweep lines
    # first, then the headline metric — so the first NON-sweep metric is
    # the headline; measured values present, no error lines
    main = [m for m in metrics if not m.startswith("headline_")]
    assert main[0] == "bert_tiny_cpu_smoke", metrics
    assert "fused_layer_norm_fwdbwd_h1024" in metrics, metrics
    assert not any("error" in d for d in lines), lines
    # both optimizer-state modes raced, winner in the headline line
    assert any(m.endswith("_fp32") for m in metrics), metrics
    assert any(m.endswith("_bf16m_castout") for m in metrics), metrics
    head = [d for d in lines if d["metric"] == "bert_tiny_cpu_smoke"]
    assert head[0]["state_mode"] in ("fp32", "bf16m_castout"), head
    # the headline metric is re-emitted LAST (parse-the-tail convention)
    assert metrics[-1] == "bert_tiny_cpu_smoke", metrics
    assert len(head) == 2
    assert lines[-1]["value"] > 0


def test_failing_config_exits_nonzero():
    """A config that raises ends in a non-zero exit, alone and under the
    parent. Three CPU devices make ``tp_gpt`` raise before any compile
    (gpt_tiny's 8 heads do not divide over tp=3)."""
    r = _bench("tp_gpt", timeout=120, JAX_NUM_CPU_DEVICES="3")
    assert r.returncode != 0
    assert "not divisible by tp 3" in r.stderr, r.stderr[-2000:]
    assert not _json_lines(r.stdout), r.stdout

    r = _bench(timeout=120, JAX_NUM_CPU_DEVICES="3", BENCH_ONLY="tp_gpt")
    assert r.returncode != 0
    (line,) = _json_lines(r.stdout)
    assert line["metric"] == "tp_gpt"
    assert "not divisible by tp 3" in line["error"], line

    # a name that is no config at all is an error too, not a full run
    r = _bench("nope", timeout=120)
    assert r.returncode != 0 and "unknown config" in r.stderr
    r = _bench(timeout=120, BENCH_ONLY="nope")
    assert r.returncode != 0 and "unknown BENCH_ONLY config" in r.stdout


def test_parent_branch_initialises_no_backend():
    """The parent starts one child per config and must leave the chip to
    them: after a whole parent pass (here over an unknown name, so no
    child runs) no jax backend is initialised in the parent process."""
    code = (
        "import sys; sys.argv = ['bench.py']\n"
        "import bench\n"
        "rc = bench.main()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "sys.exit(0 if rc == 1 else 2)\n")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_ONLY="nope"))
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]


def test_ab_mode_contract():
    """`bench.py ab <pair>` — the same-process A/B instrument's output
    contract (ratio + band + absolute medians), pinned on the cheapest
    pair."""
    r = _bench("ab", "ln_h1024", timeout=450, JAX_NUM_CPU_DEVICES="1")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = _json_lines(r.stdout)
    assert [d["metric"] for d in lines] == ["ab_ln_h1024"], lines
    d = lines[0]
    lo, hi = d["band"]
    assert lo <= d["value"] <= hi, d
    assert d["a_us"] > 0 and d["b_us"] > 0
    assert d["a_wins"] == (d["value"] < 1.0)
    # an unknown pair name is a usage error
    r2 = _bench("ab", "nope", timeout=120, JAX_NUM_CPU_DEVICES="1")
    assert r2.returncode != 0
    assert "unknown ab pair" in r2.stderr
