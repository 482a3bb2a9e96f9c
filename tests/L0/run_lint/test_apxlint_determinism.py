"""APX8xx determinism-tier tests: every code fires on its known-bad
fixture and stays silent on the known-clean twin, suppression works
through the shared engine, the repo itself lints clean with the tier
enabled — and, the load-bearing part, the seeded-bug meta-tests: take
a scratch copy of the REAL scheduler/router/CI matrix, re-introduce
the exact bug class the tier was built for, and assert the checker
catches it (so every code is proven live against production code, not
just against fixtures shaped for it)."""

import os
import shutil
import subprocess
import sys

import pytest

from apex_tpu.lint.engine import lint_paths

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures",
                        "determinism")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _codes(*names, **kw):
    paths = [os.path.join(FIXTURES, n) for n in names]
    findings, n = lint_paths(paths, trace=False, determinism=True, **kw)
    assert n == len(paths) or kw.get("include_fixtures"), \
        f"fixture file(s) not linted: {paths}"
    return [f.code for f in findings]


def _dir_codes(name):
    findings, _ = lint_paths([os.path.join(FIXTURES, name)],
                             trace=False, determinism=True,
                             include_fixtures=True)
    return findings


# ---------------------------------------------------------------------------
# fixture pairs
# ---------------------------------------------------------------------------

def test_apx801_ordering():
    codes = _codes(os.path.join("serving", "apx801_bad.py"))
    # set iteration, comprehension, list(), wall clock, random, hash,
    # set-in-f-string
    assert codes.count("APX801") == 7, codes
    assert _codes(os.path.join("serving", "apx801_clean.py")) == []


def test_apx805_rng_discipline():
    codes = _codes(os.path.join("serving", "apx805_bad.py"))
    # raw PRNGKey, key reuse, split tree
    assert codes.count("APX805") == 3, codes
    assert _codes(os.path.join("serving", "apx805_clean.py")) == []


def test_apx805_batched_fold_is_clean(tmp_path):
    """The seed root read back once per request and folded for all
    slots in one program is the idiom, not a raw key; the same root
    handed to a sampler unfolded still is one."""
    name = os.path.join("serving", "apx805_batched_clean.py")
    assert _codes(name) == []
    src = open(os.path.join(FIXTURES, name)).read()
    serving = tmp_path / "serving"
    serving.mkdir()
    bad = serving / "engine.py"
    bad.write_text(src.replace(
        "self.base[slot] = np.asarray(jax.random.PRNGKey(seed))",
        "self.base[slot] = jax.random.PRNGKey(seed)"))
    findings, _ = lint_paths([str(bad)], trace=False, determinism=True,
                             include_fixtures=True)
    assert [f.code for f in findings] == ["APX805"]


def test_apx803_raise_closure():
    assert _codes(os.path.join("serving", "apx803_bad.py")) \
        == ["APX803"]
    assert _codes(os.path.join("serving", "apx803_clean.py")) == []


def test_apx803_taxonomy_test_coverage():
    findings = _dir_codes("apx803_cov_bad")
    assert [f.code for f in findings] == ["APX803"]
    assert "GhostError" in findings[0].message
    assert _dir_codes("apx803_cov_clean") == []


def test_apx804_observe_coherence():
    findings = _dir_codes("apx804_bad")
    codes = [f.code for f in findings]
    # span attr, begin+end undeclared, instant undeclared, dynamic
    # name, never-created read-back
    assert codes.count("APX804") == 6, \
        "\n".join(f.render() for f in findings)
    assert _dir_codes("apx804_clean") == []


def test_apx802_fault_contracts():
    findings = _dir_codes("apx802_bad")
    rendered = "\n".join(f.render() for f in findings)
    codes = [f.code for f in findings]
    # gamma missing from table, stale_site, AlphaError unknown,
    # beta chaos-ref missing, beta sweep absent from ci + unread,
    # gamma unconsulted + chaos-ref missing, stale CI env
    assert codes.count("APX802") == 9, rendered
    for needle in ("gamma_probe", "stale_site", "AlphaError",
                   "APEX_CHAOS_BETA_SEED", "APEX_CHAOS_STALE_SEED"):
        assert needle in rendered, f"missing {needle}:\n{rendered}"
    assert _dir_codes("apx802_clean") == []


def test_suppression_through_shared_engine():
    assert _codes(os.path.join("serving", "suppressed_det.py")) == []


def test_fixtures_skipped_without_flag():
    # tick-path rules only apply inside a `serving` directory; the
    # fixture marker keeps the whole tree out of directory walks
    findings, n = lint_paths([FIXTURES], trace=False, determinism=True)
    assert n == 0 and findings == []


def test_repo_lints_determinism_clean():
    findings, n_files = lint_paths(
        [os.path.join(REPO, "apex_tpu"), os.path.join(REPO, "tests")],
        trace=False, determinism=True)
    assert n_files > 100
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# seeded-bug meta-tests: re-introduce the real bug class in a scratch
# copy of the production code and prove the checker catches it
# ---------------------------------------------------------------------------

SERVING = os.path.join(REPO, "apex_tpu", "serving")


def _scratch_serving(tmp_path):
    dst = tmp_path / "apex_tpu" / "serving"
    shutil.copytree(SERVING, dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def _apx8(paths, code):
    findings, _ = lint_paths([str(p) for p in paths], trace=False,
                             determinism=True, select=(code,))
    return findings


def _mutate(path, old, new):
    src = path.read_text()
    assert src.count(old) == 1, f"mutation anchor drifted: {old!r}"
    path.write_text(src.replace(old, new))


def test_seeded_unsorted_requeue_caught(tmp_path):
    """Un-sort the chunked-prefill progress loop in a scratch copy of
    the REAL scheduler — the PR-8-review bug class — and APX801 must
    fire at that line."""
    dst = _scratch_serving(tmp_path)
    assert _apx8([dst], "APX801") == []  # scratch baseline is clean
    _mutate(dst / "scheduler.py",
            "for rid in sorted(progressed):",
            "for rid in progressed:")
    findings = _apx8([dst], "APX801")
    assert len(findings) == 1, [f.render() for f in findings]
    assert findings[0].path.endswith("scheduler.py")
    assert "_prefill_phase" in findings[0].message


def test_seeded_unordered_routing_key_caught(tmp_path):
    """Replace the router's deterministic load-key pick with an
    arbitrary set materialization in a scratch copy — routing order
    becomes hash-dependent — and APX801 must fire."""
    dst = _scratch_serving(tmp_path)
    _mutate(dst / "router.py",
            "return self._note_route(min(cands, key=self._load_key))",
            "return self._note_route(list(set(cands))[0])")
    findings = _apx8([dst], "APX801")
    assert len(findings) == 1, [f.render() for f in findings]
    assert findings[0].path.endswith("router.py")
    assert "_route_prefill" in findings[0].message


def test_seeded_dropped_ci_matrix_leg_caught(tmp_path):
    """Drop APEX_CHAOS_POOL_SEED from a scratch copy of the CI chaos
    matrix — the reshard/pool sites silently lose their sweep — and
    APX802 must name every orphaned site."""
    _scratch_serving(tmp_path)
    tests_dst = tmp_path / "tests"
    shutil.copytree(os.path.join(REPO, "tests", "L0", "run_serving"),
                    tests_dst / "run_serving",
                    ignore=shutil.ignore_patterns("__pycache__"))
    ci_dst = tmp_path / ".github" / "workflows"
    ci_dst.mkdir(parents=True)
    shutil.copy(os.path.join(REPO, ".github", "workflows", "ci.yml"),
                ci_dst / "ci.yml")

    scope = [tmp_path / "apex_tpu" / "serving"]
    assert _apx8(scope, "APX802") == []  # scratch baseline is clean

    ci = ci_dst / "ci.yml"
    src = ci.read_text()
    lines = [l for l in src.splitlines()
             if "APEX_CHAOS_POOL_SEED" not in l]
    assert len(lines) < len(src.splitlines())
    ci.write_text("\n".join(lines))

    findings = _apx8(scope, "APX802")
    rendered = "\n".join(f.render() for f in findings)
    for site in ("reshard_send", "reshard_recv", "pool_route"):
        assert site in rendered, rendered
    assert "APEX_CHAOS_POOL_SEED" in rendered


# ---------------------------------------------------------------------------
# CLI surface: --codes APX8* enables the tier end-to-end
# ---------------------------------------------------------------------------

def test_cli_codes_apx8_glob_enables_tier():
    from apex_tpu.lint.__main__ import main

    bad = os.path.join(FIXTURES, "serving", "apx801_bad.py")
    # the glob both enables --determinism and narrows the report
    assert main(["--no-trace", "--codes", "APX8*",
                 "--include-fixtures", bad]) == 1
    # without the tier the same file goes clean (no APX8xx run at all)
    assert main(["--no-trace", "--include-fixtures", bad]) == 0


def test_cli_determinism_flag(capsys):
    from apex_tpu.lint.__main__ import main

    bad = os.path.join(FIXTURES, "serving", "apx805_bad.py")
    assert main(["--no-trace", "--determinism",
                 "--include-fixtures", bad]) == 1
    assert "APX805" in capsys.readouterr().out
    clean = os.path.join(FIXTURES, "serving", "apx805_clean.py")
    assert main(["--no-trace", "--determinism",
                 "--include-fixtures", clean]) == 0


def test_cli_codes_unknown_apx8_pattern(capsys):
    from apex_tpu.lint.__main__ import main

    assert main(["--no-trace", "--codes", "APX87*"]) == 2
    assert "matches no known code" in capsys.readouterr().err


@pytest.mark.slow
def test_cli_module_invocation_budget():
    """`python -m apex_tpu.lint --determinism` over the repo: clean,
    and inside the 15s acceptance budget (cold interpreter included)."""
    import time

    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.lint", "--determinism",
         "--no-trace", "apex_tpu", "tests"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 15.0, f"lint took {elapsed:.1f}s"
