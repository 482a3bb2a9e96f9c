"""Cost-tier (APX6xx) tests.

Four layers, per the tier's contract:

- interpreter unit tests: exact read/write/flop/peak accounting on
  tiny synthetic programs, donation crediting (a donated cache counts
  once plus its in-place update delta), and the collective-volume fold
  over APX511 footprints;
- known-bad / known-clean pairs per code: a manifest is built from a
  clean report and each of APX601-604 must fire on a minimally-
  regressed variant while the clean twin stays silent;
- manifest plumbing: round-trip through ``--write-budgets``'s writer,
  schema validation, and hand-tightened ceilings surviving regen;
- the repo itself: every registered entry must cost-analyze, the
  committed budgets.json must gate them clean, and the medium decode
  entry must agree with the hand roofline (params + parked K/V) within
  10%.
"""

import dataclasses
import importlib.util
import os
import sys

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from apex_tpu.lint.traced import budgets, cost  # noqa: E402
from apex_tpu.lint.traced.registry import _sds  # noqa: E402


def _report(fn, args, entry="syn", path="mod.py"):
    return cost.compute(jax.make_jaxpr(fn)(*args), path, entry)


def _codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# interpreter units
# ---------------------------------------------------------------------------

def test_read_write_flops_exact():
    rep = _report(lambda x, y: x @ y,
                  (_sds((128, 64), "float32"), _sds((64, 32), "float32")))
    assert rep.read_bytes == (128 * 64 + 64 * 32) * 4
    assert rep.write_bytes == 128 * 32 * 4
    assert rep.delta_write_bytes == 0
    assert rep.flops == 2 * 128 * 32 * 64
    # everything lives at once: both operands plus the product
    assert rep.peak_live_bytes == rep.read_bytes + rep.write_bytes
    assert rep.collective_bytes == 0 and rep.per_collective == {}


def test_operands_charged_once():
    # x feeds two consumers — the roofline charges its bytes ONCE
    rep = _report(lambda x: (x * 2.0, x + 1.0),
                  (_sds((256, 128), "float32"),))
    assert rep.read_bytes == 256 * 128 * 4
    assert rep.write_bytes == 2 * 256 * 128 * 4


def test_donated_cache_counts_once():
    def step(cache, x):
        cache = jax.lax.dynamic_update_slice(cache, x, (0, 0))
        return cache, jnp.sum(x)

    args = (_sds((1024, 1024), "float32"), _sds((1, 1024), "float32"))
    donated = _report(jax.jit(step, donate_argnums=0), args)
    plain = _report(jax.jit(step), args)

    cache_b, row_b = 1024 * 1024 * 4, 1024 * 4
    # both read the full cache + the update row
    assert donated.read_bytes == plain.read_bytes == cache_b + row_b
    # donation: the cache output is absorbed, only the dus row is
    # written in place (plus the 4-byte scalar)
    assert donated.write_bytes == 4
    assert donated.delta_write_bytes == row_b
    # no donation: the updated cache is a full second buffer
    assert plain.write_bytes == cache_b + 4
    assert plain.delta_write_bytes == 0
    assert plain.hbm_total_bytes > donated.hbm_total_bytes
    # ...and peak-live sees the second buffer too
    assert plain.peak_live_bytes >= donated.peak_live_bytes + cache_b


def test_scan_multiplies_flops():
    def fn(w, xs):
        def body(c, x):
            return c, x @ w
        return jax.lax.scan(body, 0.0, xs)[1]

    rep = _report(fn, (_sds((16, 16), "float32"),
                       _sds((8, 4, 16), "float32")))
    assert rep.flops == 8 * (2 * 4 * 16 * 16)


def test_fold_footprint_pricing():
    coll = {}
    fp = [
        ("coll", "psum", ("tp",), None, 512),
        ("scan", 3, [
            ("coll", "ppermute", ("pp",), ([(0, 1), (1, 0)],), 128),
        ]),
        ("while",
         [("coll", "all_gather", ("tp",), None, 64)],
         [("coll", "psum", ("tp",), None, 32)]),
    ]
    cost._fold_footprint(fp, 2, {"tp": 4, "pp": 8}, coll)
    assert coll == {
        "psum": 2 * 512 * 4 + 2 * 32 * 4,      # bytes x axis size
        "ppermute": 2 * 3 * 128 * 2,           # bytes x hop count x scan
        "all_gather": 2 * 64 * 4,
    }


# ---------------------------------------------------------------------------
# APX601-604 — known-bad / known-clean against a built manifest
# ---------------------------------------------------------------------------

def _clean_and_manifest():
    rep = _report(lambda x: x * 2.0, (_sds((512, 128), "float32"),))
    return rep, budgets.build_manifest([rep])


def test_budget_clean_twin_silent():
    rep, manifest = _clean_and_manifest()
    assert budgets.check([rep], manifest) == []


def test_apx601_apx602_traffic_regression():
    rep, manifest = _clean_and_manifest()
    # same entry name, twice the traffic: over the 1.25x ceiling AND
    # outside the 10% drift band
    fat = _report(lambda x: (x * 2.0, x + 1.0),
                  (_sds((512, 128), "float32"),))
    findings = budgets.check([fat], manifest)
    # doubling the output also doubles what's live, so the peak cap
    # trips alongside the traffic ceiling and the drift band
    assert _codes(findings) == ["APX601", "APX602", "APX604"]
    assert "ceiling" in findings[0].message
    # a within-band wiggle (< 10%, < ceiling) stays silent on both
    small = dataclasses.replace(
        rep, write_bytes=rep.write_bytes + rep.hbm_total_bytes // 20)
    assert budgets.check([small], manifest) == []


def test_apx603_collective_mismatch_is_exact():
    rep, manifest = _clean_and_manifest()
    moved = dataclasses.replace(rep, per_collective={"psum": 64})
    findings = budgets.check([moved], manifest)
    assert _codes(findings) == ["APX603"]
    assert "psum" not in manifest["entries"]  # volume-only contract


def test_apx604_peak_live_over_cap():
    rep, manifest = _clean_and_manifest()
    cap = manifest["entries"][rep.entry]["peak_live_cap"]
    hot = dataclasses.replace(rep, peak_live_bytes=cap + 1)
    assert _codes(budgets.check([hot], manifest)) == ["APX604"]


def test_apx602_missing_entry_and_stale_manifest():
    rep, manifest = _clean_and_manifest()
    new = dataclasses.replace(rep, entry="unbudgeted")
    findings = budgets.check([new, rep], manifest)
    assert _codes(findings) == ["APX602"]
    assert "unbudgeted" in findings[0].message

    stale = budgets.check([], manifest)
    assert _codes(stale) == ["APX602"]
    assert "no longer registered" in stale[0].message
    assert stale[0].path.endswith("budgets.json")


def test_apx602_missing_or_malformed_manifest():
    rep, _ = _clean_and_manifest()
    missing = budgets.check([rep], None)
    assert _codes(missing) == ["APX602"]
    assert "--write-budgets" in missing[0].message

    bad = budgets.check([rep], {"version": 2, "entries": 3})
    assert _codes(bad) == ["APX602"]
    assert "schema" in bad[0].message


# ---------------------------------------------------------------------------
# manifest plumbing
# ---------------------------------------------------------------------------

def test_manifest_roundtrip_and_ceiling_preservation(tmp_path):
    rep, _ = _clean_and_manifest()
    path = os.path.join(str(tmp_path), "budgets.json")
    manifest = budgets.write_manifest([rep], path=path)
    assert budgets.validate(manifest) == []
    loaded = budgets.load_manifest(path)
    assert loaded == manifest
    assert budgets.check([rep], loaded, path=path) == []
    row = loaded["entries"][rep.entry]
    assert row["hbm_bytes"] == rep.hbm_total_bytes
    assert row["hbm_ceiling"] == int(rep.hbm_total_bytes * 1.25)

    # a reviewer tightens the ceiling by hand: regeneration keeps it
    loaded["entries"][rep.entry]["hbm_ceiling"] = 7
    import json
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(loaded, fh)
    regen = budgets.write_manifest([rep], path=path)
    assert regen["entries"][rep.entry]["hbm_ceiling"] == 7


def test_committed_manifest_is_valid():
    manifest = budgets.load_manifest()
    assert manifest is not None, "budgets.json must be committed"
    assert budgets.validate(manifest) == []


# ---------------------------------------------------------------------------
# the repo registry under the committed budgets
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def repo_reports():
    from apex_tpu.lint.traced import (
        ensure_cpu_devices, repo_entries, run_entries,
    )
    ensure_cpu_devices()
    reports = []
    findings = run_entries(repo_entries(), run_checks=False,
                           cost_out=reports)
    assert findings == [], "\n".join(f.render() for f in findings)
    return reports


def test_repo_costs_clean_under_committed_budgets(repo_reports):
    assert len(repo_reports) >= 23
    findings = budgets.check(repo_reports, budgets.load_manifest())
    assert findings == [], "\n".join(f.render() for f in findings)


def test_medium_decode_matches_hand_roofline(repo_reports):
    """The decode ceiling by hand: every param byte plus the K/V rows the
    ragged ladder holds, page by page, per step. The interpreter must
    land within 10% of that independent derivation."""
    rep = {r.entry: r for r in repo_reports}[
        "gpt_paged_decode_step_medium_ragged"]

    from apex_tpu.models.gpt import GPTConfig, init_gpt
    cfg = GPTConfig(use_rope=True)
    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    param_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params))
    # the registry entry's ladder: 32 slots, uniform 32..512, pages of 64
    pages = sum(-(-(32 + round(i * 480 / 31)) // 64) for i in range(32))
    kv_bytes = pages * 64 * cfg.num_layers * cfg.hidden_size * 2 * 2
    hand = param_bytes + kv_bytes
    assert abs(rep.hbm_total_bytes - hand) / hand < 0.10


# ---------------------------------------------------------------------------
# seeded-bug meta-test: drop the decode cache donation
# ---------------------------------------------------------------------------

def _scratch_import(src_path, transform, tmp_path, name):
    txt = open(src_path, encoding="utf-8").read()
    seeded = transform(txt)
    assert seeded != txt, "seed transform did not apply"
    p = os.path.join(str(tmp_path), name + ".py")
    with open(p, "w", encoding="utf-8") as fh:
        fh.write(seeded)
    spec = importlib.util.spec_from_file_location(name, p)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except Exception:
        sys.modules.pop(name, None)
        raise
    return mod


def test_seeded_donation_removal_fires_apx601(tmp_path):
    """Strip ``donate_argnums=1`` from the decode jit: the KV cache now
    writes a full second buffer every step, which must blow through a
    manifest seeded from the donating version."""
    from apex_tpu.lint.traced.registry import (
        _paged_serving_args, _serving_cfg,
    )
    from apex_tpu.serving import decode

    seeded = _scratch_import(
        decode.__file__,
        lambda t: t.replace(
            "jax.jit(decode, donate_argnums=1)", "jax.jit(decode)"),
        tmp_path, "decode_seeded_apx601")

    # deep enough that the cache dominates the step's traffic (the
    # registry's 2x32 shape is param-bound and wouldn't clear the
    # 1.25x ceiling even doubled)
    cfg = _serving_cfg()
    params, cache = _paged_serving_args(cfg, num_slots=8, max_len=256,
                                        num_pages=8 * 16 + 2)
    args = (params, cache, _sds((8,), "int32"), _sds((8,), "bool"))

    def rep_of(mod):
        closed = jax.make_jaxpr(mod.make_paged_decode_fn(cfg))(*args)
        return cost.compute(closed, "decode.py", "decode_step")

    clean, bad = rep_of(decode), rep_of(seeded)
    assert bad.hbm_total_bytes > clean.hbm_total_bytes
    # the un-donated cache is charged as a full extra write
    cache_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(cache))
    assert bad.write_bytes - clean.write_bytes >= cache_bytes // 2

    manifest = budgets.build_manifest([clean])
    assert budgets.check([clean], manifest) == []
    codes = _codes(budgets.check([bad], manifest))
    assert "APX601" in codes, codes

    sys.modules.pop("decode_seeded_apx601", None)
