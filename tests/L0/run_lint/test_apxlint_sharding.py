"""Sharding-tier (APX7xx) tests.

Same three layers as the trace tier:

- known-bad / known-clean *entry* pairs: every APX701-704 verifier must
  fire on a rule table or builder that seeds exactly its contract
  violation and stay silent on the minimally-different clean twin;
- a seeded-bug meta-test: a scratch copy of ``apex_tpu.partition.tables``
  gets one rule's tensor axis textually flipped, is imported under a
  throwaway name, and APX702 must fire — while the unmodified table
  stays silent under the identical harness;
- the repo registry itself must be populated and clean (including the
  dp2 x tp2 ZeRO step gated against the committed budgets.json).

Plus the satellites that live in this tier: the ``--codes`` /
``--prune`` CLI surface and the budgets.json prune semantics.
"""

import dataclasses
import importlib.util
import os
import re
import sys

import pytest

jax = pytest.importorskip("jax")
from jax.sharding import PartitionSpec as P  # noqa: E402

from apex_tpu.lint.sharded.registry import (  # noqa: E402
    ShardedEntry, check_repo, repo_entries, run_entries,
)
from apex_tpu.lint.traced.registry import _mesh, _sds  # noqa: E402
from apex_tpu.transformer import parallel_state as ps  # noqa: E402

MOD = "apex_tpu.lint"  # attribution target for synthetic entries


def _codes(entries, manifest=None):
    return [f.code for f in run_entries(entries, manifest=manifest)]


def _msgs(entries, manifest=None):
    return [f.message for f in run_entries(entries, manifest=manifest)]


def _rule_entry(name, rules, trees, **kw):
    return ShardedEntry(name, MOD, rules=lambda: rules,
                        trees=lambda: trees, **kw)


def _build_entry(name, build, *, tp=2, n_devices=4, **kw):
    return ShardedEntry(name, MOD, rules=lambda: (), build=build,
                        mesh=_mesh(tp=tp, n_devices=n_devices),
                        min_devices=n_devices, **kw)


def _skip_if_few_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")


# ---------------------------------------------------------------------------
# APX701 — rule-table coverage and spec sanity
# ---------------------------------------------------------------------------

def test_apx701_uncovered_leaf():
    trees = {"params": {"a": _sds((4,), "float32"),
                        "b": _sds((4,), "float32")}}
    msgs = _msgs([_rule_entry("t", (("a$", P(None)),), trees)])
    assert len(msgs) == 1 and "no rule matches" in msgs[0], msgs
    assert "'b'" in msgs[0]


def test_apx701_overlapping_rules():
    trees = {"params": {"a": _sds((4,), "float32"),
                        "b": _sds((4,), "float32")}}
    rules = (("a", P(None)), ("a|b", P(None)))
    msgs = _msgs([_rule_entry("t", rules, trees)])
    assert len(msgs) == 1 and "first-match-wins" in msgs[0], msgs


def test_apx701_dead_rule():
    trees = {"params": {"a": _sds((4,), "float32")}}
    rules = (("a", P(None)), ("zz", P(None)))
    msgs = _msgs([_rule_entry("t", rules, trees)])
    assert len(msgs) == 1 and "dead rule" in msgs[0], msgs


def test_apx701_spec_outranks_array():
    trees = {"params": {"a": _sds((4,), "float32")}}
    msgs = _msgs([_rule_entry("t", (("a", P("model", None)),), trees)])
    assert len(msgs) == 1 and "rank" in msgs[0], msgs


def test_apx701_axis_sanity_is_tree_independent():
    rules = (("a", P("tensor")),          # no such mesh axis
             ("b", P("model", "model")),  # same axis twice in one spec
             ("c(", P(None)))             # unparseable pattern
    msgs = _msgs([_rule_entry("t", rules, {})])
    assert len(msgs) == 3, msgs
    assert "do not exist" in msgs[0]
    assert "repeats" in msgs[1]
    assert "not a valid regex" in msgs[2]


def test_apx701_clean_table():
    trees = {"params": {"a": _sds((4,), "float32"), "b": _sds((), "float32")}}
    rules = (("a$", P("model")), ("b$", P()))
    assert _codes([_rule_entry("t", rules, trees)]) == []


# ---------------------------------------------------------------------------
# APX702 — cross-tree consistency
# ---------------------------------------------------------------------------

def test_apx702_root_anchored_rule_breaks_optimizer_families():
    # "^w$" matches the param path but not "m/w" / "v/w"; the fallthrough
    # rule replicates — exactly the drift the family re-match exists for
    rules = (("^w$", P("model")), ("/w$", P(None)))
    trees = {"params": {"w": _sds((4,), "float32")},
             "aux": {"box": {"w": _sds((4,), "float32")}}}
    findings = run_entries([_rule_entry("t", rules, trees,
                                        optimizer_families=("m", "v"))])
    assert [f.code for f in findings] == ["APX702", "APX702"], \
        [f.render() for f in findings]
    assert "optimizer family 'm'" in findings[0].message
    assert "shard differently" in findings[0].message


def test_apx702_unanchored_table_keeps_families_consistent():
    rules = (("w$", P("model")),)
    trees = {"params": {"w": _sds((4,), "float32")}}
    assert _codes([_rule_entry("t", rules, trees,
                               optimizer_families=("m", "v", "master"))]) == []


def _kv_trees():
    return {"params": {"qkv": {"kernel": _sds((4, 8), "float32")}},
            "kv_cache": {"k": _sds((2, 2, 2), "bfloat16"),
                         "v": _sds((2, 2, 2), "bfloat16"),
                         "lengths": _sds((2,), "int32")}}


def _kv_rules(cache_spec):
    return (("qkv/kernel$", P(None, "model")),
            (r"(^|/)(k|v)$", cache_spec),
            ("lengths$", P()))


def test_apx702_kv_head_axis_must_match_qkv():
    bad = _rule_entry("t", _kv_rules(P(None, None, None)), _kv_trees(),
                      kv_cache_tree="kv_cache")
    msgs = _msgs([bad])
    assert len(msgs) == 1 and "head axes" in msgs[0], msgs
    clean = _rule_entry("t", _kv_rules(P(None, "model", None)), _kv_trees(),
                        kv_cache_tree="kv_cache")
    assert _codes([clean]) == []


def test_apx702_kv_k_and_v_must_shard_alike():
    rules = (("qkv/kernel$", P(None, "model")),
             (r"(^|/)k$", P(None, "model", None)),
             (r"(^|/)v$", P(None, None, "model")),
             ("lengths$", P()))
    msgs = _msgs([_rule_entry("t", rules, _kv_trees(),
                              kv_cache_tree="kv_cache")])
    assert len(msgs) == 1 and "!= v spec" in msgs[0], msgs


def test_apx702_reference_spec_mismatch():
    trees = {"params": {"w": _sds((4, 4), "float32")}}
    rules = (("w$", P("model", None)),)
    bad = _rule_entry("t", rules, trees,
                      reference_specs=lambda: {"params":
                                               {"w": P(None, "model")}})
    msgs = _msgs([bad])
    assert len(msgs) == 1 and "hand-maintained reference" in msgs[0], msgs
    clean = _rule_entry("t", rules, trees,
                        reference_specs=lambda: {"params":
                                                 {"w": P("model", None)}})
    assert _codes([clean]) == []


# ---------------------------------------------------------------------------
# APX703 — rule-derived specs must survive into the staged program
# ---------------------------------------------------------------------------

def _b703_stale_in_specs():
    def body(x):
        return x * 2.0

    # wired with a stale hand-written spec; the table derives tensor-
    # sharded for this operand
    fn = ps.shard_map(body, in_specs=(P(ps.DATA_AXIS, None),),
                      out_specs=P(ps.DATA_AXIS, None))
    return fn, (_sds((8, 8), "float32"),), (P(ps.TENSOR_AXIS, None),)


def _b703_aligned():
    def body(x):
        return x * 2.0

    specs = (P(ps.DATA_AXIS, None),)
    fn = ps.shard_map(body, in_specs=specs, out_specs=specs[0])
    return fn, (_sds((8, 8), "float32"),), specs


def _b703_never_mapped():
    fn = lambda x: x * 2.0
    return fn, (_sds((8,), "float32"),), (P(ps.DATA_AXIS),)


def _b703_replicated_w():
    def body(x, w):
        return x @ w.T  # the transpose must keep the taint on the dot

    specs = (P(ps.DATA_AXIS, None), P())
    fn = ps.shard_map(body, in_specs=specs,
                      out_specs=P(ps.DATA_AXIS, None))
    return fn, (_sds((8, 32), "float32"), _sds((32, 32), "float32")), specs


def _b703_sharded_w():
    def body(x, w):
        return x @ w

    specs = (P(ps.DATA_AXIS, None), P(None, ps.TENSOR_AXIS))
    fn = ps.shard_map(body, in_specs=specs,
                      out_specs=P(ps.DATA_AXIS, ps.TENSOR_AXIS))
    return fn, (_sds((8, 32), "float32"), _sds((32, 32), "float32")), specs


def test_apx703_in_names_disagree_with_table():
    _skip_if_few_devices(4)
    findings = run_entries([_build_entry("stale", _b703_stale_in_specs)])
    assert [f.code for f in findings] == ["APX703"], \
        [f.render() for f in findings]
    assert "does not shard what the table says" in findings[0].message
    assert _codes([_build_entry("ok", _b703_aligned)]) == []


def test_apx703_in_specs_never_applied():
    _skip_if_few_devices(4)
    msgs = _msgs([_build_entry("unmapped", _b703_never_mapped)])
    assert len(msgs) == 1 and "never applied" in msgs[0], msgs


def test_apx703_silently_replicated_matmul_operand():
    _skip_if_few_devices(4)
    # (32, 32) fp32 = 4 KiB; the floor is lowered so the fixture stays tiny
    findings = run_entries([_build_entry("repl", _b703_replicated_w,
                                         replication_floor=1024)])
    assert [f.code for f in findings] == ["APX703"], \
        [f.render() for f in findings]
    assert "fully replicated" in findings[0].message
    assert "dot_general" in findings[0].message
    assert _codes([_build_entry("shard", _b703_sharded_w,
                                replication_floor=1024)]) == []


# ---------------------------------------------------------------------------
# APX704 — per-rank schedule + budgets.json-gated collective volume
# ---------------------------------------------------------------------------

def _b704_divergent():
    def body(x):
        i = jax.lax.axis_index(ps.DATA_AXIS)
        return jax.lax.cond(
            i == 0,
            lambda v: jax.lax.psum(v, ps.DATA_AXIS),
            lambda v: v * 2.0, x)

    specs = (P(ps.DATA_AXIS),)
    fn = ps.shard_map(body, in_specs=specs, out_specs=P(ps.DATA_AXIS))
    return fn, (_sds((8, 4), "float32"),), specs


def _b704_uniform():
    def body(x):
        return jax.lax.psum(x, ps.DATA_AXIS)

    specs = (P(ps.DATA_AXIS),)
    fn = ps.shard_map(body, in_specs=specs, out_specs=P())
    return fn, (_sds((8, 4), "float32"),), specs


def test_apx704_divergent_generated_schedule():
    _skip_if_few_devices(2)
    findings = run_entries([_build_entry("div", _b704_divergent,
                                         tp=1, n_devices=2)])
    assert [f.code for f in findings] == ["APX704"], \
        [f.render() for f in findings]
    assert "rule-generated schedule" in findings[0].message
    assert _codes([_build_entry("uni", _b704_uniform,
                                tp=1, n_devices=2)]) == []


def test_apx704_budget_row_gates_collective_volume():
    _skip_if_few_devices(2)
    e = _build_entry("vol", _b704_uniform, tp=1, n_devices=2,
                     budget_name="synthetic_vol")
    # no committed record: the entry demands one
    msgs = _msgs([e], manifest={"version": 1, "entries": {}})
    assert len(msgs) == 1 and "no budgets.json record" in msgs[0], msgs
    # a record with the wrong volume fires ...
    manifest = {"version": 1,
                "entries": {"synthetic_vol": {"collective_bytes": 1}}}
    findings = run_entries([e], manifest=manifest)
    assert [f.code for f in findings] == ["APX704"], \
        [f.render() for f in findings]
    m = re.search(r"staged collective volume (\d+) B", findings[0].message)
    assert m and int(m.group(1)) > 0
    # ... and pinning the measured volume goes clean
    manifest["entries"]["synthetic_vol"]["collective_bytes"] = int(m.group(1))
    assert _codes([e], manifest=manifest) == []


# ---------------------------------------------------------------------------
# seeded-bug meta-test over a scratch copy of the real table
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


def test_seeded_qkv_axis_flip_fires_apx702(tmp_path):
    from apex_tpu.partition import tables

    seeded = _scratch_import(
        tables.__file__,
        lambda t: t.replace('("layers/qkv/kernel", P(None, None, t)),',
                            '("layers/qkv/kernel", P(None, t, None)),'),
        tmp_path, "tables_seeded_apx702")

    base = next(e for e in repo_entries() if e.name == "gpt_tiny_rules")
    bad = dataclasses.replace(base, name="gpt_seeded",
                              rules=seeded.gpt_rules)
    findings = run_entries([bad])
    # the flip drifts from the hand reference AND orphans the KV cache's
    # head axis — both are APX702, nothing else fires
    assert findings and {f.code for f in findings} == {"APX702"}, \
        [f.render() for f in findings]
    # identical harness, unmodified table: silent
    assert _codes([base]) == []


# ---------------------------------------------------------------------------
# registry + engine integration
# ---------------------------------------------------------------------------

def test_sharded_registry_populated_and_clean():
    names = {e.name for e in repo_entries()}
    assert {"gpt_tiny_rules", "gpt_tiny_dp2xtp2_zero"} <= names, names
    findings = check_repo()
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# budgets.json prune semantics (--write-budgets --prune)
# ---------------------------------------------------------------------------

class _Rep:
    def __init__(self, entry):
        self.entry = entry
        self.hbm_total_bytes = 10
        self.collective_bytes = 5
        self.peak_live_bytes = 3


def test_budgets_prune_drops_only_stale_rows():
    from apex_tpu.lint.traced import budgets

    stale_row = {"hbm_bytes": 9, "hbm_ceiling": 9, "collective_bytes": 9,
                 "peak_live_bytes": 9, "peak_live_cap": 9}
    prev = {"version": 1, "tolerance": 0.1,
            "entries": {"kept": {"hbm_bytes": 1, "hbm_ceiling": 100,
                                 "collective_bytes": 1,
                                 "peak_live_bytes": 1, "peak_live_cap": 100},
                        "stale": dict(stale_row)}}
    reports = [_Rep("kept")]
    carried = budgets.build_manifest(reports, previous=prev)
    assert carried["entries"]["stale"] == stale_row  # verbatim by default
    pruned = budgets.build_manifest(reports, previous=prev, prune=True)
    assert set(pruned["entries"]) == {"kept"}
    assert budgets.pruned_names(reports, prev) == ["stale"]
    assert budgets.pruned_names(reports, None) == []


# ---------------------------------------------------------------------------
# CLI surface: --codes and --prune
# ---------------------------------------------------------------------------

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_cli_codes_selects_matched_codes_only():
    from apex_tpu.lint.__main__ import main

    bad = os.path.join(FIXTURES, "apx101_bad.py")
    # the fixture's own code is reported ...
    assert main(["--no-trace", "--codes", "APX101", bad]) == 1
    # ... but a file whose findings are all outside the subset goes clean
    other = os.path.join(FIXTURES, "apx401_bad.py")
    assert main(["--no-trace", "--codes", "APX101", other]) == 0


def test_cli_codes_rejects_unknown_pattern(capsys):
    from apex_tpu.lint.__main__ import main

    assert main(["--no-trace", "--codes", "APX97*"]) == 2
    assert "matches no known code" in capsys.readouterr().err


def test_cli_prune_requires_write_budgets(capsys):
    from apex_tpu.lint.__main__ import main

    assert main(["--prune"]) == 2
    assert "--write-budgets" in capsys.readouterr().err
