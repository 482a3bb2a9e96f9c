"""APX701/APX702 — partition-rule table coverage and cross-tree drift.

APX701 is the table's own contract: over the union of an entry's
registered abstract trees, every non-scalar leaf is matched by exactly
one rule, every matched spec fits its array (rank <= ndim), every mesh
axis a spec names exists on the canonical mesh and appears at most once
per spec, and every rule matches at least one leaf (a dead rule is a
typo'd pattern silently replicating whatever it was meant to shard —
the exact failure mode ``match_partition_rules``'s unmatched-leaf error
exists to kill, one step earlier).

APX702 is everything the repo *derives* from the table staying
identical per tensor family: optimizer moments / master weights
(re-matched under an ``m/``-, ``v/``-, ``master/``-prefixed path, so a
root-anchored pattern shows up as drift), the serving KV cache's head
axis against the attention qkv weights' tensor-parallel axis, and the
rule-derived spec tree against the hand-maintained reference
(``gpt_partition_specs``) where one is
registered. A flipped axis in one rule fires here before it ever
reaches a pod slice.
"""

import re
from typing import List, Optional

from jax.sharding import PartitionSpec

from apex_tpu.lint import Finding


def _flat_specs(tree):
    import jax

    from apex_tpu.partition import tree_path_name

    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
    return [(tree_path_name(path), spec) for path, spec in flat]


def _safe_match(rules, tree) -> Optional[object]:
    from apex_tpu.partition import match_partition_rules

    try:
        return match_partition_rules(rules, tree)
    except ValueError:
        return None  # uncovered leaves: already an APX701 finding


def check(entry, path: str) -> List[Finding]:
    from apex_tpu.partition import (
        optimizer_state_specs, rule_match_table, spec_axis_names,
    )
    from apex_tpu.transformer import parallel_state as ps

    rules = tuple(entry.rules())
    findings: List[Finding] = []

    # -- APX701: per-rule spec sanity (tree-independent) ------------------
    known_axes = set(ps.MESH_AXIS_NAMES)
    for i, (pattern, spec) in enumerate(rules):
        try:
            re.compile(pattern)
        except re.error as exc:
            findings.append(Finding(
                "APX701", path, 1,
                f"entry '{entry.name}': rule {i} pattern {pattern!r} "
                f"is not a valid regex: {exc}"))
            continue
        axes = spec_axis_names(spec)
        unknown = [a for a in axes if a not in known_axes]
        if unknown:
            findings.append(Finding(
                "APX701", path, 1,
                f"entry '{entry.name}': rule {i} ({pattern!r}) names "
                f"mesh axes {unknown} that do not exist "
                f"(mesh axes: {sorted(known_axes)})"))
        dupes = sorted({a for a in axes if axes.count(a) > 1})
        if dupes:
            findings.append(Finding(
                "APX701", path, 1,
                f"entry '{entry.name}': rule {i} ({pattern!r}) repeats "
                f"mesh axes {dupes} within one spec — an array dim "
                f"cannot shard over the same axis twice"))

    trees = entry.trees() if entry.trees is not None else {}

    # -- APX701: coverage over the registered trees -----------------------
    live = set()
    for tree_name, tree in sorted(trees.items()):
        for leaf_path, leaf, hits in rule_match_table(rules, tree):
            live.update(hits)
            ndim = len(getattr(leaf, "shape", ()))
            if ndim == 0:
                continue  # scalars replicate without consulting the table
            if not hits:
                findings.append(Finding(
                    "APX701", path, 1,
                    f"entry '{entry.name}': no rule matches "
                    f"'{tree_name}' leaf '{leaf_path}' (shape "
                    f"{tuple(leaf.shape)}) — it would raise at shard "
                    f"time"))
                continue
            if len(hits) > 1:
                pats = [rules[i][0] for i in hits]
                findings.append(Finding(
                    "APX701", path, 1,
                    f"entry '{entry.name}': '{tree_name}' leaf "
                    f"'{leaf_path}' matched by {len(hits)} rules "
                    f"{pats} — first-match-wins hides all but "
                    f"{pats[0]!r}"))
                continue
            spec = rules[hits[0]][1]
            if len(tuple(spec)) > ndim:
                findings.append(Finding(
                    "APX701", path, 1,
                    f"entry '{entry.name}': rule {rules[hits[0]][0]!r} "
                    f"spec {spec} has rank {len(tuple(spec))} > array "
                    f"rank {ndim} of '{tree_name}' leaf '{leaf_path}'"))
    if trees:
        for i in sorted(set(range(len(rules))) - live):
            findings.append(Finding(
                "APX701", path, 1,
                f"entry '{entry.name}': rule {i} ({rules[i][0]!r}) "
                f"matches no leaf of any registered tree — dead rule "
                f"(typo'd pattern?)"))

    # -- APX702: derived trees must agree per tensor family ---------------
    params = trees.get("params")
    param_specs = _safe_match(rules, params) if params is not None else None

    if entry.optimizer_families and param_specs is not None:
        fams = optimizer_state_specs(rules, params,
                                     families=entry.optimizer_families)
        base = _flat_specs(param_specs)
        for fam in entry.optimizer_families:
            for (leaf_path, pspec), (_, fspec) in zip(
                    base, _flat_specs(fams[fam])):
                if pspec != fspec:
                    findings.append(Finding(
                        "APX702", path, 1,
                        f"entry '{entry.name}': optimizer family "
                        f"'{fam}' of param '{leaf_path}' derives spec "
                        f"{fspec} but the param derives {pspec} — "
                        f"state and weights would shard differently"))

    if entry.reference_specs is not None:
        refs = entry.reference_specs()
        for tree_name, ref_tree in sorted(refs.items()):
            if tree_name not in trees:
                continue
            derived = _safe_match(rules, trees[tree_name])
            if derived is None:
                continue
            for (leaf_path, dspec), (_, rspec) in zip(
                    _flat_specs(derived), _flat_specs(ref_tree)):
                if dspec != rspec:
                    findings.append(Finding(
                        "APX702", path, 1,
                        f"entry '{entry.name}': rule-derived spec "
                        f"{dspec} for '{tree_name}' leaf '{leaf_path}' "
                        f"!= hand-maintained reference {rspec}"))

    if entry.kv_cache_tree is not None and param_specs is not None:
        cache_specs = _safe_match(rules, trees[entry.kv_cache_tree])
        if cache_specs is not None:
            flat_cache = dict(_flat_specs(cache_specs))
            k_spec = next((s for p, s in flat_cache.items()
                           if p == "k" or p.endswith("/k")), None)
            v_spec = next((s for p, s in flat_cache.items()
                           if p == "v" or p.endswith("/v")), None)
            if k_spec != v_spec:
                findings.append(Finding(
                    "APX702", path, 1,
                    f"entry '{entry.name}': KV cache k spec {k_spec} "
                    f"!= v spec {v_spec}"))
            qkv_axes = set()
            for leaf_path, spec in _flat_specs(param_specs):
                if re.search(entry.qkv_kernel_re, leaf_path):
                    entries_ = tuple(spec)
                    last = entries_[-1] if entries_ else None
                    if last is not None:
                        qkv_axes.update(
                            last if isinstance(last, tuple) else (last,))
            head_axes = set(spec_axis_names(k_spec or PartitionSpec()))
            if head_axes != qkv_axes:
                findings.append(Finding(
                    "APX702", path, 1,
                    f"entry '{entry.name}': KV-cache head axes "
                    f"{sorted(head_axes)} != qkv output-dim axes "
                    f"{sorted(qkv_axes)} — decode would gather heads "
                    f"a rank's qkv shard never produced"))
    return findings
