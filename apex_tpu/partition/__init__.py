"""Partition-rule engine: one regex table shards everything.

``match_partition_rules`` turns an ordered ``(pattern, PartitionSpec)``
table into the spec pytree for any parameter-shaped tree;
``gpt_rules`` is the default Megatron-layout table;
``optimizer_state_specs`` re-derives moment/master-weight specs from
the same table; ``make_shard_and_gather_fns`` materializes per-leaf
placement closures; ``make_mesh`` builds the dp x tp x pp x cp mesh
through ``parallel_state``. The APX7xx lint tier
(``python -m apex_tpu.lint --sharding``) statically verifies the
tables and every tree derived from them — see
``docs/source/partitioning.rst``.
"""

from apex_tpu.partition.mesh import make_mesh
from apex_tpu.partition.rules import (
    make_shard_and_gather_fns,
    match_partition_rules,
    optimizer_state_specs,
    rule_match_table,
    spec_axis_names,
    tree_path_name,
    tree_paths,
)
from apex_tpu.partition.tables import (
    draft_gpt_rules,
    gpt_quant_rules,
    gpt_rules,
    kv_cache_quant_rules,
    paged_kv_cache_rules,
)

__all__ = [
    "draft_gpt_rules",
    "gpt_quant_rules",
    "gpt_rules",
    "kv_cache_quant_rules",
    "paged_kv_cache_rules",
    "make_mesh",
    "make_shard_and_gather_fns",
    "match_partition_rules",
    "optimizer_state_specs",
    "rule_match_table",
    "spec_axis_names",
    "tree_path_name",
    "tree_paths",
]
