"""Partition-rule engine tests (``apex_tpu.partition``): regex -> spec
matching semantics, the default GPT/BERT tables against the
hand-maintained references, optimizer/serving spec derivation from the
same table, the dp x tp x pp x cp mesh factory, and shard/gather
placement roundtrips on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.partition import (
    gpt_rules,
    make_mesh,
    make_shard_and_gather_fns,
    match_partition_rules,
    optimizer_state_specs,
    rule_match_table,
    spec_axis_names,
    tree_paths,
)
from apex_tpu.transformer import parallel_state as ps


def _sds(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _flat(tree):
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# matching semantics
# ---------------------------------------------------------------------------

def test_first_match_wins_and_search_is_unanchored():
    rules = (("w$", P("model", None)), ("a/w", P(None, "model")))
    tree = {"a": {"w": _sds((4, 4))}, "m": {"a": {"w": _sds((4, 4))}}}
    specs = match_partition_rules(rules, tree)
    # both leaves end in 'w': rule 0 wins everywhere, and the m/-prefixed
    # copy matches identically (the optimizer-family contract)
    assert specs["a"]["w"] == P("model", None)
    assert specs["m"]["a"]["w"] == P("model", None)


def test_scalar_leaves_replicate_without_rules():
    specs = match_partition_rules((), {"step": _sds(())})
    assert specs["step"] == P()


def test_unmatched_leaf_raises_with_path_and_shape():
    with pytest.raises(ValueError, match=r"a/w.*\(4, 8\)"):
        match_partition_rules((("nope", P()),), {"a": {"w": _sds((4, 8))}})


def test_tree_paths_and_match_table():
    tree = {"a": {"w": _sds((4,))}, "b": _sds((4,))}
    assert tree_paths(tree) == ["a/w", "b"]
    table = rule_match_table((("w", P(None)), ("zz", P())), tree)
    assert [(name, hits) for name, _, hits in table] == \
        [("a/w", [0]), ("b", [])]


def test_spec_axis_names_flattens_tuple_entries():
    assert spec_axis_names(P(("model", "data"), None)) == ["model", "data"]
    assert spec_axis_names(P(None, "model")) == ["model"]
    assert spec_axis_names(P()) == []


# ---------------------------------------------------------------------------
# default tables == hand-maintained references
# ---------------------------------------------------------------------------

def test_gpt_rules_reproduce_hand_specs():
    from apex_tpu.models.gpt import gpt_partition_specs, gpt_tiny, init_gpt

    cfg = gpt_tiny()
    params = jax.eval_shape(
        lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
    assert _flat(match_partition_rules(gpt_rules(), params)) == \
        _flat(gpt_partition_specs(cfg))


def test_optimizer_state_specs_track_param_specs():
    from apex_tpu.models.gpt import gpt_tiny, init_gpt

    params = jax.eval_shape(
        lambda k: init_gpt(k, gpt_tiny()), jax.random.PRNGKey(0))
    base = _flat(match_partition_rules(gpt_rules(), params))
    fams = optimizer_state_specs(gpt_rules(), params)
    assert set(fams) == {"m", "v", "master"}
    for fam in fams:
        assert _flat(fams[fam]) == base


def test_cache_partition_specs_derive_from_rules():
    from apex_tpu.serving.cache import paged_cache_partition_specs

    specs = paged_cache_partition_specs()
    assert specs.k == P(None, None, None, ps.TENSOR_AXIS)
    assert specs.v == specs.k
    assert specs.lengths == P() and specs.block_tables == P()
    quant = paged_cache_partition_specs(quantized=True)
    assert quant.k == specs.k
    assert quant.k_scale == quant.v_scale == P(None, None, ps.TENSOR_AXIS)
    # a custom table flows through
    flipped = ((r"(^|/)(k|v)$", P(None, None, ps.TENSOR_AXIS, None)),
               (r"(^|/)(lengths|block_tables)$", P()))
    assert paged_cache_partition_specs(flipped).k == \
        P(None, None, ps.TENSOR_AXIS, None)


def test_fused_adam_state_partition_specs():
    from apex_tpu.optimizers.fused_adam import FusedAdam

    param_specs = {"w": P("model", None), "b": P(None)}
    st = FusedAdam().state_partition_specs(param_specs)
    assert st.step == P()
    assert st.m == param_specs and st.v == param_specs
    with pytest.raises(ValueError, match="flat"):
        FusedAdam(use_flat_kernel=True).state_partition_specs(param_specs)


def test_distributed_adam_partition_spec_tensor_axis():
    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        DistributedFusedAdam,
    )

    opt = DistributedFusedAdam(dp_size=2)
    assert opt.partition_spec().master == P(ps.DATA_AXIS, None)
    joint = opt.partition_spec(tensor_axis=ps.TENSOR_AXIS)
    assert joint.master == P((ps.TENSOR_AXIS, ps.DATA_AXIS), None)
    assert joint.m == joint.master and joint.v == joint.master
    assert joint.step == P()


# ---------------------------------------------------------------------------
# mesh factory
# ---------------------------------------------------------------------------

def test_make_mesh_installs_requested_degrees():
    mesh = make_mesh(dp=2, tp=2, pp=2, cp=1)
    assert dict(mesh.shape) == {"data": 2, "pipe": 2, "context": 1,
                                "model": 2}
    assert ps.get_mesh() is mesh
    assert ps.get_tensor_model_parallel_world_size() == 2


def test_make_mesh_rejects_oversubscription_and_bad_degrees():
    with pytest.raises(ValueError, match="needs 16 devices"):
        make_mesh(dp=4, tp=4)
    with pytest.raises(ValueError, match="positive"):
        make_mesh(dp=0)
    with pytest.raises(ValueError, match="exactly"):
        make_mesh(dp=2, tp=2, devices=jax.devices()[:2])


def test_initialize_model_parallel_validates_dp():
    with pytest.raises(ps.ParallelStateError, match="gives dp = 4"):
        ps.initialize_model_parallel(tensor_model_parallel_size_=2,
                                     data_parallel_size_=3)


# ---------------------------------------------------------------------------
# shard / gather fns
# ---------------------------------------------------------------------------

def test_shard_and_gather_roundtrip():
    mesh = make_mesh(dp=2, tp=2)
    tree = {"w": jnp.arange(32.0).reshape(4, 8),
            "b": jnp.arange(8.0)}
    specs = {"w": P(ps.TENSOR_AXIS, None), "b": P()}
    shard_fns, gather_fns = make_shard_and_gather_fns(specs, mesh)
    sharded = jax.tree_util.tree_map(lambda f, x: f(x), shard_fns, tree)
    assert sharded["w"].sharding.spec == P(ps.TENSOR_AXIS, None)
    back = jax.tree_util.tree_map(lambda f, x: f(x), gather_fns, sharded)
    np.testing.assert_array_equal(np.asarray(back["w"]), tree["w"])
    np.testing.assert_array_equal(np.asarray(back["b"]), tree["b"])
