"""``models/hybrid`` against the benchmark's plain reference
(``benchmark/reference/olmo_hybrid_7b.py``: float32, the recurrence token by
token) at the rehearsal size of the configuration file, on the weights the
reference makes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import hybrid
from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def tiny():
    """(reference module, sizes, config object, float32 weights with the
    norms' weights moved off 1 so that a norm applied without them shows)."""
    ref = harness.load_module("reference", "olmo_hybrid_7b",
                              os.path.join(REPO, "benchmark"))
    config = harness.rehearsal_view(harness.load_json(
        REPO, "benchmark", "configs", "olmo_hybrid_7b.json"))
    sz = ref.sizes_of(config)
    cfg = hybrid.HybridConfig.from_layer_types(
        config["layer_types"], vocab_size=sz["vocab"],
        hidden_size=sz["hidden"], num_heads=sz["heads"],
        ffn_hidden_size=sz["ffn"], linear_heads=sz["linear_heads"],
        linear_key_dim=sz["linear_key_dim"],
        linear_value_dim=sz["linear_value_dim"],
        conv_kernel=sz["conv_kernel"], rms_norm_eps=sz["eps"])
    served = jax.jit(lambda key: ref.make_weights(sz, key))(ref.seed_key(5))
    count = [0]

    def off_one(path, leaf):
        if path[-1].key != "weight" or leaf.dtype != jnp.float32:
            return leaf
        count[0] += 1
        return leaf + 0.3 * jax.random.normal(
            jax.random.PRNGKey(count[0]), leaf.shape)

    served = jax.tree_util.tree_map_with_path(off_one, served)
    return ref, sz, cfg, served


def test_config_object_follows_the_published_layer_types(tiny):
    _, sz, cfg, _ = tiny
    assert (cfg.num_periods, cfg.linear_per_period) == (1, 3)
    assert cfg.num_layers == sz["layers"] == 4 and cfg.recurrent
    assert cfg.state_shapes(5) == ((3, 5, 2, 16, 32), (3, 5, 3, 128))
    assert cfg.state_bytes_per_slot() == 4 * (3 * 2 * 16 * 32 + 3 * 3 * 128)
    full = hybrid.olmo_hybrid_7b()
    assert (full.num_layers, full.num_linear_layers, full.head_dim) == (
        32, 24, 128)
    for bad in (["full_attention"], ["linear_attention"] * 3,
                ["linear_attention", "full_attention", "full_attention"]):
        with pytest.raises(ValueError, match="layer_types"):
            hybrid.HybridConfig.from_layer_types(bad)


@pytest.mark.parametrize("s", [7, 64, 150])
def test_forward_matches_the_reference_in_float32(tiny, s):
    """Same weights as float32 on both sides: the chunked kernel, the fused
    norms and the flash path against plain jax.numpy."""
    ref, sz, cfg, served = tiny
    params = jax.tree.map(lambda a: a.astype(jnp.float32), served)
    ids = jnp.asarray(np.random.RandomState(s).randint(2, sz["vocab"], s))
    got = jax.jit(lambda p, i: hybrid.apply_hybrid(p, cfg, i))(params, ids)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, i: ref.logits_at(
            p, sz, i, jnp.arange(s)))(served, ids)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_served_bfloat16_weights_stay_near_the_reference(tiny):
    """As served (bfloat16 matrices, float32 between the products): the
    distance that remains is the rounding of each product's inputs."""
    ref, sz, cfg, served = tiny
    ids = jnp.asarray(np.random.RandomState(1).randint(2, sz["vocab"], 96))
    got = jax.jit(lambda p, i: hybrid.apply_hybrid(p, cfg, i))(served, ids)
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(served, sz, ids, jnp.arange(96))
        low = ref.logits_at(served, sz, ids, jnp.arange(96),
                            "bfloat16_state")
    def rms(x):
        return float(jnp.sqrt(jnp.mean(jnp.square(x))))

    # logits of standard deviation 1: about 1% of that (the tails are heavy,
    # so the root mean square is what is compared)
    assert 1e-4 < rms(got - want) < 0.03
    # the control's distance is of another order than the served program's
    assert rms(low - want) > 2 * rms(got - want)


def test_init_hybrid_has_the_reference_trees_structure(tiny):
    ref, sz, cfg, served = tiny
    mine = hybrid.init_hybrid(jax.random.PRNGKey(0), cfg)
    assert jax.tree.structure(mine) == jax.tree.structure(served)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, served)
    assert served["head"]["kernel"].dtype == jnp.bfloat16
    assert served["periods"]["linear"][0]["a_log"].dtype == jnp.float32
