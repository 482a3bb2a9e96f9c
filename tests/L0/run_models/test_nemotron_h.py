"""``models/nemotron_h`` against the benchmark's plain reference
(``benchmark/reference/nemotron3_super_120b_a12b.py``: float32, the recurrence
token by token, every held expert in a loop, full softmax over repeated K/V)
at the rehearsal size of the configuration file, on the weights the reference
makes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import nemotron_h as nh
from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
TOL = dict(rtol=2e-4, atol=2e-4)


def load(**changes):
    """(reference module, sizes, config object) at the rehearsal size, with
    ``changes`` to the configuration's keys."""
    ref = harness.load_module("reference", "nemotron3_super_120b_a12b", BENCH)
    config = {**harness.rehearsal_view(harness.load_json(
        BENCH, "configs", "nemotron3_super_120b_a12b.json")), **changes}
    sz = ref.sizes_of(config)
    runner = harness.load_module("runners", "nemotron_serve", BENCH)
    return ref, sz, runner.model_config(config, sz)


def weights(ref, sz, seed=5):
    """Float32 copies of the weights the reference makes, the norms' weights
    and the router's bias moved off their initial values so that one applied
    wrongly shows."""
    served = jax.jit(lambda key: ref.make_weights(sz, key))(ref.seed_key(seed))
    count = [0]

    def moved(path, leaf):
        leaf = leaf.astype(jnp.float32)
        if path[-1].key not in ("weight", "router_bias", "bias", "d") \
                or leaf.ndim > 2:
            return leaf
        count[0] += 1
        return leaf + 0.2 * jax.random.normal(
            jax.random.PRNGKey(count[0]), leaf.shape)

    return jax.tree_util.tree_map_with_path(moved, served)


@pytest.fixture(scope="module")
def tiny():
    ref, sz, cfg = load()
    return ref, sz, cfg, weights(ref, sz)


def layer(params, place, repeat=0):
    """The parameters of the layer at ``place`` of the period."""
    return jax.tree.map(lambda a: a[repeat], params["periods"][place])


def hidden(sz, s=40, seed=0):
    return jnp.asarray(np.random.RandomState(seed).standard_normal(
        (s, sz["hidden"])), jnp.float32)


def test_config_object_reads_the_pattern_string(tiny):
    _, sz, cfg, _ = tiny
    assert cfg.pattern == cfg.period == "MEM*E" and cfg.repeats == 1
    assert [cfg.layers_of(k) for k in "M*E"] == [2, 1, 2] and cfg.recurrent
    assert cfg.state_shapes(5) == ((2, 5, 8, 16, 32), (2, 5, 3, 256))
    assert cfg.state_bytes_per_slot() == 4 * 2 * (8 * 16 * 32 + 3 * 256)
    assert (cfg.kv_layers, cfg.kv_row_width) == (1, 32)
    assert cfg.counter_shapes() == {"moe_load": (2, 8), "moe_hit": (2,),
                                    "moe_steps": (1,)}
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset) == (16, 8,
                                                                      0)
    twice = nh.nemotron_h_tiny(pattern="MEM*EMEM*E")
    assert (twice.period, twice.repeats) == ("MEM*E", 2)
    for bad in ("MEMX", "EEE", "MMM", "**E"):
        with pytest.raises(ValueError, match="pattern"):
            nh.nemotron_h_tiny(pattern=bad)
    with pytest.raises(ValueError, match="are not among the router's"):
        nh.nemotron_h_tiny(expert_offset=12)


def test_published_config_counts_to_the_models_name():
    """120B-A12B: the layer equations, checked by a count over the shapes
    ``init`` makes for the whole published pattern."""
    cfg = nh.nemotron3_super_120b_a12b()
    assert [cfg.layers_of(k) for k in "M*E"] == [40, 8, 40]
    assert cfg.num_layers == 88 and cfg.period == cfg.pattern
    assert (cfg.d_inner, cfg.conv_channels) == (8192, 10240)
    shapes = jax.eval_shape(lambda k: nh.init(k, cfg), jax.random.PRNGKey(0))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e9, 1) == 120.7
    one_expert = 2 * 1024 * 2688
    active = total - 40 * (512 - 22) * one_expert
    assert round(active / 1e9, 1) == 12.8
    held = nh.NemotronHConfig(vocab_size=32768, pattern="MEMEMEMEM*E",
                              experts_held=128)
    shapes = jax.eval_shape(lambda k: nh.init(k, held), jax.random.PRNGKey(0))
    cut = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(cut / 1e9, 2) == 4.65
    assert held.state_shapes(128) == ((5, 128, 128, 64, 128),
                                      (5, 128, 3, 10240))
    assert held.state_bytes_per_slot() == 4 * 5 * (128 * 64 * 128
                                                   + 3 * 10240)


def test_mamba_layer_matches_the_reference(tiny):
    ref, sz, cfg, params = tiny
    lp, x = layer(params, 0), hidden(sz)
    with jax.default_matmul_precision("highest"):
        got, state, tail = nh.mamba_block_prefill(
            lp, x, cfg, jnp.ones((40,), jnp.int32))
        want = ref.mamba_layer(lp, sz, x)
    np.testing.assert_allclose(got, want, **TOL)
    assert state.shape == (8, 16, 32) and tail.shape == (3, 256)


def test_attention_layer_matches_the_reference(tiny):
    ref, sz, cfg, params = tiny
    lp, x = layer(params, 3), hidden(sz, seed=1)
    with jax.default_matmul_precision("highest"):
        got, k, v = nh.attention_block_prefill(
            lp, x, cfg, jnp.ones((40,), jnp.int32), jnp.float32)
        want = ref.attention_layer(lp, sz, x)
    np.testing.assert_allclose(got, want, **TOL)
    assert k.shape == v.shape == (40, cfg.num_kv_heads * cfg.head_dim)


def test_expert_layer_matches_the_reference_and_its_choice(tiny):
    ref, sz, cfg, params = tiny
    lp, x = layer(params, 1), hidden(sz, seed=2)
    with jax.default_matmul_precision("highest"):
        got, sizes, chosen = nh.expert_block(lp, x, cfg,
                                             jnp.ones((40,), bool))
        want, want_chosen = ref.expert_layer(lp, sz, x)
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(want_chosen, -1))
    # what each held expert got is what the router gave it
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(want_chosen).ravel(), minlength=16)[:8])


def test_padding_rows_are_routed_nowhere(tiny):
    _, sz, cfg, params = tiny
    lp, x = layer(params, 1), hidden(sz, seed=3)
    real = jnp.arange(40) < 25
    _, sizes, chosen = nh.expert_block(lp, x, cfg, real)
    held = np.asarray(chosen[:25])
    assert int(sizes.sum()) == int(((held >= 0) & (held < 8)).sum())


@pytest.mark.parametrize("pattern", ["MEM*E", "ME*MME*M"])
def test_apply_is_the_references_forward(pattern):
    """The second pattern has two repeats of its period (``ME*M``): the scan
    over periods, and the order of the stacked layers."""
    ref, sz, cfg = load(hybrid_override_pattern=pattern,
                        num_hidden_layers=len(pattern))
    assert cfg.repeats == len(pattern) // len(cfg.period)
    params = weights(ref, sz)
    ids = jnp.asarray(np.random.RandomState(4).randint(2, sz["vocab"], 40))
    with jax.default_matmul_precision("highest"):
        got = nh.apply(params, cfg, ids)
        want = ref.logits_at(params, sz, ids, jnp.arange(40))
    np.testing.assert_allclose(got, want, **TOL)


def test_ties_in_the_router_break_the_same_way_in_program_and_reference(
        tiny):
    """Two experts with the same column of the router's matrix score alike,
    to the bit: whichever is kept, program (``lax.top_k``) and reference
    (a stable descending sort) keep the same one, the lower index."""
    from apex_tpu.transformer.functional import moe

    ref, sz, _, params = tiny
    lp = layer(params, 1)
    kernel = lp["router"]["kernel"]
    kernel = kernel.at[:, 9].set(kernel[:, 2]).at[:, 12].set(kernel[:, 5]) \
        .at[:, 13].set(kernel[:, 5])
    lp = {**lp, "router": {"kernel": kernel},
          "router_bias": jnp.zeros_like(lp["router_bias"])}
    u = hidden(sz, s=200, seed=5)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.route(lp, sz, u)
        logits = jnp.dot(u, kernel, precision=jax.lax.Precision.HIGHEST)
    got, _ = moe.route(logits, lp["router_bias"], sz["experts_per_token"],
                       sz["routed_scale"])
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
    # and the ties were really met: a row that keeps 5 and not all of 12, 13
    kept = [set(r) for r in np.asarray(got).tolist()]
    assert any(5 in r and not {12, 13} <= r for r in kept)
    assert not any((9 in r and 2 not in r) or (13 in r and 12 not in r)
                   for r in kept)


def test_the_four_chips_shares_add_up_to_the_uncut_layer():
    """Four configurations hold experts 0-3, 4-7, 8-11 and 12-15 of one
    expert layer of 16; each adds its own experts' part to the residual and
    the shared expert. Their sum, the residual and the shared expert counted
    once, is the reference's output with all 16 held."""
    ref, sz_all, _ = load(n_routed_experts=16)
    assert (sz_all["experts_held"], sz_all["router_experts"]) == (16, 16)
    whole = weights(ref, sz_all, seed=9)
    x = hidden(sz_all, seed=6)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(layer(whole, 1), sz_all, x)
    total, base = 0.0, None
    for offset in (0, 4, 8, 12):
        _, sz, cfg = load(n_routed_experts=4, expert_offset=offset)
        assert (cfg.experts_held, cfg.expert_offset, cfg.num_experts) == (
            4, offset, 16)
        lp = layer(weights(ref, sz, seed=9), 1)
        # the share's experts ARE the whole model's, by their number there
        np.testing.assert_array_equal(
            lp["w1"], layer(whole, 1)["w1"][offset:offset + 4])
        with jax.default_matmul_precision("highest"):
            out, sizes, _ = nh.expert_block(lp, x, cfg, jnp.ones((40,), bool))
            u = nh._rms(lp["norm"], x, cfg.rms_norm_eps)
            base = x + nh._dense(lp["shared_out"], jnp.square(jax.nn.relu(
                nh._dense(lp["shared_in"], u))))
        total = total + (out - base)
    np.testing.assert_allclose(total + base, want, **TOL)


def test_as_served_in_bfloat16_the_forward_stays_close_and_routes_alike():
    """The weights as served (bfloat16), activations into every product as
    two bfloat16 terms: the logits stay within 2e-3 of the float32
    reference's on the same weights and the routers choose alike, where one
    term (activations rounded to 8 bits before every product) is ten times
    further off."""
    ref, sz, cfg = load()
    served = jax.jit(lambda key: ref.make_weights(sz, key))(ref.seed_key(11))
    assert served["head"]["kernel"].dtype == jnp.bfloat16
    ids = jnp.asarray(np.random.RandomState(8).randint(2, sz["vocab"], 64))
    ones = jnp.ones(ids.shape, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(served, sz, ids, jnp.arange(64))
        want_routes = ref.hidden_states(served, sz, ids)[1]
    x, *_, routes = nh.prefill_layers(served, cfg, nh.embed(served, ids),
                                      ones, routes=True)
    got = nh.logits_of(served, cfg, x)
    assert float(jnp.abs(got - want).max()) < 2e-3
    np.testing.assert_array_equal(np.sort(routes, -1),
                                  np.sort(want_routes, -1))

    def rounded(p, x):                  # models.hybrid._dense's rule
        return jnp.dot(x.astype(jnp.bfloat16), p["kernel"],
                       preferred_element_type=jnp.float32)

    real = nh._dense
    try:
        nh._dense = rounded
        low = nh.apply(served, cfg, ids)
    finally:
        nh._dense = real
    assert float(jnp.abs(low - want).max()) > 10 * float(
        jnp.abs(got - want).max())
