"""``apex_tpu.models.deepseek`` against the benchmark's plain reference
(``benchmark/reference/deepseek_v3.py``: float32, the EXPANDED attention, no
cache) on seeded weights at a tiny size; the absorbed decode form against the
expanded one; YaRN's frequencies against hand-computed values; and the share
of the experts a chip holds against the uncut layer."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import deepseek
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
ref = harness.load_module("reference", "deepseek_v3", BENCH)


def sizes_of(cfg: deepseek.DeepseekConfig) -> dict:
    """The reference's sizes for a program config."""
    return {"vocab": cfg.vocab_size, "hidden": cfg.hidden_size,
            "layers": cfg.num_layers, "dense_layers": cfg.first_k_dense,
            "expert_layers": cfg.moe_layers, "heads": cfg.num_heads,
            "q_rank": cfg.q_lora_rank, "kv_rank": cfg.kv_lora_rank,
            "nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim,
            "v_dim": cfg.v_head_dim, "dense_ffn": cfg.ffn_size,
            "expert_ffn": cfg.moe_ffn_size,
            "shared_ffn": cfg.shared_experts * cfg.moe_ffn_size,
            "router_experts": cfg.num_experts,
            "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "experts_per_token": cfg.experts_per_token,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scale": cfg.routed_scaling_factor,
            "eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "rope_factor": cfg.rope_factor,
            "rope_original": cfg.rope_original_positions,
            "rope_beta_fast": cfg.rope_beta_fast,
            "rope_beta_slow": cfg.rope_beta_slow,
            "rope_mscale": cfg.rope_mscale,
            "rope_mscale_all_dim": cfg.rope_mscale_all_dim,
            "latent_width": cfg.latent_width, "row_width": cfg.kv_row_width,
            "positions": 128}


def biased(params, seed=5, std=0.02):
    """The tree with a router bias that changes choices."""
    bias = std * jax.random.normal(jax.random.PRNGKey(seed),
                                   params["moe"]["router_bias"].shape)
    return {**params, "moe": {**params["moe"], "router_bias": bias}}


@pytest.fixture(scope="module")
def tiny():
    cfg = deepseek.deepseek_tiny()
    return cfg, biased(deepseek.init(jax.random.PRNGKey(1), cfg))


def test_forward_matches_the_plain_reference(tiny):
    """Logits at every position, and the routers' choices, against the
    reference given the same share (8 of 16 experts, the same weights)."""
    cfg, params = tiny
    ids = jnp.asarray(np.random.RandomState(0).randint(2, 512, 70))
    with jax.default_matmul_precision("highest"):
        got = deepseek.apply(params, cfg, ids)
        want = ref.logits_at(params, sizes_of(cfg), ids, jnp.arange(70))
        mine = deepseek.prefill_layers(
            params, cfg, deepseek.embed(params, ids),
            jnp.ones((70,), jnp.int32), routes=True)[-1]
        theirs = ref.hidden_states(params, sizes_of(cfg), ids)[1]
    assert got.shape == (70, 512)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(np.sort(mine, -1), np.sort(theirs, -1))


def test_padding_changes_nothing_before_it(tiny):
    cfg, params = tiny
    ids = np.random.RandomState(1).randint(2, 512, 64)
    mask = (np.arange(64) < 41).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        padded, rows = deepseek.prefill_layers(
            params, cfg, deepseek.embed(params, jnp.asarray(ids)),
            jnp.asarray(mask))
        whole = deepseek.prefill_layers(
            params, cfg, deepseek.embed(params, jnp.asarray(ids[:41])),
            jnp.ones((41,), jnp.int32))[0]
    np.testing.assert_allclose(padded[:41], whole, atol=2e-5)
    # one row a token a layer: c_kv and k_pe, zeros to the lane tile
    assert rows.shape == (3, 64, 128)
    assert not np.any(np.asarray(rows[..., cfg.latent_width:]))


def test_absorbed_scores_and_contexts_are_the_expanded_ones(tiny):
    """``q_nope . (W^K c_kv) == (q_nope W^K) . c_kv`` and ``(P c_kv) W^V == P
    (c_kv W^V)``, head by head, on the layer's own weights."""
    cfg, params = tiny
    lp = params["dense"][0]["attn"]
    rng = np.random.RandomState(2)
    s, nh, kr = 23, cfg.num_heads, cfg.kv_lora_rank
    q_nope = jnp.asarray(rng.standard_normal((nh, cfg.qk_nope_head_dim)),
                         jnp.float32)
    c_kv = jnp.asarray(rng.standard_normal((s, kr)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        k_nope = jnp.einsum("sc,hdc->hsd", c_kv, lp["kv_b_k"])
        v = jnp.einsum("sc,hcd->hsd", c_kv, lp["kv_b_v"])
        expanded = jnp.einsum("hd,hsd->hs", q_nope, k_nope)
        q_lat = jnp.einsum("hd,hdc->hc", q_nope, lp["kv_b_k"])
        absorbed = q_lat @ c_kv.T
        p = jax.nn.softmax(expanded, -1)
        ctx = jnp.einsum("hs,hsd->hd", p, v)
        ctx_absorbed = jnp.einsum("hc,hcd->hd", p @ c_kv, lp["kv_b_v"])
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)
    np.testing.assert_allclose(ctx_absorbed, ctx, atol=1e-5)


def test_yarn_frequencies_by_hand():
    """As published (64 rope dimensions, theta 10000, factor 40, original
    4096, betas 32 and 1): the correction dimensions are floor(10.47) and
    ceil(22.51); pairs 0-10 keep their frequency, pairs 23-31 turn 40 times
    slower, pair 16 is 6/13 of the way. The attention factor is 1.3689 and
    the softmax scale 192^-0.5 * 1.3689^2."""
    cfg = deepseek.deepseek_v3()
    inv = deepseek.yarn_inv_freq(cfg)
    assert inv.shape == (32,) and inv.dtype == np.float32
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    np.testing.assert_allclose(
        inv[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    assert deepseek.yarn_mscale(40, 1) == pytest.approx(1.36888794, rel=1e-6)
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * 1.36888794 ** 2, rel=1e-6)
    # the reference computes its own, from the configuration file's keys
    config = harness.load_json(BENCH, "configs", "deepseek_v3.json")
    np.testing.assert_array_equal(ref.yarn_inv_freq(ref.sizes_of(config)),
                                  inv)
    assert ref.softmax_scale(ref.sizes_of(config)) == pytest.approx(
        cfg.softmax_scale, rel=1e-9)


def test_rope_rotates_neighbouring_pairs_and_stands_de_interleaved():
    x = jnp.asarray([[1.0, 0.0, 0.0, 2.0]])        # pairs (1, 0) and (0, 2)
    quarter = (jnp.zeros((1, 2)), jnp.ones((1, 2)))     # cos 0, sin 1
    np.testing.assert_allclose(deepseek.rope(x, *quarter),
                               [[0.0, -2.0, 1.0, 0.0]], atol=1e-7)
    # a rotation keeps every pair's length and the dot product of two
    # vectors at the same position
    cfg = deepseek.deepseek_tiny()
    rng = np.random.RandomState(3)
    a, b = (jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
            for _ in range(2))
    cos, sin = deepseek._angles(cfg, jnp.arange(5) + 9)
    np.testing.assert_allclose(
        jnp.sum(deepseek.rope(a, cos, sin) * deepseek.rope(b, cos, sin), -1),
        jnp.sum(a * b, -1), rtol=1e-5)


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up(tiny):
    """16 experts over 4 chips (4 each) in 2 groups: the routed parts of all
    four shares plus the shared expert ONCE are the uncut layer, program and
    reference alike."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, n_group=2, topk_group=1,
                              experts_per_token=3)
    whole = dataclasses.replace(cfg, experts_held=16)
    key = jax.random.PRNGKey(9)
    lp = jax.tree.map(lambda a: a[0],
                      biased(deepseek.init(key, whole))["moe"])
    x = jax.random.normal(jax.random.PRNGKey(10), (37, cfg.hidden_size))
    real = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        uncut, sizes, chosen = deepseek.expert_mlp(lp, x, whole, real)
        u = deepseek._rms(lp["mlp_norm"], x, cfg.rms_norm_eps)
        parts, rows = [], 0
        for share in range(4):
            mine = dataclasses.replace(cfg, experts_held=4,
                                       expert_offset=4 * share)
            held = {**lp, "w_gate_up": lp["w_gate_up"][4 * share:][:4],
                    "w_down": lp["w_down"][4 * share:][:4]}
            part, share_sizes, share_chosen = deepseek._experts(
                held, u, mine, real)
            # every chip routes over all 16 alike
            np.testing.assert_array_equal(share_chosen, chosen)
            np.testing.assert_array_equal(share_sizes,
                                          sizes[4 * share:][:4])
            rows += int(share_sizes.sum())
            parts.append(part)
        shared = deepseek._dense(lp["shared_down"], deepseek._swiglu(
            deepseek._dense(lp["shared_gate_up"], u)))
        theirs, ref_chosen = ref.expert_mlp(lp, sizes_of(whole), x)
    assert rows == 37 * 3           # every assignment is on exactly one chip
    # only one group of 8 is kept: a token's 3 experts lie in ONE group
    assert (np.asarray(chosen) // 8 == np.asarray(chosen)[:, :1] // 8).all()
    np.testing.assert_allclose(x + sum(parts) + shared, uncut, atol=2e-5)
    np.testing.assert_allclose(uncut, theirs, atol=2e-5)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(ref_chosen, -1))


def test_a_long_prompt_goes_through_the_experts_in_blocks(tiny, monkeypatch):
    cfg, params = tiny
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.hidden_size))
    real = jnp.arange(64) < 50
    whole = deepseek.expert_mlp(lp, x, cfg, real)
    monkeypatch.setattr(deepseek, "_MOE_ROWS", 16)
    blocks = deepseek.expert_mlp(lp, x, cfg, real)
    np.testing.assert_allclose(blocks[0], whole[0], atol=1e-6)
    np.testing.assert_array_equal(blocks[1], whole[1])
    np.testing.assert_array_equal(blocks[2], whole[2])


def test_config_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="not among the router's"):
        deepseek.deepseek_tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="dense layers"):
        deepseek.deepseek_tiny(first_k_dense=4)
    cfg = deepseek.deepseek_v3()
    assert (cfg.latent_width, cfg.kv_row_width, cfg.kv_layers) == (
        576, 640, 61)
    assert not cfg.recurrent and cfg.latent
