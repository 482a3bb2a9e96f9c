"""``apex_tpu.models.exaone_moe`` against the benchmark's plain reference
(``benchmark/reference/k_exaone_236b_a23b.py``: float32, every layer keeps
every position and the window is an explicit band mask, no cache) on seeded
weights at a tiny size; prefill and decode through the two pools against the
model's own forward; each assumed convention by a property of its own; and
the share of the experts a chip holds against the uncut
layer."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import exaone_moe
from apex_tpu.models.exaone_moe import FULL, SLIDING
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
ref = harness.load_module("reference", "k_exaone_236b_a23b", BENCH)


def sizes_of(cfg: exaone_moe.ExaoneMoeConfig, page_size=4) -> dict:
    """The reference's sizes for a program config."""
    from apex_tpu.serving.cache import ring_pages

    return {"vocab": cfg.vocab_size, "hidden": cfg.hidden_size,
            "layers": cfg.num_layers, "dense_layers": cfg.first_k_dense,
            "expert_layers": cfg.moe_layers,
            "layer_types": tuple(cfg.layer_types),
            "full_layers": cfg.kv_layers, "window_layers": cfg.window_layers,
            "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "sliding_window": cfg.sliding_window,
            "window": cfg.window, "dense_ffn": cfg.ffn_size,
            "expert_ffn": cfg.moe_ffn_size,
            "shared_ffn": cfg.shared_experts * cfg.moe_ffn_size,
            "router_experts": cfg.num_experts,
            "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "experts_per_token": cfg.experts_per_token,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scale": cfg.routed_scaling_factor,
            "eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "row_width": cfg.kv_row_width, "page_size": page_size,
            "ring_pages": ring_pages(cfg.window, page_size),
            "cache_dtype": "float32", "positions": 128}


def seasoned(params, seed=5):
    """The tree with a router bias that changes choices and norm weights
    that are not all ones (so that a norm in the wrong place shows)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def leaf(path, a):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            return 0.02 * jax.random.normal(next(keys), a.shape)
        if "norm" in name:
            return 1.0 + 0.2 * jax.random.normal(next(keys), a.shape)
        return a

    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def tiny():
    cfg = exaone_moe.exaone_moe_tiny()
    return cfg, seasoned(exaone_moe.init(jax.random.PRNGKey(1), cfg))


def both(cfg, params, n=70, seed=0):
    """(program logits, reference logits, program routes, reference routes)
    over ``n`` random tokens."""
    ids = jnp.asarray(np.random.RandomState(seed).randint(2, 512, n))
    with jax.default_matmul_precision("highest"):
        got = exaone_moe.apply(params, cfg, ids)
        want = ref.logits_at(params, sizes_of(cfg), ids, jnp.arange(n))
        mine = exaone_moe.prefill_layers(
            params, cfg, exaone_moe.embed(params, ids),
            jnp.ones((n,), jnp.int32))[3]
        theirs = ref.hidden_states(params, sizes_of(cfg), ids)[1]
    return got, want, np.sort(mine, -1), np.sort(theirs, -1)


def test_forward_matches_the_plain_reference(tiny):
    """Logits at every position, and the routers' choices, against the
    reference given the same share (8 of 16 experts, the same weights); 70
    positions are nine windows of 8."""
    cfg, params = tiny
    got, want, mine, theirs = both(cfg, params)
    assert got.shape == (70, 512) and mine.shape == (4, 70, 4)
    np.testing.assert_array_equal(mine, theirs)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_prompt_attends_in_float32_over_the_rows_the_cache_keeps(tiny):
    """With a bfloat16 cache the prompt path rounds K and V rows ONCE, to
    what the cache keeps, and nothing else: queries, probabilities and
    context stay float32 (as the decode kernel reads them). So it agrees
    with the reference told the same ``cache_dtype`` up to the few elements
    that two float32 values a hair apart round to different bfloat16
    neighbours, and several times less well with the reference that keeps
    float32 rows."""
    cfg, params = tiny
    ids = jnp.asarray(np.random.RandomState(4).randint(2, 512, 70))
    with jax.default_matmul_precision("highest"):
        x, (k, _), (wk, _), _ = exaone_moe.prefill_layers(
            params, cfg, exaone_moe.embed(params, ids),
            jnp.ones((70,), jnp.int32), jnp.bfloat16)
        got = exaone_moe.logits_of(params, cfg, x)
        kept = ref.logits_at(params, {**sizes_of(cfg),
                                      "cache_dtype": "bfloat16"}, ids,
                             jnp.arange(70))
        unrounded = ref.logits_at(params, sizes_of(cfg), ids, jnp.arange(70))
    assert k.dtype == wk.dtype == jnp.bfloat16 and x.dtype == jnp.float32
    near = np.abs(np.asarray(got - kept))
    far = np.abs(np.asarray(got - unrounded))
    assert near.max() < 3e-3 and np.sqrt((near ** 2).mean()) < 3e-4
    assert far.max() > 3 * near.max()
    assert np.sqrt((far ** 2).mean()) > 5 * np.sqrt((near ** 2).mean())


def test_the_window_is_applied_and_the_reference_can_leave_it_out(tiny):
    """The ``full_window`` control attends causally in every layer: equal to
    the program up to the first position a window cuts (position 8 sees 0 ..
    8 where the band gives 1 .. 8), apart after it."""
    cfg, params = tiny
    ids = jnp.asarray(np.random.RandomState(1).randint(2, 512, 40))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(exaone_moe.apply(params, cfg, ids))
        full = np.asarray(ref.logits_at(params, sizes_of(cfg), ids,
                                        jnp.arange(40), "full_window"))
    np.testing.assert_allclose(got[:8], full[:8], atol=2e-4)
    assert np.abs(got[8:] - full[8:]).max(-1).min() > 1e-3
    with pytest.raises(ValueError):
        ref.hidden_states(params, sizes_of(cfg), ids, "float16")


def _one_attention(tiny, windowed, x=None, lp=None, n=24):
    """The first expert layer's attention sub-layer over ``n`` rows."""
    cfg, params = tiny
    lp = lp or jax.tree.map(lambda w: w[0], params["moe"])["attn"]
    if x is None:
        x = jnp.asarray(np.random.RandomState(3).randn(n, cfg.hidden_size),
                        jnp.float32)
    with jax.default_matmul_precision("highest"):
        return x, lp, exaone_moe.attention_prefill(
            lp, x, cfg, jnp.ones((x.shape[0],), jnp.int32), jnp.float32,
            windowed)[0]


@pytest.mark.parametrize("assumption", [
    "sublayer_norm_on_the_output", "qk_norm_per_head",
    "rotary_on_the_sliding_layers_only", "window_counts_the_token_itself"])
def test_each_assumed_convention_shows_in_the_program(tiny, assumption):
    """What the published config does not say is one convention, written
    once (the module's docstring; the configuration file's ``assumed``).
    Each by a property of its own, beside the agreement with the reference."""
    cfg, _ = tiny
    x, lp, out = _one_attention(tiny, True)
    if assumption == "sublayer_norm_on_the_output":
        # x + w * normed(f(x)): what the sub-layer adds is linear in w
        twice = {**lp, "norm": {"weight": 2.0 * lp["norm"]["weight"]}}
        np.testing.assert_allclose(_one_attention(tiny, True, x, twice)[2] - x,
                                   2.0 * (out - x), atol=1e-5)
    elif assumption == "qk_norm_per_head":
        # the norm takes a head's scale out (up to its eps): q's columns three
        # times as large
        q_width = cfg.num_heads * cfg.head_dim
        scale = jnp.where(jnp.arange(lp["qkv"]["kernel"].shape[1]) < q_width,
                          3.0, 1.0)
        scaled = {**lp, "qkv": {"kernel": lp["qkv"]["kernel"] * scale}}
        np.testing.assert_allclose(_one_attention(tiny, True, x, scaled)[2],
                                   out, atol=1e-4)
    elif assumption == "rotary_on_the_sliding_layers_only":
        u, at = x[:6], jnp.arange(6)
        with jax.default_matmul_precision("highest"):
            q0, k0, _ = exaone_moe._qkv(lp, u, cfg, at, False)
            q5, k5, _ = exaone_moe._qkv(lp, u, cfg, at + 5, False)
            r0, s0, _ = exaone_moe._qkv(lp, u, cfg, at, True)
            r5, s5, _ = exaone_moe._qkv(lp, u, cfg, at + 5, True)
        np.testing.assert_array_equal(q0, q5)       # a full layer: no place
        np.testing.assert_array_equal(k0, k5)
        assert np.abs(np.asarray(r0 - r5)).max() > 1e-2
        score = lambda q, k: jnp.einsum("ihd,jhd->hij", q[:, :2], k)
        np.testing.assert_allclose(score(r0, s0), score(r5, s5), atol=1e-4)
    else:
        # query i sees keys i - 7 .. i at window 8, and not i - 8
        i, w = 20, cfg.window
        moved = lambda j: _one_attention(
            tiny, True, x.at[j].add(1.0))[2][i] - out[i]
        assert w == cfg.sliding_window == 8
        np.testing.assert_array_equal(moved(i - w), 0.0)
        assert np.abs(np.asarray(moved(i - w + 1))).max() > 1e-4


def test_layers_left_over_after_the_whole_periods_run_unrolled():
    """Seven layers: a dense one, one period L L G L, and two more sliding
    layers behind the scan; rows come out by kind in layer order."""
    cfg = exaone_moe.exaone_moe_tiny(
        num_layers=7, layer_types=(SLIDING,) * 3 + (FULL,) + (SLIDING,) * 3)
    assert cfg.pattern == (True, True, False, True)
    assert [cfg.pool_layer(i) for i in range(7)] == [0, 1, 2, 0, 3, 4, 5]
    params = seasoned(exaone_moe.init(jax.random.PRNGKey(2), cfg))
    got, want, mine, theirs = both(cfg, params, 30)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(mine, theirs)
    ids = jnp.arange(30) + 2
    _, (k, v), (wk, wv), chosen = exaone_moe.prefill_layers(
        params, cfg, exaone_moe.embed(params, ids), jnp.ones((30,), jnp.int32))
    assert k.shape == v.shape == (1, 30, 32)
    assert wk.shape == wv.shape == (6, 30, 32) and chosen.shape == (6, 30, 4)


def test_prefill_then_decode_through_both_pools_is_the_forward(tiny):
    """The two cores on the seam's own terms (no engine): a prefill writes
    the full layer's pages and the LAST pages of the prompt into the slot's
    cycle, decode steps read both in place; logits equal ``apply`` at every
    position across four turns of the cycle."""
    from apex_tpu.serving.cache import init_window_cache
    from apex_tpu.serving.decode import (make_model_decode_fn,
                                         make_model_prefill_fn)

    cfg, params = tiny
    page, slots, max_len = 4, 2, 96
    ids = np.random.RandomState(3).randint(2, 512, 80)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(exaone_moe.apply(params, cfg, jnp.asarray(ids)))
        cache = init_window_cache(cfg, slots, max_len, 2 + slots * 24, page,
                                  jnp.float32)
        assert cache.wk.shape == (4, 2 + slots * 3, page, 32)
        n0, bucket, slot = 21, 32, 1
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n0] = ids[:n0]
        pages = np.arange(2, 2 + 24, dtype=np.int32)
        write = np.full((bucket // page,), 1, np.int32)
        write[:6] = pages[:6]
        cache, logits = make_model_prefill_fn(cfg)(
            params, cache, padded, (np.arange(bucket) < n0).astype(np.int32),
            jnp.int32(slot), jnp.asarray(write), jnp.asarray(pages))
        np.testing.assert_allclose(logits[0], want[n0 - 1], atol=2e-4)
        decode = make_model_decode_fn(cfg)
        active = jnp.asarray([False, True])
        for t in range(n0, 80):
            tokens = jnp.asarray([0, ids[t]], jnp.int32)
            cache, logits = decode(params, cache, tokens, active)
            np.testing.assert_allclose(logits[slot], want[t], atol=2e-4)
    assert cache.lengths.tolist() == [0, 80]
    assert int(cache.counters["moe_steps"][0]) == 59
    assert cache.counters["moe_load"].shape == (4, 8)
    # 59 steps of ONE active slot: each held expert layer got at most 4 rows
    load = np.asarray(cache.counters["moe_load"]).sum(-1)
    assert (load <= 59 * 4).all() and (load > 0).all()


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """16 experts over 8 chips (2 each), one group: the routed parts of all
    eight shares plus the shared expert ONCE are the uncut layer, program
    and reference alike (the guide's test of the cut)."""
    from apex_tpu.models import deepseek

    cfg, params = tiny
    whole = dataclasses.replace(cfg, experts_held=16)
    lp = jax.tree.map(lambda a: a[0], seasoned(exaone_moe.init(
        jax.random.PRNGKey(9), whole))["moe"])
    x = jax.random.normal(jax.random.PRNGKey(10), (37, cfg.hidden_size))
    real = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        uncut, sizes, chosen = exaone_moe.expert_block(lp, x, whole, real)
        parts, ref_parts, rows = [], [], 0
        for share in range(8):
            mine = dataclasses.replace(cfg, experts_held=2,
                                       expert_offset=2 * share)
            held = {**lp, "w_gate_up": lp["w_gate_up"][2 * share:][:2],
                    "w_down": lp["w_down"][2 * share:][:2]}
            part, shared, share_sizes, share_chosen = deepseek.expert_parts(
                held, x, mine, real)
            # every chip routes over all 16 alike
            np.testing.assert_array_equal(share_chosen, chosen)
            np.testing.assert_array_equal(share_sizes,
                                          sizes[2 * share:][:2])
            rows += int(share_sizes.sum())
            parts.append(part)
            ref_parts.append(ref.experts_of(held, sizes_of(mine), x)[0])
        ref_shared = ref.experts_of(lp, sizes_of(whole), x)[1]
        theirs, ref_chosen = ref.expert_mlp(lp, sizes_of(whole), x)
        norm = lambda y: exaone_moe._rms(lp["mlp_norm"], y, cfg.rms_norm_eps)
    assert rows == 37 * 4           # every assignment is on exactly one chip
    np.testing.assert_allclose(x + norm(sum(parts) + shared), uncut,
                               atol=2e-5)
    np.testing.assert_allclose(x + norm(sum(ref_parts) + ref_shared), theirs,
                               atol=2e-5)
    np.testing.assert_allclose(uncut, theirs, atol=2e-5)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(ref_chosen, -1))


def test_a_long_prompt_goes_through_the_dense_mlp_in_blocks(tiny,
                                                           monkeypatch):
    cfg, params = tiny
    lp = params["dense"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.hidden_size))
    whole = exaone_moe.dense_block(lp, x, cfg)
    monkeypatch.setattr(exaone_moe, "_MLP_ROWS", 16)
    np.testing.assert_allclose(exaone_moe.dense_block(lp, x, cfg), whole,
                               atol=1e-6)


def test_rotary_is_the_default_one_over_the_whole_head():
    """Pair ``i`` is ``(x[i], x[i + d / 2])`` at ``theta^(-2i/d)``: a
    rotation (norms kept), the identity at position 0, and the score of two
    rotated vectors depends on the distance alone."""
    x, y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.asarray([0, 1, 7, 100, 5000])
    rx = exaone_moe.rope(x, pos, 1e6)
    np.testing.assert_allclose(rx[0], x[0], atol=1e-7)
    np.testing.assert_allclose(jnp.sum(rx * rx, -1), jnp.sum(x * x, -1),
                               rtol=1e-5)
    first = np.cos(1.0) * x[1, 0, 0] - np.sin(1.0) * x[1, 0, 8]
    np.testing.assert_allclose(rx[1, 0, 0], first, rtol=1e-5)
    a, b = x[:1, :1], y[:1, :1]
    near = jnp.sum(exaone_moe.rope(a, pos[:1] + 3, 1e6)
                   * exaone_moe.rope(b, pos[:1], 1e6))
    far = jnp.sum(exaone_moe.rope(a, pos[:1] + 903, 1e6)
                  * exaone_moe.rope(b, pos[:1] + 900, 1e6))
    np.testing.assert_allclose(near, far, atol=1e-3)


def test_config_states_the_seam_and_refuses_what_it_cannot_hold():
    cfg = exaone_moe.k_exaone_236b_a23b()
    assert (cfg.kv_layers, cfg.window_layers, cfg.window, cfg.kv_row_width) \
        == (12, 36, 128, 1024)
    assert cfg.pattern == (True, True, False, True) and cfg.moe_layers == 47
    assert not cfg.recurrent and not cfg.latent
    assert cfg.counter_shapes() == {"moe_load": (47, 128), "moe_hit": (47,),
                                    "moe_steps": (1,)}
    with pytest.raises(ValueError, match="not among the router's"):
        exaone_moe.exaone_moe_tiny(experts_held=8, expert_offset=12)
    with pytest.raises(ValueError, match="layers of types"):
        exaone_moe.exaone_moe_tiny(num_layers=4)
    assert cfg.period == exaone_moe.exaone_moe_tiny().period == 4
    assert exaone_moe.exaone_moe_tiny(
        layer_types=(SLIDING,) * 4 + (FULL,)).period == 5
    with pytest.raises(ValueError, match="one kind alone"):
        exaone_moe.exaone_moe_tiny(layer_types=(SLIDING,) * 5)
