"""``apex_tpu.models.bailing_hybrid`` against the benchmark's plain reference
(``benchmark/reference/ling3_flash_vl.py``: float32, KDA as the token-by-token
recurrence, MLA expanded, no cache) on seeded weights at a tiny size; prefill
and decode through state and latent pool against the model's own forward;
the share of the experts a chip holds against the uncut layer; and what the
published config leaves open, one field each, refused when it says another
form."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import bailing_hybrid as bh
from apex_tpu.models import deepseek
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
ref = harness.load_module("reference", "ling3_flash_vl", BENCH)


def sizes_of(cfg: bh.BailingHybridConfig, first_layer=1) -> dict:
    """The reference's sizes for a program config."""
    return {"vocab": cfg.vocab_size, "hidden": cfg.hidden_size,
            "depth": cfg.num_layers, "first_layer": first_layer,
            "layer_types": tuple(cfg.layer_types),
            "kda_layers": cfg.kda_layers, "mla_layers": cfg.kv_layers,
            "dense_layers": cfg.first_k_dense,
            "expert_layers": cfg.moe_layers, "heads": cfg.num_heads,
            "head_dim": cfg.head_dim, "conv_kernel": cfg.conv_kernel,
            "kda_lower_bound": cfg.kda_lower_bound,
            "kv_rank": cfg.kv_lora_rank, "nope": cfg.qk_nope_head_dim,
            "rope": cfg.qk_rope_head_dim, "v_dim": cfg.v_head_dim,
            "dense_ffn": cfg.ffn_size, "expert_ffn": cfg.moe_ffn_size,
            "expert_width": cfg.moe_ffn_size,
            "shared_ffn": cfg.shared_experts * cfg.moe_ffn_size,
            "router_experts": cfg.num_experts,
            "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "experts_per_token": cfg.experts_per_token,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scale": cfg.routed_scaling_factor,
            "eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "latent_width": cfg.latent_width, "row_width": cfg.kv_row_width,
            "cache_dtype": "float32", "positions": 128}


def seasoned(params, seed=5):
    """The tree with a router bias that changes choices and norm weights
    that are not all ones (so that a norm in the wrong place shows)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 128))

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
    cfg = bh.bailing_hybrid_tiny()
    return cfg, seasoned(bh.init(jax.random.PRNGKey(1), cfg))


def test_the_layer_pattern_is_the_published_one():
    """Layer ``l`` is MLA iff ``(l + 1) % 6 == 0``: seven of 42; the cut
    (layers 1-7) is a KDA layer and then ``K K K M K K``."""
    whole = bh.ling3_flash()
    assert [i for i, t in enumerate(whole.layer_types) if t == bh.MLA] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert (whole.kda_layers, whole.kv_layers, whole.moe_layers) \
        == (35, 7, 40)
    assert bh.layer_types_of(1, 7, 6) == (bh.KDA,) * 4 + (bh.MLA,) \
        + (bh.KDA,) * 2
    tiny = bh.bailing_hybrid_tiny()
    assert (tiny.kda_layers, tiny.kv_layers, tiny.moe_layers) == (6, 1, 6)
    assert tiny.recurrent and tiny.latent
    assert tiny.kv_row_width == 128 and tiny.latent_width == 40
    assert whole.kv_row_width == 640 and whole.latent_width == 576
    assert whole.state_bytes_per_slot() == 35 * (32 * 128 * 128 + 3 * 12288) * 4


def test_forward_matches_the_plain_reference(tiny):
    """Logits at every position, and the routers' choices, against the
    reference given the same share (8 of 16 experts, the same weights): the
    chunked KDA against the recurrence, MLA expanded on both sides."""
    cfg, params = tiny
    n = 70
    ids = jnp.asarray(np.random.RandomState(0).randint(2, 512, n))
    with jax.default_matmul_precision("highest"):
        got = bh.apply(params, cfg, ids)
        mine = bh.prefill_layers(params, cfg, bh.embed(params, ids),
                                 jnp.ones((n,), jnp.int32), routes=True)[-1]
        want = ref.logits_at(params, sizes_of(cfg), ids, jnp.arange(n))
        theirs = ref.hidden_states(params, sizes_of(cfg), ids)[1]
    assert got.shape == (n, 512) and mine.shape == (6, n, 4)
    np.testing.assert_array_equal(np.sort(mine, -1), np.sort(theirs, -1))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_both_controls_part_from_the_program(tiny):
    """``scalar_gate`` (each head's decay its mean over the channels) and
    ``bfloat16_activations`` are other models: far from the program where
    the float32 reference is near."""
    cfg, params = tiny
    ids = jnp.asarray(np.random.RandomState(2).randint(2, 512, 64))
    at = jnp.arange(64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(bh.apply(params, cfg, ids))
        sound = np.asarray(ref.logits_at(params, sizes_of(cfg), ids, at))
        gate = np.asarray(ref.logits_at(params, sizes_of(cfg), ids, at,
                                        "scalar_gate"))
        low = np.asarray(ref.logits_at(params, sizes_of(cfg), ids, at,
                                       "bfloat16_activations"))
    near = np.abs(got - sound).max()
    assert near < 2e-4
    assert np.abs(got - gate).max() > 100 * near
    assert np.abs(got - low).max() > 20 * near
    # the first token has no history to decay: the gate's form cannot show
    np.testing.assert_allclose(gate[0], sound[0], atol=2e-4)
    with pytest.raises(ValueError):
        ref.hidden_states(params, sizes_of(cfg), ids, "float16")


def test_the_prompt_path_rounds_the_rows_the_cache_keeps(tiny):
    """With a bfloat16 cache the latent rows come out bfloat16 and keys and
    values are expanded from THOSE: nearer to the reference told the same
    ``cache_dtype`` than to the one that keeps float32 rows."""
    cfg, params = tiny
    ids = jnp.asarray(np.random.RandomState(4).randint(2, 512, 70))
    with jax.default_matmul_precision("highest"):
        x, states, tails, rows = bh.prefill_layers(
            params, cfg, bh.embed(params, ids), jnp.ones((70,), jnp.int32),
            jnp.bfloat16)
        got = np.asarray(bh.logits_of(params, cfg, x))
        kept = np.asarray(ref.logits_at(
            params, {**sizes_of(cfg), "cache_dtype": "bfloat16"}, ids,
            jnp.arange(70)))
        unrounded = np.asarray(ref.logits_at(params, sizes_of(cfg), ids,
                                             jnp.arange(70)))
    assert rows.dtype == jnp.bfloat16 and rows.shape == (1, 70, 128)
    assert states.dtype == tails.dtype == jnp.float32
    assert states.shape == (6, 4, 16, 16) and tails.shape == (6, 3, 192)
    rms = lambda d: float(np.sqrt((d ** 2).mean()))
    assert rms(got - kept) < rms(got - unrounded)


def test_prefill_then_decode_through_state_and_pool_is_the_forward(tiny):
    """The two cores on the seam's own terms (no engine): a prefill writes
    the slot's six states and tails and the ONE MLA layer's pages, decode
    steps update the state in place and read the pool in place; logits equal
    ``apply`` at every position. The cache is the hybrid's tuple with no
    ``v``."""
    from apex_tpu.serving.cache import HybridKVCache, init_hybrid_cache
    from apex_tpu.serving.decode import (make_model_decode_fn,
                                         make_model_prefill_fn)

    cfg, params = tiny
    page, slots, max_len = 4, 2, 96
    ids = np.random.RandomState(3).randint(2, 512, 60)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(bh.apply(params, cfg, jnp.asarray(ids)))
        cache = init_hybrid_cache(cfg, slots, max_len, 2 + slots * 24, page,
                                  jnp.float32)
        assert isinstance(cache, HybridKVCache) and cache.v is None
        assert cache.k.shape == (1, 2 + slots * 24, page, 128)
        assert cache.state.shape == (6, slots, 4, 16, 16)
        assert cache.conv.shape == (6, slots, 3, 192)
        assert len(jax.tree.leaves(cache)) == 5 + 3
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
        assert not np.any(np.asarray(cache.state[:, 0]))     # slot 0: as made
        decode = make_model_decode_fn(cfg)
        active = jnp.asarray([False, True])
        for t in range(n0, 60):
            tokens = jnp.asarray([0, ids[t]], jnp.int32)
            cache, logits = decode(params, cache, tokens, active)
            np.testing.assert_allclose(logits[slot], want[t], atol=2e-4)
    assert cache.lengths.tolist() == [0, 60]
    assert not np.any(np.asarray(cache.state[:, 0]))
    assert not np.any(np.asarray(cache.conv[:, 0]))
    assert int(cache.counters["moe_steps"][0]) == 39
    assert cache.counters["moe_load"].shape == (6, 8)
    load = np.asarray(cache.counters["moe_load"]).sum(-1)
    assert (load <= 39 * 4).all() and (load > 0).all()


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """16 experts in 4 groups over 8 chips (2 each): the routed parts of all
    eight shares plus the shared expert ONCE are the uncut layer, program
    and reference alike (the guide's test of the cut)."""
    cfg, params = tiny
    whole = dataclasses.replace(cfg, experts_held=16)
    lp = seasoned(bh.init(jax.random.PRNGKey(9), whole))["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(10), (37, cfg.hidden_size))
    real = jnp.ones((37,), bool)
    with jax.default_matmul_precision("highest"):
        uncut, sizes, chosen = deepseek.expert_mlp(lp, x, whole, real)
        u = bh._rms(lp["mlp_norm"], x, cfg.rms_norm_eps)
        parts, ref_parts, rows = [], [], 0
        for share in range(8):
            mine = dataclasses.replace(cfg, experts_held=2,
                                       expert_offset=2 * share)
            held = {**lp, "w_gate_up": lp["w_gate_up"][2 * share:][:2],
                    "w_down": lp["w_down"][2 * share:][:2]}
            part, shared, share_sizes, share_chosen = deepseek.expert_parts(
                held, u, mine, real)
            # every chip routes over all 16 alike
            np.testing.assert_array_equal(share_chosen, chosen)
            np.testing.assert_array_equal(share_sizes,
                                          sizes[2 * share:][:2])
            rows += int(share_sizes.sum())
            parts.append(part)
            ref_parts.append(ref.experts_of(held, sizes_of(mine), u)[0])
        ref_shared = ref.experts_of(lp, sizes_of(whole), u)[1]
        theirs, ref_chosen = ref.expert_mlp(lp, sizes_of(whole), x)
    assert rows == 37 * 4           # every assignment is on exactly one chip
    np.testing.assert_allclose(x + sum(parts) + shared, uncut, atol=2e-5)
    np.testing.assert_allclose(x + sum(ref_parts) + ref_shared, theirs,
                               atol=2e-5)
    np.testing.assert_allclose(uncut, theirs, atol=2e-5)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(ref_chosen, -1))


def test_the_same_model_is_drawn_whichever_experts_a_chip_holds():
    """The reference draws expert ``e`` by its number in the whole model: a
    chip that holds experts 2-3 draws what the chip that holds 0-7 draws
    there, and every other leaf alike."""
    cfg = bh.bailing_hybrid_tiny()
    first = sizes_of(cfg)
    other = sizes_of(dataclasses.replace(cfg, experts_held=2,
                                         expert_offset=2))
    a = ref.make_weights(first, ref.seed_key(7))
    b = ref.make_weights(other, ref.seed_key(7))
    for at in range(1, 7):
        la, lb = a["layers"][at], b["layers"][at]
        np.testing.assert_array_equal(la["w_gate_up"][2:4], lb["w_gate_up"])
        np.testing.assert_array_equal(la["w_down"][2:4], lb["w_down"])
        np.testing.assert_array_equal(la["router"]["kernel"],
                                      lb["router"]["kernel"])
    np.testing.assert_array_equal(a["layers"][0]["in_proj"]["kernel"],
                                  b["layers"][0]["in_proj"]["kernel"])
    # what the program takes: its own tree's structure and shapes
    mine = jax.eval_shape(lambda k: bh.init(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(a)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(mine)] \
        == [(x.shape, x.dtype) for x in jax.tree.leaves(a)]


def test_a_clamped_swiglu_in_a_held_layer_is_refused_by_name():
    """``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` are
    0 in every held layer; a non-zero entry is refused where the file is read
    (``sizes_of``, which runner and reference both call) rather than guessed
    at, and the program's config has no field for one."""
    assert "swiglu_limits" not in {
        f.name for f in dataclasses.fields(bh.BailingHybridConfig)}
    config = harness.rehearsal_view(harness.load_json(
        BENCH, "configs", "ling3_flash_vl.json"))
    assert ref.sizes_of(config)["depth"] == 7
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        clamped = {**config, key: [0, 0, 0, 0, 0, 0, 5]}
        with pytest.raises(ValueError, match=key):
            ref.sizes_of(clamped)
        with pytest.raises(ValueError, match=key):
            ref.sizes_of({**config, key: [0] * 6})      # one entry a layer


@pytest.mark.parametrize("field", sorted(bh.ASSUMED))
def test_each_assumed_form_is_one_name_held_by_both_sides(field):
    """What the published config leaves open is written ONE way, under one
    name in the program's ``ASSUMED``, the reference's and the configuration
    file's ``assumed``: the same names and values on all three, no option of
    the model's config, and a file that states another form is refused by
    name by the reference and by the runner."""
    assert bh.ASSUMED == ref.ASSUMED
    config = harness.load_json(BENCH, "configs", "ling3_flash_vl.json")
    said = config["assumed"][field]
    assert said[0] == bh.ASSUMED[field] and len(said) == 2 and said[1]
    assert field not in {
        f.name for f in dataclasses.fields(bh.BailingHybridConfig)}
    other = {**config, "assumed": {**config["assumed"],
                                   field: ["another", "why"]}}
    with pytest.raises(ValueError, match=field):
        ref.sizes_of(other)
    runner = harness.load_module("runners", "ling_serve")
    sz = ref.sizes_of(config)
    assert runner.model_config(config, sz).kda_layers == sz["kda_layers"]
    with pytest.raises(harness.BenchmarkError, match=field):
        runner.model_config(other, sz)


def test_each_head_of_the_mla_layer_has_one_gate(tiny):
    """``y = W_o concat_h(o_h * sigmoid(w_g,h . u))``: with only head 2's
    rows of ``W_o`` left, head 2's gate column moves the output and another
    head's does not; with every gate column zero each head passes half."""
    cfg, params = tiny
    lp = params["layers"][4]
    assert cfg.layer_types[4] == bh.MLA
    wq = cfg.num_heads * cfg.qk_head_dim + cfg.latent_width
    kernel = lp["a_proj"]["kernel"]
    assert kernel.shape == (64, wq + cfg.num_heads)
    x = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.hidden_size))
    mask = jnp.ones((24,), jnp.int32)
    v = cfg.v_head_dim
    only = jnp.zeros_like(lp["out"]["kernel"]).at[2 * v:3 * v].set(
        lp["out"]["kernel"][2 * v:3 * v])

    def run(a_kernel, out_kernel=only):
        with jax.default_matmul_precision("highest"):
            return bh.mla_prefill({**lp, "a_proj": {"kernel": a_kernel},
                                   "out": {"kernel": out_kernel}}, x, cfg,
                                  mask, jnp.float32)[0] - x

    base = run(kernel)
    assert float(jnp.abs(base).max()) > 1e-3
    np.testing.assert_array_equal(run(kernel.at[:, wq + 1].mul(-3.0)), base)
    assert float(jnp.abs(run(kernel.at[:, wq + 2].mul(-3.0)) - base).max()) \
        > 1e-4
    # every gate column zero: sigmoid(0) = 1 / 2 for every head, which the
    # reference gives with its gate's columns zero too
    zero = kernel.at[:, wq:].set(0.0)
    half = run(zero, lp["out"]["kernel"])
    theirs = ref.mla_layer(
        jax.tree.map(lambda a: a.astype(jnp.float32),
                     {k: lp[k] for k in ("norm", "kv_norm", "kv_b_k",
                                         "kv_b_v", "out")}
                     | {"a_proj": {"kernel": zero}}),
        sizes_of(cfg), x) - x
    np.testing.assert_allclose(half, theirs, atol=2e-5)
