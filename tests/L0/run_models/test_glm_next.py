"""``models.glm_next`` (GLM-5.3-Flash's family) on seeded random weights at
the tiny sizes of the benchmark's rehearsal: the whole forward against the
benchmark's plain reference, with equal routes and equal picks; the serving
path (prefill, then decode through state, pool and index cache) against the
forward at contexts below, at and above the top-k; a prompt taken a stretch at
a time; the cut (all eight shares add up to the uncut layer, program and
reference); the clamp where it binds; and every ``assumed`` field refused
where it is read when the file states another form."""

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import glm_next
from apex_tpu.models.deepseek import expert_parts, swiglu_mlp
from apex_tpu.serving import PagedDecodeEngine
from benchmark import harness

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
REF = harness.load_module("reference", "glm_5_3_flash", BENCH)
RUNNER = harness.load_module("runners", "glm_serve", BENCH)


def config_file():
    return harness.rehearsal_view(harness.load_json(
        BENCH, "configs", "glm_5_3_flash.json"))


@pytest.fixture(scope="module")
def tiny():
    """(sizes, config object, float32 weights the reference made)."""
    config = config_file()
    sz = {**REF.sizes_of(config), "cache_dtype": "float32", "positions": 256}
    cfg = RUNNER.model_config(config, sz)
    served = jax.jit(lambda key: REF.make_weights(sz, key))(REF.seed_key(5))
    return sz, cfg, jax.tree.map(lambda a: a.astype(jnp.float32), served)


def draw(seed, n):
    return np.random.RandomState(seed).randint(2, 512, n)


def test_the_tiny_preset_is_the_rehearsals_and_the_published_one_the_files():
    config = config_file()
    sz = REF.sizes_of(config)
    cfg = RUNNER.model_config(config, sz)
    assert cfg == glm_next.glm_next_tiny(
        max_position_embeddings=cfg.max_position_embeddings)
    full = harness.load_json(BENCH, "configs", "glm_5_3_flash.json")
    cut = RUNNER.model_config(full, REF.sizes_of(full))
    whole = glm_next.glm_5_3_flash()
    for name in ("hidden_size", "num_heads", "head_dim", "q_lora_rank",
                 "kv_lora_rank", "qk_nope_head_dim", "v_head_dim",
                 "index_n_heads", "index_head_dim", "index_topk",
                 "index_kpool", "hc_mult", "hc_sinkhorn_iters", "ffn_size",
                 "moe_ffn_size", "num_experts", "experts_per_token",
                 "swiglu_limit", "kda_gate_rank", "rms_norm_eps"):
        assert getattr(cut, name) == getattr(whole, name), name
    assert cut.layer_types == whole.layer_types[2:7] \
        == ("kda", "dsa", "kda", "kda", "kda")
    assert (cut.first_k_dense, cut.experts_held, cut.vocab_size) \
        == (1, 36, 19360)
    assert (whole.kv_row_width, whole.top_groups, whole.kv_layers) \
        == (512, 512, 11)


def test_apply_is_the_plain_reference_with_equal_routes_and_equal_picks(tiny):
    sz, cfg, params = tiny
    ids = jnp.asarray(draw(0, 91))
    got = glm_next.apply(params, cfg, ids)
    *_, chosen, picks = glm_next.prefill_layers(
        params, cfg, ids, jnp.ones(ids.shape, jnp.int32), routes=True)
    with jax.default_matmul_precision("highest"):
        want = REF.logits_at(params, sz, ids, jnp.arange(91))
        _, ref_chosen, ref_picks = REF.hidden_states(params, sz, ids)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(ref_chosen, -1))
    np.testing.assert_array_equal(picks, ref_picks)
    # the selection is engaged: late queries pick 4 of up to 22 groups
    assert picks.shape == (1, 91, 23) and int(picks[0, 90].sum()) == 4
    assert int(picks[0, 13].sum()) == 3


@pytest.mark.parametrize("precision", REF.PRECISIONS[1:])
def test_each_control_of_the_reference_reads_apart_from_it(tiny, precision):
    sz, _, params = tiny
    ids = jnp.asarray(draw(1, 64))
    with jax.default_matmul_precision("highest"):
        want = REF.logits_at(params, sz, ids, jnp.arange(64))
        low = REF.logits_at(params, sz, ids, jnp.arange(64), precision)
    assert float(jnp.abs(low - want).max()) > 0.05
    if precision == "dense_attention":
        # nothing to select below the top-k: the same numbers there
        np.testing.assert_allclose(low[:16], want[:16], atol=1e-5)


def engine(cfg, params, page, slots=2, max_len=128):
    return PagedDecodeEngine(
        params, cfg, num_slots=slots, max_len=max_len,
        num_pages=PagedDecodeEngine.full_pool_pages(slots, max_len, page),
        page_size=page, cache_dtype=jnp.float32, prefix_sharing=False,
        buckets=(16, 32, 64, 128))


@pytest.mark.parametrize("page", [4, 8])
@pytest.mark.parametrize("prompt, cont", [(5, 9), (12, 10), (45, 12)])
def test_prefill_and_decode_through_the_caches_are_the_forward(tiny, page,
                                                              prompt, cont):
    """Contexts below the top-k of 16 positions (5..13), across it (12..21)
    and above it (45..56); pages of one group and of two."""
    _, cfg, params = tiny
    ids, more = draw(prompt, prompt), draw(cont, cont)
    eng = engine(cfg, params, page)
    rows = [np.asarray(eng.prefill(1, ids))[0]]
    active = jnp.arange(2) == 1
    for i, t in enumerate(more):
        assert eng.prepare_decode({1: prompt + i}) == []
        rows.append(np.asarray(eng.decode(
            jnp.zeros((2,), jnp.int32).at[1].set(int(t)), active))[1])
    want = glm_next.apply(params, cfg, jnp.asarray(np.concatenate(
        [ids, more])))[prompt - 1:]
    np.testing.assert_allclose(np.stack(rows), want, atol=1e-4)
    counters = eng.read_counters()
    mapped = sum(prompt + i + 1 for i in range(cont))
    assert counters["dsa_rows_mapped"].tolist() == [mapped]
    read = int(counters["dsa_rows_read"][0])
    assert (read == mapped) == (prompt + cont - 1 < 20)
    assert read <= cont * (16 + 4)


@pytest.mark.parametrize("real", [101, 40])
def test_a_prompt_taken_a_stretch_at_a_time_is_the_prompt(tiny, monkeypatch,
                                                          real):
    """Four stretches of 32, of which a prompt of 40 runs two; a server's
    path (``last``) attends 64 keys from the first two stretches and 128 from
    the others, and runs no stretch behind the prompt's last token."""
    _, cfg, params = tiny
    ids = jnp.asarray(draw(3, 128))
    mask = (jnp.arange(128) < real).astype(jnp.int32)
    whole = glm_next.prefill_layers(params, cfg, ids, mask, routes=True)
    monkeypatch.setattr(glm_next, "_STRETCH", 32)
    monkeypatch.setattr(glm_next, "_QUERY_BLOCK", 16)
    monkeypatch.setattr(glm_next, "_KEY_EXTENT", 64)
    parts = glm_next.prefill_layers(params, cfg, ids, mask, routes=True)
    last = glm_next.prefill_layers(params, cfg, ids, mask, last=True)
    p = cfg.index_kpool

    def real_part(out):     # what lies behind the last token is never read
        x, states, tails, rows, (keys, index_tails) = out[:5]
        return [states, tails, rows[:, :real], keys[:, :real // p],
                index_tails[:, :real % p]]

    for a, b in zip([whole[0][:real]] + real_part(whole),
                    [parts[0][:real]] + real_part(parts)):
        np.testing.assert_allclose(a, b, atol=1e-4)
    for a, b in zip(whole[5:], parts[5:]):      # the routes and the picks
        np.testing.assert_array_equal(a[..., :real, :], b[..., :real, :])
    # a server asks for the last real token's streams alone, and keeps the
    # states, the tails, the rows and the pooled keys of the real positions
    assert last[0].shape == (1, cfg.hc_mult, cfg.hidden_size)
    np.testing.assert_allclose(last[0][0], whole[0][real - 1], atol=1e-4)
    for a, b in zip(real_part(whole), real_part(last)):
        np.testing.assert_allclose(a, b, atol=1e-4)


# -- the cut -----------------------------------------------------------------------

def test_all_eight_shares_add_up_to_the_uncut_layer_in_the_program(tiny):
    """The guide's test of the cut: the routed parts of the eight chips that
    share a layer (2 of the router's 16 experts each), and the shared expert
    once, are the layer with all 16 held."""
    import dataclasses

    sz, cfg, params = tiny
    lp = params["layers"][2]
    u = jax.random.normal(jax.random.PRNGKey(0), (24, cfg.hidden_size))
    real = jnp.ones((24,), bool)
    wide = jax.jit(lambda key: REF.make_weights(
        {**sz, "experts_held": 16}, key))(REF.seed_key(5))["layers"][2]
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), wide)
    # the cut holds the first experts of the same model
    np.testing.assert_array_equal(wide["w_down"][:8], lp["w_down"])
    uncut = dataclasses.replace(cfg, experts_held=16)
    routed, shared, sizes, chosen = expert_parts(wide, u, uncut, real)
    total = 0.0
    for share in range(8):
        part = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * share)
        mine = {**wide, "w_gate_up": wide["w_gate_up"][2 * share:2 * share + 2],
                "w_down": wide["w_down"][2 * share:2 * share + 2]}
        r, s, n, c = expert_parts(mine, u, part, real)
        np.testing.assert_array_equal(c, chosen)
        np.testing.assert_allclose(s, shared, atol=1e-6)
        np.testing.assert_array_equal(n, sizes[2 * share:2 * share + 2])
        total = total + r
    np.testing.assert_allclose(total, routed, atol=2e-5)
    assert int(sizes.sum()) == 24 * cfg.experts_per_token


def test_all_eight_shares_add_up_to_the_uncut_layer_in_the_reference(tiny):
    sz, _, _ = tiny
    u = jax.random.normal(jax.random.PRNGKey(0), (24, sz["hidden"]))
    wide_sz = {**sz, "experts_held": 16}
    wide = jax.jit(lambda key: REF.make_weights(wide_sz, key))(
        REF.seed_key(5))["layers"][2]
    with jax.default_matmul_precision("highest"):
        routed, shared, chosen = REF.experts_of(wide, wide_sz, u)
        total = 0.0
        for share in range(8):
            part = {**sz, "experts_held": 2, "expert_offset": 2 * share}
            mine = {**wide,
                    "w_gate_up": wide["w_gate_up"][2 * share:2 * share + 2],
                    "w_down": wide["w_down"][2 * share:2 * share + 2]}
            r, s, c = REF.experts_of(mine, part, u)
            np.testing.assert_array_equal(c, chosen)
            np.testing.assert_allclose(s, shared, atol=1e-6)
            total = total + r
    np.testing.assert_allclose(total, routed, atol=2e-5)


def test_the_references_experts_work_only_the_rows_that_chose_them(
        tiny, monkeypatch):
    """Over a sequence of whole blocks an expert of the reference works the
    rows that chose it, a block at a time, and no others: the same numbers as
    every expert over every row (64 rows in blocks of 16, 8 experts held of
    16, 4 a token: some experts take two blocks, some one)."""
    sz, _, params = tiny
    lp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params["layers"][1])
    u = jax.random.normal(jax.random.PRNGKey(3), (64, sz["hidden"]))
    with jax.default_matmul_precision("highest"):
        dense = REF.experts_of(lp, sz, u)
        monkeypatch.setattr(REF, "BLOCK", 16)
        picked = REF.experts_of(lp, sz, u)
    np.testing.assert_array_equal(dense[2], picked[2])
    for a, b in zip(dense[:2], picked[:2]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(jnp.abs(dense[0]).max()) > 0.1


@pytest.mark.parametrize("which", ["experts", "shared_expert", "dense_mlp"])
def test_the_clamp_binds_and_both_sides_clamp_alike(tiny, which):
    """Inputs scaled until ``swiglu_limit`` binds: program and reference
    agree, and neither is what an unclamped SwiGLU gives."""
    import dataclasses

    sz, cfg, params = tiny
    scale = 400.0
    u = scale * jax.random.normal(jax.random.PRNGKey(1), (16, cfg.hidden_size))
    real = jnp.ones((16,), bool)
    free = dataclasses.replace(cfg, swiglu_limit=0.0)
    with jax.default_matmul_precision("highest"):
        if which == "dense_mlp":
            lp = params["layers"][0]
            got = swiglu_mlp(lp, u, cfg.swiglu_limit)
            loose = swiglu_mlp(lp, u)
            want = REF.dense_mlp_of(lp, sz, u)
        else:
            lp = params["layers"][1]
            at = 0 if which == "experts" else 1
            got = expert_parts(lp, u, cfg, real)[at]
            loose = expert_parts(lp, u, free, real)[at]
            want = REF.experts_of(lp, sz, u)[at]
        gate_up = u @ (lp["gate_up"]["kernel"] if which == "dense_mlp"
                       else lp["shared_gate_up"]["kernel"])
    assert float(jnp.abs(gate_up).max()) > 10 * cfg.swiglu_limit
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
    assert float(jnp.abs(got - loose).max()) > 1.0


# -- the assumed forms -------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(REF.ASSUMED))
def test_a_file_that_states_another_form_is_refused_where_it_is_read(name):
    """Each ``assumed`` field, by the reference and by the runner that builds
    the program's config; both name the field."""
    assert glm_next.ASSUMED == REF.ASSUMED
    config = copy.deepcopy(config_file())
    assert config["assumed"][name][0] == REF.ASSUMED[name]
    config["assumed"][name][0] = "another_form"
    with pytest.raises(ValueError, match=name):
        REF.sizes_of(config)
    sound = REF.sizes_of(config_file())
    with pytest.raises(harness.BenchmarkError, match=name):
        RUNNER.model_config(config, sound)


def test_a_form_the_file_adds_or_a_published_form_changed_is_refused():
    config = copy.deepcopy(config_file())
    config["assumed"]["a_new_choice"] = ["some_form", "why"]
    with pytest.raises(ValueError, match="a_new_choice"):
        REF.sizes_of(config)
    for key, value in (("qk_rope_head_dim", 64), ("n_group", 8),
                       ("scoring_func", "softmax"), ("mhc", False)):
        config = copy.deepcopy(config_file())
        config[key] = value
        with pytest.raises(ValueError, match=key):
            REF.sizes_of(config)
    config = copy.deepcopy(config_file())
    config["layer_types"] = config["layer_types"][:4]
    with pytest.raises(ValueError, match="layer_types"):
        REF.sizes_of(config)
    with pytest.raises(ValueError, match="index_topk"):
        glm_next.glm_next_tiny(index_topk=18)
    with pytest.raises(ValueError, match="whole groups"):
        glm_next.glm_next_tiny().index_shapes(2, 10, 6)
