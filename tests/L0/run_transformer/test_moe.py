"""The expert layer's four steps (``transformer.functional.moe``): the router,
the sort by held expert, the grouped product ``apex_moe_gmm_fwd`` (interpret
mode here) against a loop over the experts, and the weighted sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import moe


def loop(lhs, rhs, sizes, activation=None):
    """Row ``i`` of group ``g`` times ``rhs[g]``, one expert at a time, in
    float64; rows past the last group are NaN (nothing defined there)."""
    lhs, rhs = np.asarray(lhs, np.float64), np.asarray(rhs, np.float64)
    out = np.full((lhs.shape[0], rhs.shape[2]), np.nan)
    at = 0
    for g, n in enumerate(np.asarray(sizes)):
        part = lhs[at:at + n] @ rhs[g]
        out[at:at + n] = np.square(np.maximum(part, 0.0)) \
            if activation == "relu2" else part
        at += n
    return out


SIZES = {
    "ragged": [5, 0, 1, 17, 0, 0, 3, 16],
    "single_rows": [1, 1, 1, 1, 1, 1, 1, 1],
    "all_empty": [0, 0, 0, 0, 0, 0, 0, 0],
    "one_long_group": [0, 0, 40, 0, 0, 0, 0, 0],
    "first_and_last": [2, 0, 0, 0, 0, 0, 0, 33],
    "full": [6, 6, 6, 6, 6, 6, 6, 6],
}


@pytest.mark.parametrize("activation", [None, "relu2"])
@pytest.mark.parametrize("case", list(SIZES))
def test_grouped_product_is_the_loop_over_experts(case, activation):
    sizes = np.asarray(SIZES[case], np.int32)
    m, k, n = 48, 32, 128
    rng = np.random.RandomState(len(case))
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((8, k, n)), jnp.float32)
    got = np.asarray(moe.grouped_matmul(lhs, rhs, jnp.asarray(sizes),
                                        activation=activation))
    want = loop(lhs, rhs, sizes, activation)
    rows = int(sizes.sum())
    assert got.shape == (m, n) and got.dtype == np.float32
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=1e-4, atol=1e-4)


def test_grouped_product_in_bfloat16_with_rows_no_multiple_of_the_tile():
    sizes = np.asarray([3, 0, 9, 7], np.int32)
    rng = np.random.RandomState(5)
    lhs = jnp.asarray(rng.standard_normal((21, 64)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((4, 64, 256)), jnp.bfloat16)
    got = moe.grouped_matmul(lhs, rhs, jnp.asarray(sizes),
                             out_dtype=jnp.bfloat16)
    assert got.shape == (21, 256) and got.dtype == jnp.bfloat16
    want = loop(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    np.testing.assert_allclose(np.asarray(got, np.float32)[:19], want[:19],
                               rtol=2e-2, atol=2e-1)


def test_grouped_product_refuses_shapes_that_do_not_fit():
    with pytest.raises(ValueError, match="do not fit"):
        moe.grouped_matmul(jnp.zeros((8, 4)), jnp.zeros((2, 5, 8)),
                           jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="activation"):
        moe.grouped_matmul(jnp.zeros((8, 4)), jnp.zeros((2, 4, 8)),
                           jnp.zeros((2,), jnp.int32), activation="gelu")


def test_route_takes_the_largest_biased_scores_and_weighs_by_the_unbiased():
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0, 0.5, 3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0, 0.0, -5.0])
    chosen, weights = moe.route(logits, bias, 3, 5.0)
    # the bias lifts expert 3 in and pushes expert 5 out of the choice ...
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 2, 3]
    s = jax.nn.sigmoid(logits[0])
    picked = np.asarray(s)[np.asarray(chosen[0])]
    # ... and leaves the weights to the scores themselves
    np.testing.assert_allclose(weights[0], 5.0 * picked / picked.sum(),
                               rtol=1e-6)
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32


def test_route_breaks_ties_towards_the_lower_index():
    """Equal scores: ``lax.top_k`` keeps the lower index, as a stable
    descending sort does (the reference's rule)."""
    logits = jnp.zeros((2, 6)).at[1, 4].set(1.0)
    chosen, _ = moe.route(logits, jnp.zeros((6,)), 3, 1.0)
    assert np.asarray(chosen).tolist() == [[0, 1, 2], [4, 0, 1]]
    want = np.argsort(-np.asarray(jax.nn.sigmoid(logits)), axis=-1,
                      kind="stable")[:, :3]
    np.testing.assert_array_equal(chosen, want)


def test_dispatch_sorts_by_held_expert_and_drops_no_token():
    experts = jnp.asarray([[5, 2, 9], [2, 4, 7], [4, 5, 0], [6, 6, 2]])
    weights = jnp.arange(12, dtype=jnp.float32).reshape(4, 3) + 1.0
    real = jnp.asarray([True, True, True, False])       # row 3 is padding
    d = moe.dispatch(experts, weights, expert_offset=4, experts_held=4,
                     real=real)
    # experts 4..7 are held: 4 twice, 5 twice, 6 never (its row is padding),
    # 7 once; every one of those assignments is there, in token order
    assert np.asarray(d.sizes).tolist() == [2, 2, 0, 1]
    n = int(d.sizes.sum())
    assert np.asarray(d.token[:n]).tolist() == [1, 2, 0, 2, 1]
    assert np.asarray(d.weight[:n]).tolist() == [5.0, 7.0, 1.0, 8.0, 6.0]
    assert np.asarray(d.here).tolist() == [True] * n + [False] * (12 - n)
    assert not np.any(np.asarray(d.weight[n:]))


def test_combine_is_each_rows_weighted_sum_over_what_was_computed_here():
    experts = jnp.asarray([[0, 1], [1, 3], [2, 3]])
    weights = jnp.asarray([[0.5, 0.25], [1.0, 2.0], [3.0, 4.0]])
    d = moe.dispatch(experts, weights, expert_offset=0, experts_held=2)
    out = jnp.arange(6, dtype=jnp.float32)[:, None] * jnp.ones((6, 4))
    # sorted assignments: (row 0, e0), (row 0, e1), (row 1, e1), then absent
    out = out.at[3:].set(jnp.nan)           # never written by the product
    got = moe.combine(out, d, rows=3)
    np.testing.assert_allclose(got[:, 0], [0.5 * 0 + 0.25 * 1, 1.0 * 2, 0.0])
    assert got.shape == (3, 4) and not np.any(np.isnan(np.asarray(got)))


def test_the_whole_layer_over_held_experts_is_the_masked_dense_sum():
    """route, dispatch, two grouped products and combine against a dense
    loop over the held experts with the router's weights as a mask."""
    rng = np.random.RandomState(7)
    rows, experts, held, offset, k = 20, 16, 8, 4, 4
    x = jnp.asarray(rng.standard_normal((rows, 32)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((held, 32, 128)) / 6, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((held, 128, 128)) / 11, jnp.float32)
    logits = jnp.asarray(rng.standard_normal((rows, experts)), jnp.float32)
    chosen, weights = moe.route(logits, jnp.zeros((experts,)), k, 2.5)
    d = moe.dispatch(chosen, weights, offset, held)
    mid = moe.grouped_matmul(x[d.token], w1, d.sizes, activation="relu2")
    got = moe.combine(moe.grouped_matmul(mid, w2, d.sizes), d, rows)
    want = np.zeros((rows, 128))
    for r in range(rows):
        for e, w in zip(np.asarray(chosen[r]), np.asarray(weights[r])):
            if offset <= e < offset + held:
                h = np.square(np.maximum(
                    np.asarray(x[r], np.float64) @ np.asarray(w1[e - offset]),
                    0.0))
                want[r] += w * (h @ np.asarray(w2[e - offset]))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_float32_rows_into_bfloat16_matrices_go_in_as_two_terms():
    """``hi + lo``: 16 bits of the rows' mantissa reach the product, where
    rows rounded to bfloat16 first lose all but 8."""
    sizes = np.asarray([7, 0, 12, 5], np.int32)
    rng = np.random.RandomState(6)
    lhs = jnp.asarray(rng.standard_normal((24, 128)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.bfloat16)
    want = loop(lhs, rhs.astype(jnp.float32), sizes)
    two = np.asarray(moe.grouped_matmul(lhs, rhs, jnp.asarray(sizes)))
    one = np.asarray(moe.grouped_matmul(lhs.astype(jnp.bfloat16), rhs,
                                        jnp.asarray(sizes)))
    err = lambda got: float(np.abs(got[:24] - want[:24]).max())
    assert err(two) < 2e-3 < 0.02 < err(one)


# -- the group limit (deepseek_v3: n_group 8, topk_group 4) ---------------------

def by_the_rule(logits, bias, k, scale, n_group, topk_group):
    """The published rule, row by row in numpy."""
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    choice = scores + np.asarray(bias, np.float64)
    rows, experts = choice.shape
    per = experts // n_group
    chosen, weights = [], []
    for r in range(rows):
        groups = choice[r].reshape(n_group, per)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        eligible = np.full(experts, -np.inf)
        for g in kept:
            eligible[g * per:(g + 1) * per] = choice[r, g * per:(g + 1) * per]
        picked = np.argsort(-eligible, kind="stable")[:k]
        chosen.append(picked)
        weights.append(scale * scores[r, picked] / scores[r, picked].sum())
    return np.stack(chosen), np.stack(weights)


def test_one_group_lowers_to_what_no_group_limit_lowers_to():
    args = (jax.ShapeDtypeStruct((6, 16), jnp.float32),
            jax.ShapeDtypeStruct((16,), jnp.float32))
    plain = jax.jit(lambda l, b: moe.route(l, b, 4, 2.5)).lower(*args)
    one = jax.jit(lambda l, b: moe.route(l, b, 4, 2.5, 1, 1)).lower(*args)
    assert plain.as_text() == one.as_text()


def test_group_limited_route_is_the_published_rule():
    rng = np.random.RandomState(11)
    logits = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.standard_normal((32,)), jnp.float32)
    chosen, weights = moe.route(logits, bias, 6, 2.5, n_group=8, topk_group=3)
    want, want_w = by_the_rule(logits, bias, 6, 2.5, 8, 3)
    np.testing.assert_array_equal(chosen, want)
    np.testing.assert_allclose(weights, want_w, rtol=1e-5)
    # the limit does something here: without it other experts are chosen
    free, _ = moe.route(logits, bias, 6, 2.5)
    assert (np.sort(np.asarray(free), -1)
            != np.sort(np.asarray(chosen), -1)).any()


def test_a_group_outside_the_best_is_never_chosen_whatever_its_top_expert():
    """Group 2 holds the single largest score and one dud: its two best sum
    to less than groups 0 and 1, so with two groups kept it is out, and its
    top expert with it."""
    logits = jnp.asarray([[2.0, 1.9, 1.8, 1.7, 5.0, -9.0]])
    chosen, weights = moe.route(logits, jnp.zeros((6,)), 3, 1.0, n_group=3,
                                topk_group=2)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 2]
    np.testing.assert_allclose(np.asarray(weights).sum(), 1.0, rtol=1e-6)
    # all three groups kept: the largest score is back
    chosen, _ = moe.route(logits, jnp.zeros((6,)), 3, 1.0, n_group=3,
                          topk_group=3)
    assert 4 in np.asarray(chosen[0]).tolist()


def test_the_bias_moves_groups_and_choices_and_never_the_weights():
    logits = jnp.asarray([[1.0, 0.9, 0.2, 0.1, 0.8, 0.7, 0.0, -0.1]])
    none = jnp.zeros((8,))
    chosen, _ = moe.route(logits, none, 2, 2.5, n_group=4, topk_group=1)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1]
    # a bias on group 2's experts makes it the best group ...
    bias = none.at[4].set(0.3).at[5].set(0.3)
    chosen, weights = moe.route(logits, bias, 2, 2.5, n_group=4,
                                topk_group=1)
    assert sorted(np.asarray(chosen[0]).tolist()) == [4, 5]
    # ... and the weights are the unbiased scores', normalised and scaled
    s = np.asarray(jax.nn.sigmoid(logits[0]))[np.asarray(chosen[0])]
    np.testing.assert_allclose(weights[0], 2.5 * s / s.sum(), rtol=1e-6)


def test_group_limits_that_cannot_give_k_are_refused():
    with pytest.raises(ValueError, match="cannot give"):
        moe.route(jnp.zeros((2, 16)), jnp.zeros((16,)), 6, 1.0, n_group=4,
                  topk_group=1)
    with pytest.raises(ValueError, match="cannot give"):
        moe.route(jnp.zeros((2, 10)), jnp.zeros((10,)), 2, 1.0, n_group=4,
                  topk_group=2)


def test_a_long_k_widens_the_column_tile_and_short_ones_keep_theirs():
    # the published latent experts (k 1024 and 2688): as before
    assert moe._column_tile(1024, 2688, 2) == 896
    assert moe._column_tile(2688, 1024, 2) == 512
    # hidden 7168 in bfloat16: 128 columns fit 3 MB, two lane tiles are taken
    assert moe._column_tile(7168, 4096, 2) == 256
    assert moe._column_tile(2048, 7168, 2) == 512
    assert moe._column_tile(64, 200, 4) == 200        # no multiple of 128


def test_a_layers_experts_are_read_out_of_the_stack_in_place():
    """``first_group``: ``rhs`` holds three layers' experts; a call names
    its layer by the first of them, traced or not, and gives what the
    layer's own slice gives."""
    sizes = jnp.asarray([5, 0, 9, 2], jnp.int32)
    rng = np.random.RandomState(8)
    lhs = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((3, 4, 64, 256)), jnp.float32)
    flat = stack.reshape(12, 64, 256)
    for layer in range(3):
        want = moe.grouped_matmul(lhs, stack[layer], sizes)
        got = jax.jit(lambda first: moe.grouped_matmul(
            lhs, flat, sizes, first_group=first))(jnp.int32(4 * layer))
        np.testing.assert_array_equal(np.asarray(got)[:16],
                                      np.asarray(want)[:16])
    with pytest.raises(ValueError, match="do not fit"):
        moe.grouped_matmul(lhs, flat[:3], sizes, first_group=0)
