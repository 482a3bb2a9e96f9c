"""The two delta-rule kernels with a decay per key CHANNEL (Kimi Delta
Attention: ``apex_kda_chunk_fwd`` / ``apex_kda_decode_fwd``) in interpret
mode against the recurrence written out token by token in float64, and the
scalar decay beside them: the same bodies under their old names."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import gated_delta as gd

BOUND = -5.0        # the layer's kda_lower_bound


def token_by_token(q, k, v, log_decay, beta, state=None):
    """S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T, o_t =
    S_t^T q_t, in float64, ``log_decay`` (H, s, d_k): (o (H, s, d_v), S)."""
    q, k, v, log_decay, beta = (np.asarray(x, np.float64)
                                for x in (q, k, v, log_decay, beta))
    heads, s, dk = q.shape
    dv = v.shape[-1]
    S = np.zeros((heads, dk, dv)) if state is None \
        else np.array(state, np.float64)
    out = np.zeros((heads, s, dv))
    eye = np.eye(dk)
    for t in range(s):
        for h in range(heads):
            kt = k[h, t]
            S[h] = (eye - beta[h, t] * np.outer(kt, kt)) @ (
                np.exp(log_decay[h, t])[:, None] * S[h]) \
                + beta[h, t] * np.outer(kt, v[h, t])
            out[h, t] = S[h].T @ q[h, t]
    return out, S


def inputs(seed, heads, s, dk, dv, decay="mixed", alike=0.0):
    """Normalised q and k, ``beta`` in (0, 1), and a decay per channel in
    ``(BOUND, 0)``: ``mixed`` draws every channel's from the whole range,
    ``bound`` sets all of them AT the bound, ``slow`` near 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (heads, s, dk))
    k = jax.random.normal(ks[1], (heads, s, dk)) + alike
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (heads, s, dv))
    log_decay = BOUND * jax.nn.sigmoid(
        3.0 * jax.random.normal(ks[3], (heads, s, dk)))
    if decay == "bound":
        log_decay = jnp.full_like(log_decay, BOUND)
    elif decay == "slow":
        log_decay = 1e-3 * log_decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (heads, s)))
    return q, k, v, log_decay, beta


@pytest.mark.parametrize("s, chunk, decay, alike", [
    (64, 64, "mixed", 0.0),     # one whole chunk
    (128, 64, "mixed", 0.5),    # state carried over a chunk boundary
    (320, 64, "mixed", 0.0),    # five chunks
    (64, 64, "bound", 0.0),     # log a = -5 on every channel for 64 tokens:
    #                             the diagonal block's factor is exp(75)
    (128, 64, "slow", 1.5),     # decays near 0 and keys alike
    (48, 16, "mixed", 0.5),     # the solve's block is the whole chunk
])
def test_chunked_kda_matches_the_recurrence(s, chunk, decay, alike):
    args = inputs(s + chunk, 3, s, 16, 24, decay, alike)
    o, state = jax.jit(lambda *a: gd.gated_delta_chunked(*a, chunk=chunk))(
        *args)
    assert np.all(np.isfinite(np.asarray(o)))
    want_o, want_s = token_by_token(*args)
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    np.testing.assert_allclose(state, want_s, atol=1e-4)


@pytest.mark.parametrize("length", [1, 37, 64, 100])
def test_a_padded_tail_leaves_the_state_as_found(length):
    """Positions of ``log a = 0`` and ``b = 0`` past the true length: the
    state after the padded sequence is the state at ``length``."""
    s = 128
    q, k, v, log_decay, beta = inputs(length, 2, s, 16, 24, alike=0.5)
    real = jnp.arange(s) < length
    o, state = gd.gated_delta_chunked(
        q, k, v, jnp.where(real[None, :, None], log_decay, 0.0),
        jnp.where(real, beta, 0.0))
    want_o, want_s = token_by_token(*(x[:, :length] for x in
                                      (q, k, v, log_decay, beta)))
    np.testing.assert_allclose(o[:, :length], want_o, atol=1e-4)
    np.testing.assert_allclose(state, want_s, atol=1e-4)


def test_a_decay_per_channel_has_to_have_the_keys_shape():
    q, k, v, log_decay, beta = inputs(0, 2, 64, 16, 24)
    with pytest.raises(ValueError, match="decay per key channel"):
        gd.gated_delta_chunked(q, k, v, log_decay[..., :8], beta)
    with pytest.raises(ValueError, match="decay per key channel"):
        gd.gated_delta_step(q[:, 0][None], k[:, 0][None], v[:, 0][None],
                            log_decay[:, 0, :8][None], beta[:, 0][None],
                            jnp.zeros((1, 1, 2, 16, 24)), jnp.int32(0),
                            jnp.asarray([True]))


def test_equal_channels_give_what_the_scalar_decay_gives():
    """A vector whose channels are all one number is the scalar rule: the
    two forms of both kernels agree."""
    q, k, v, log_decay, beta = inputs(5, 3, 128, 16, 24)
    scalar = log_decay[..., 0]
    same = jnp.broadcast_to(scalar[..., None], log_decay.shape)
    o1, s1 = gd.gated_delta_chunked(q, k, v, scalar, beta)
    o2, s2 = gd.gated_delta_chunked(q, k, v, same, beta)
    np.testing.assert_allclose(o1, o2, atol=2e-5)
    np.testing.assert_allclose(s1, s2, atol=2e-5)
    state = jnp.stack([s1])[:, None]                # (1, 1, H, dk, dv)
    step = lambda d: gd.gated_delta_step(
        q[:, 0][None], k[:, 0][None], v[:, 0][None], d, beta[:, 0][None],
        state, jnp.int32(0), jnp.asarray([True]))
    (o1, t1), (o2, t2) = step(scalar[:, 0][None]), step(same[:, 0][None])
    np.testing.assert_allclose(o1, o2, atol=1e-6)
    np.testing.assert_allclose(t1, t2, atol=1e-6)


def test_step_against_the_recurrence_inactive_slots_and_other_layers():
    """One step on layer 1 of a stacked state for three slots, the middle
    one inactive: the active slots equal one token of the recurrence from
    their state; the inactive slot keeps its state and gives zeros; layers 0
    and 2 are untouched."""
    heads, dk, dv, slots = 3, 16, 24, 3
    q, k, v, log_decay, beta = inputs(3, heads, slots, dk, dv)
    at = lambda x: jnp.moveaxis(x, 1, 0)            # (slots, H, ...)
    start = jax.random.normal(jax.random.PRNGKey(9),
                              (3, slots, heads, dk, dv))
    active = jnp.asarray([True, False, True])
    o, after = jax.jit(gd.gated_delta_step)(
        at(q), at(k), at(v), at(log_decay), at(beta), start, jnp.int32(1),
        active)
    for i in range(slots):
        one = lambda x: x[:, i:i + 1]
        want_o, want_s = token_by_token(one(q), one(k), one(v),
                                        one(log_decay), one(beta),
                                        start[1, i])
        if active[i]:
            np.testing.assert_allclose(o[i], want_o[:, 0], atol=2e-5)
            np.testing.assert_allclose(after[1, i], want_s, atol=2e-5)
        else:
            assert not np.any(np.asarray(o[i]))
            np.testing.assert_array_equal(after[1, i], start[1, i])
    np.testing.assert_array_equal(after[0], start[0])
    np.testing.assert_array_equal(after[2], start[2])


def test_the_stepped_state_comes_back_in_the_buffer_it_came_in():
    """Under donation the per-channel call aliases the state as the scalar
    call does: the lowered program names an input-output pair, under the
    per-channel call's own name."""
    heads, dk, dv, slots = 2, 16, 24, 2
    f32 = jnp.float32
    shapes = [((slots, heads, dk), f32), ((slots, heads, dk), f32),
              ((slots, heads, dv), f32), ((slots, heads, dk), f32),
              ((slots, heads), f32), ((4, slots, heads, dk, dv), f32),
              ((), jnp.int32), ((slots,), jnp.bool_)]
    lowered = jax.jit(gd.gated_delta_step, donate_argnums=5).lower(
        *[jax.ShapeDtypeStruct(s, d) for s, d in shapes])
    assert "tf.aliasing_output" in lowered.as_text()
    assert "apex_kda_decode_fwd" in lowered.as_text(debug_info=True)


def test_chunked_then_stepped_equals_stepped_all_the_way():
    """The chunked kernel over a prompt, then the step kernel token by
    token, against the step kernel from the first token on: the same outputs
    and the same state (and both the recurrence's)."""
    heads, dk, dv, prompt, new = 3, 16, 24, 64, 7
    q, k, v, log_decay, beta = inputs(11, heads, prompt + new, dk, dv)
    want_o, want_s = token_by_token(q, k, v, log_decay, beta)
    step = jax.jit(gd.gated_delta_step)
    active = jnp.asarray([True])

    def run(state, first):
        outs = []
        for t in range(first, prompt + new):
            o, state = step(q[:, t][None], k[:, t][None], v[:, t][None],
                            log_decay[:, t][None], beta[:, t][None], state,
                            jnp.int32(0), active)
            outs.append(o[0])
        return jnp.stack(outs, 1), state

    _, state = gd.gated_delta_chunked(*(x[:, :prompt] for x in
                                        (q, k, v, log_decay, beta)))
    o_mixed, s_mixed = run(state[None, None], prompt)
    o_step, s_step = run(jnp.zeros((1, 1, heads, dk, dv)), 0)
    np.testing.assert_allclose(o_mixed, o_step[:, prompt:], atol=1e-4)
    np.testing.assert_allclose(s_mixed, s_step, atol=1e-4)
    np.testing.assert_allclose(o_step, want_o, atol=1e-4)
    np.testing.assert_allclose(s_step[0, 0], want_s, atol=1e-4)


#: sha256 of the lowered text of both kernels' callers with a SCALAR decay,
#: at the shapes below, as the parent commit (f9c0a1f) lowers them: the
#: per-channel branch may not change a byte of the scalar programs
PARENT_TEXT = {
    "chunked": "85f07dd65a81e20f69c312d3fc6db33f0bb420ca2293ba47d37252cdee0d25d7",
    "step": "49c11f9ac02432d6c26a3a1c3f0e564e3d1b8bf1504a38f478beee6a51d5dec7",
}


def _scalar_programs():
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    return {
        "chunked": jax.jit(gd.gated_delta_chunked).lower(
            sds((2, 128, 16), f32), sds((2, 128, 16), f32),
            sds((2, 128, 24), f32), sds((2, 128), f32), sds((2, 128), f32)),
        "step": jax.jit(gd.gated_delta_step).lower(
            sds((3, 2, 16), f32), sds((3, 2, 16), f32), sds((3, 2, 24), f32),
            sds((3, 2), f32), sds((3, 2), f32), sds((2, 3, 2, 16, 24), f32),
            sds((), jnp.int32), sds((3,), jnp.bool_)),
    }


@pytest.mark.parametrize("which", sorted(PARENT_TEXT))
def test_a_scalar_decay_lowers_to_the_parents_text(which):
    text = _scalar_programs()[which].as_text()
    assert "apex_kda" not in _scalar_programs()[which].as_text(
        debug_info=True)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[which]


@pytest.mark.parametrize("length", [0, 1, 2, 3, 4, 11])
def test_the_ring_tail_steps_as_the_shifted_tail_does(length):
    """``conv_ring_step`` from ``ring_of_tail`` against ``conv_step`` from
    ``causal_conv``'s tail, five steps on from a prompt of ``length`` (short
    ones leave zeros in the ring): the same outputs, the ring the shifted
    tail's rows each at ``position % (w-1)``, and no row of a slot that is
    not active moved."""
    w, c, b = 4, 24, 3
    ks = jax.random.split(jax.random.PRNGKey(length), 3)
    weight = jax.random.normal(ks[0], (w, c))
    prompt = jax.random.normal(ks[1], (b, 16, c))
    steps = jax.random.normal(ks[2], (5, b, c))
    tail = jnp.stack([gd.causal_conv(prompt[i], weight, length)[1]
                      for i in range(b)])
    ring = jnp.stack([gd.ring_of_tail(tail[i], length) for i in range(b)])
    active = jnp.asarray([True, False, True])
    for t, x in enumerate(steps):
        pos = jnp.full((b,), length + t, jnp.int32)
        want, tail = gd.conv_step(x, tail, weight)
        got, stepped = gd.conv_ring_step(x, ring, weight, pos, active)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(stepped[1], ring[1])
        ring = gd.conv_ring_step(x, ring, weight, pos,
                                 jnp.ones((b,), bool))[1]
        for row in range(w - 1):        # tail[row] is position pos - 2 + row
            at = (length + t + 1 - (w - 1) + row) % (w - 1)
            np.testing.assert_array_equal(ring[:, at], tail[:, row])


def test_slots_at_different_positions_share_one_ring_step():
    """Each slot's ``pos`` picks its own oldest row and its own order of
    taps."""
    w, c = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    weight = jax.random.normal(ks[0], (w, c))
    ring = jax.random.normal(ks[1], (3, w - 1, c))
    x = jax.random.normal(ks[2], (3, c))
    pos = jnp.asarray([9, 10, 11], jnp.int32)
    y, new = gd.conv_ring_step(x, ring, weight, pos, jnp.ones((3,), bool))
    for i, p in enumerate([9, 10, 11]):
        rows = [ring[i, (p - 3 + j) % 3] for j in range(3)] + [x[i]]
        np.testing.assert_allclose(
            y[i], sum(weight[j] * rows[j] for j in range(w)), rtol=1e-5,
            atol=1e-5)
        np.testing.assert_array_equal(new[i, p % 3], x[i])
