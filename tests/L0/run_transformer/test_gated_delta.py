"""The two Gated DeltaNet kernels in interpret mode against the recurrence
written out token by token in float64, and the convolution beside them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import gated_delta as gd


def token_by_token(q, k, v, log_decay, beta, state=None):
    """S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t, in float64: (o (H, s, d_v), S (H, d_k, d_v))."""
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
            S[h] = np.exp(log_decay[h, t]) * (
                eye - beta[h, t] * np.outer(kt, kt)) @ S[h] \
                + beta[h, t] * np.outer(kt, v[h, t])
            out[h, t] = S[h].T @ q[h, t]
    return out, S


def inputs(seed, heads, s, dk, dv, beta_lo=0.0, alike=0.0):
    """Normalised q and k (``alike`` adds a common direction to the keys,
    which is what breaks a careless triangular inverse), decays with time
    constants from a few to a few thousand tokens, ``beta`` in (beta_lo, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (heads, s, dk))
    k = jax.random.normal(ks[1], (heads, s, dk)) + alike
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (heads, s, dv))
    log_decay = -jnp.exp(jax.random.uniform(ks[3], (heads, s), minval=-8.0,
                                            maxval=-1.0))
    beta = beta_lo + (2.0 - beta_lo) * jax.nn.sigmoid(
        jax.random.normal(ks[4], (heads, s)))
    return q, k, v, log_decay, beta


@pytest.mark.parametrize("s, chunk, beta_lo, alike", [
    (64, 64, 0.0, 0.0),         # one whole chunk
    (192, 64, 0.0, 0.0),        # state carried over two chunk boundaries
    (128, 64, 1.0, 1.5),        # beta in (1, 2): negative eigenvalues, and
    #                             keys alike enough that k_t . k_i is near 1
    (48, 16, 1.0, 0.5),         # the solve's block is the whole chunk
    (96, 32, 0.0, 0.5),         # one merge of two 16-row blocks
])
def test_chunked_kernel_matches_the_recurrence(s, chunk, beta_lo, alike):
    args = inputs(s + chunk, 3, s, 16, 24, beta_lo, alike)
    o, state = jax.jit(lambda *a: gd.gated_delta_chunked(*a, chunk=chunk))(
        *args)
    want_o, want_s = token_by_token(*args)
    assert float(np.min(np.asarray(args[4]))) > beta_lo - 1e-6
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(state, want_s, atol=2e-5)


@pytest.mark.parametrize("length", [1, 37, 64, 100])
def test_padding_past_the_true_length_leaves_the_state_untouched(length):
    """A sequence that is no multiple of the chunk is padded with positions
    of ``log alpha = 0`` and ``beta = 0``: the state after the whole padded
    sequence is the state at ``length``, whatever q, k, v the padding holds."""
    s = 128
    q, k, v, log_decay, beta = inputs(length, 2, s, 16, 24, alike=0.5)
    real = jnp.arange(s) < length
    o, state = gd.gated_delta_chunked(
        q, k, v, jnp.where(real, log_decay, 0.0), jnp.where(real, beta, 0.0))
    want_o, want_s = token_by_token(q[:, :length], k[:, :length],
                                    v[:, :length], log_decay[:, :length],
                                    beta[:, :length])
    np.testing.assert_allclose(o[:, :length], want_o, atol=2e-5)
    np.testing.assert_allclose(state, want_s, atol=2e-5)


def test_sequence_must_be_whole_chunks():
    q, k, v, log_decay, beta = inputs(0, 1, 40, 16, 24)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        gd.gated_delta_chunked(q, k, v, log_decay, beta)


def test_unit_lower_inverse_is_the_inverse_when_keys_are_alike():
    """Entries of N near 2 all below the diagonal: the Neumann product would
    pass through 1e15 and cancel; substitution does not."""
    n = jnp.tril(1.9 * jnp.ones((2, 64, 64)), -1)
    inv = gd.unit_lower_inverse(n)
    np.testing.assert_allclose(
        jnp.matmul(inv, jnp.eye(64) + n, precision="highest"),
        np.broadcast_to(np.eye(64), (2, 64, 64)), atol=1e-4)
    with pytest.raises(ValueError, match="power-of-two"):
        gd.unit_lower_inverse(jnp.zeros((48, 48)))


def test_prefill_state_then_decode_steps_equal_the_whole_sequence():
    """The chunked kernel over a prompt, then the step kernel token by token
    on layer 1 of a stacked state: outputs and state equal the recurrence
    over the whole sequence; other layers and inactive slots keep theirs."""
    heads, dk, dv, prompt, new = 3, 16, 24, 64, 9
    q, k, v, log_decay, beta = inputs(11, heads, prompt + new, dk, dv, 1.0)
    want_o, want_s = token_by_token(q, k, v, log_decay, beta)
    _, state = gd.gated_delta_chunked(q[:, :prompt], k[:, :prompt],
                                      v[:, :prompt], log_decay[:, :prompt],
                                      beta[:, :prompt])
    slots = 2
    stacked = jnp.full((3, slots, heads, dk, dv), 7.0).at[1, 0].set(state)
    step = jax.jit(gd.gated_delta_step, donate_argnums=5)
    active = jnp.asarray([True, False])
    for t in range(prompt, prompt + new):
        def both(x):        # slot 0 is the sequence, slot 1 rides along
            return jnp.stack([x[:, t], x[:, t]])
        o, stacked = step(both(q), both(k), both(v), both(log_decay),
                          both(beta), stacked, jnp.int32(1), active)
        np.testing.assert_allclose(o[0], want_o[:, t], atol=2e-5)
        assert not np.any(np.asarray(o[1]))
    np.testing.assert_allclose(stacked[1, 0], want_s, atol=2e-5)
    assert np.all(np.asarray(stacked[1, 1]) == 7.0)
    assert np.all(np.asarray(stacked[0]) == 7.0)
    assert np.all(np.asarray(stacked[2]) == 7.0)


def test_step_kernel_refuses_a_state_of_another_shape_or_dtype():
    q, k, v, log_decay, beta = inputs(0, 2, 1, 16, 24)
    args = (q[:, 0][None], k[:, 0][None], v[:, 0][None], log_decay[:, 0][None],
            beta[:, 0][None])
    for state in (jnp.zeros((1, 1, 2, 16, 8)),
                  jnp.zeros((1, 1, 2, 16, 24), jnp.bfloat16)):
        with pytest.raises(ValueError, match="does not hold float32"):
            gd.gated_delta_step(*args, state, jnp.int32(0),
                                jnp.asarray([True]))


def test_causal_conv_and_its_step_agree_and_the_tail_skips_the_padding():
    """``y_t`` sums the last four inputs; the tail handed on is the three
    inputs before ``length`` (zeros before the start), never the padding."""
    rng = np.random.RandomState(0)
    s, chan = 12, 5
    x = jnp.asarray(rng.randn(s, chan), jnp.float32)
    w = jnp.asarray(rng.randn(4, chan), jnp.float32)
    for length in (1, 2, 7, 12):
        y, tail = gd.causal_conv(x, w, jnp.int32(length))
        xp = np.concatenate([np.zeros((3, chan)), np.asarray(x)])
        want = sum(np.asarray(w)[j] * xp[j:j + s] for j in range(4))
        np.testing.assert_allclose(y, want, atol=1e-6)
        np.testing.assert_allclose(tail, xp[length:length + 3], atol=0)
    # from the tail at 7, one step gives position 7 of the whole convolution
    y, tail = gd.causal_conv(x, w, jnp.int32(7))
    y7, tail8 = gd.conv_step(x[7][None], tail[None], w)
    np.testing.assert_allclose(y7[0], y[7], atol=1e-6)
    np.testing.assert_allclose(tail8[0], x[5:8], atol=0)
