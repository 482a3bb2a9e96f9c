"""Manifold-constrained hyper-connections (``models.layers.hyper_connect``):
the Sinkhorn projection, one sub-layer against the equations, the parameters
at which the four streams are one residual stream, what a sub-layer hands
back beside its output, and the two shared functions this PR split or gave an
argument lowering, without it, to the text they had."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models import layers

N, WIDTH, ROWS = 4, 32, 6


def drawn(seed=0, spread=0.5):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    hp = layers.init_hyper_connection(k1, N, WIDTH, spread)
    # scalars large enough that the token-dependent part of every map counts
    hp = {**hp, "a_pre": jnp.float32(0.7), "a_post": jnp.float32(0.4),
          "a_res": jnp.float32(0.9)}
    return hp, jax.random.normal(k2, (ROWS, N, WIDTH))


def test_hres_is_doubly_stochastic_after_twenty_passes():
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (50, N, N)))
    out = layers.sinkhorn(m, 20)
    np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(out.sum(-2), 1.0, atol=1e-5)
    assert (np.asarray(out) > 0).all()
    # one pass is not enough for such a matrix: the passes matter
    assert np.abs(np.asarray(layers.sinkhorn(m, 1).sum(-1)) - 1).max() > 1e-2
    # a sub-layer's own map stands near the identity, where the passes
    # contract slowly (by the square of the limit's second singular value a
    # pass, 0.8 here): its columns sum to 1 (the last pass), its rows to
    # within 2% after twenty, in the program and the reference alike
    hp, streams = drawn()
    res = layers.hyper_open(streams, hp)[2]
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=3e-2)


def test_one_sublayer_is_the_papers_equations():
    hp, streams = drawn(1)
    f = lambda u: jnp.tanh(u) * 3.0
    got = np.asarray(layers.hyper_connect(streams, hp, f))
    x = np.asarray(streams, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in hp.items()}
    sig = lambda t: 1 / (1 + np.exp(-t))
    for r in range(ROWS):
        flat = x[r].reshape(-1)
        flat = flat / np.sqrt((flat ** 2).mean() + 1e-6)
        maps = flat @ p["proj"]
        pre = sig(p["a_pre"] * maps[:N] + p["b_pre"])
        post = 2 * sig(p["a_post"] * maps[N:2 * N] + p["b_post"])
        res = np.exp(p["a_res"] * maps[2 * N:].reshape(N, N) + p["b_res"])
        for _ in range(20):
            res = res / res.sum(-1, keepdims=True)
            res = res / res.sum(-2, keepdims=True)
        y = np.tanh(pre @ x[r]) * 3.0
        np.testing.assert_allclose(got[r], res @ x[r] + post[:, None] * y,
                                   rtol=2e-5, atol=2e-5)


def test_single_stream_parameters_give_the_plain_residual_to_the_last_bit():
    """``Hres`` the identity, ``Hpost`` 1 and ``Hpre`` the first stream
    alone: that stream is then ``x + f(x)`` bit for bit, and the others stay
    as they were."""
    _, streams = drawn(2)
    hp = {"proj": jnp.zeros((N * WIDTH, 2 * N + N * N)),
          "a_pre": jnp.float32(0.0), "a_post": jnp.float32(0.0),
          "a_res": jnp.float32(0.0),
          "b_pre": jnp.asarray([40.0] + [-jnp.inf] * (N - 1)),
          "b_post": jnp.asarray([0.0] + [-jnp.inf] * (N - 1)),
          "b_res": jnp.where(jnp.eye(N) > 0, 0.0, -jnp.inf)}
    f = lambda u: jnp.sin(u) * 0.37
    got = layers.hyper_connect(streams, hp, f)
    np.testing.assert_array_equal(got[:, 0], streams[:, 0] + f(streams[:, 0]))
    np.testing.assert_array_equal(got[:, 1:], streams[:, 1:])


def test_what_a_sublayer_hands_back_beside_its_output_comes_through():
    hp, streams = drawn(3)
    f = lambda u: (u * 2.0, "state", 7)
    mixed, state, seven = layers.hyper_connect(streams, hp, f)
    assert (state, seven) == ("state", 7)
    np.testing.assert_array_equal(
        mixed, layers.hyper_connect(streams, hp, lambda u: u * 2.0))
    # open, the sub-layer, close: the same three steps by hand
    opened = layers.hyper_open(streams, hp)
    np.testing.assert_array_equal(
        mixed, layers.hyper_close(streams, opened, opened[0] * 2.0))


def test_drawn_with_a_spread_no_stream_idles():
    hp = layers.init_hyper_connection(jax.random.PRNGKey(4), N, WIDTH, 0.5)
    streams = jax.random.normal(jax.random.PRNGKey(5), (ROWS, N, WIDTH))
    mix, post, res = layers.hyper_open(streams, hp)
    assert mix.shape == (ROWS, WIDTH) and (np.asarray(post) > 0.2).all()
    # near the identity, and no stream cut off from the others
    assert (np.diagonal(np.asarray(res), axis1=1, axis2=2) > 0.5).all()
    assert (np.asarray(res) > 1e-4).all()
    plain = layers.init_hyper_connection(jax.random.PRNGKey(4), N, WIDTH)
    pre = jax.nn.sigmoid(plain["b_pre"])
    np.testing.assert_allclose(pre, 1.0 / N, rtol=1e-6)


def lowered(f, *args):
    return jax.jit(f).lower(*args).as_text()


def test_a_model_without_streams_lowers_to_the_text_it_had():
    """The two shared pieces this model changed, without their new argument:
    ``models.deepseek._swiglu`` with no limit is the parent's line, and
    ``models.bailing_hybrid.kda_prefill`` / ``kda_decode`` around the new
    ``kda_mix_*`` are the parent's ``x + W_o [...]`` (the whole programs'
    hashes: ``scripts/shacmp.sh``)."""
    from apex_tpu.models import bailing_hybrid as bh
    from apex_tpu.models import deepseek
    from apex_tpu.transformer.functional.gated_delta import (
        CHUNK, causal_conv, gated_delta_chunked, ring_of_tail,
    )

    x = jnp.ones((8, 64))
    assert lowered(lambda g: deepseek._swiglu(g), x) == lowered(
        lambda g: jax.nn.silu(g[:, :32]) * g[:, 32:], x)
    assert lowered(lambda g: deepseek._swiglu(g, 10.0), x) \
        != lowered(lambda g: deepseek._swiglu(g), x)

    cfg = bh.bailing_hybrid_tiny()
    lp = bh.init(jax.random.PRNGKey(0), cfg)["layers"][0]
    x = jnp.ones((64, cfg.hidden_size))
    mask = jnp.ones((64,), jnp.int32)

    def parents(lp, x, mask):       # kda_prefill as PR 42 wrote it
        s = x.shape[0]
        real = mask.astype(bool)
        conv_in, log_decay, gate, beta = bh._kda_in(lp, x, cfg)
        length = jnp.sum(mask)
        conv_out, tail = causal_conv(
            conv_in, lp["conv"]["weight"].astype(jnp.float32), length)
        q, k, v = bh._kda_heads(conv_out, cfg)
        log_decay = jnp.where(real[:, None, None], log_decay, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
        pad = -s % CHUNK

        def lead(t):
            t = jnp.moveaxis(t, 1, 0)
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

        o, state = gated_delta_chunked(lead(q), lead(k), lead(v),
                                       lead(log_decay), lead(beta))
        o = jnp.moveaxis(o[:, :s], 0, 1)
        return x + bh._kda_out(lp, o, gate, cfg), state, \
            ring_of_tail(tail, length)

    assert lowered(lambda lp, x, m: bh.kda_prefill(lp, x, cfg, m), lp, x,
                   mask) == lowered(lambda lp, x, m: parents(lp, x, m), lp, x,
                                    mask)
