"""Flash-attention kernel golden tests vs pure-jnp attention.

SURVEY.md §4 pattern: Pallas kernel compared against the stock jnp
implementation within dtype-scaled tolerances, fwd + grads, across
mask types and dtypes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import flash_attention


@pytest.fixture(params=[True, False], ids=["kernel", "xla"])
def fa(request):
    """Exercise BOTH dispatch paths: the Pallas kernel and the XLA
    short-seq path (`use_kernel` forced each way)."""
    return functools.partial(flash_attention, use_kernel=request.param)


def _reference(q, k, v, mask=None, causal=False, scale=None):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    neg = jnp.float32(-1e30)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] != 0, s, neg)
    if causal:
        sq, sk = s.shape[-2:]
        causal_m = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(causal_m, s, neg)
    # fully-masked rows: flash returns 0, mimic that
    p = jax.nn.softmax(s, axis=-1)
    any_valid = (s > neg / 2).any(-1, keepdims=True)
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def _qkv(key, b, h, s, d, dtype):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, h, s, d), dtype)  # noqa: E731
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(dtype, causal, fa):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 3, 80, 24, dtype)
    out = fa(q, k, v, causal=causal)
    ref = _reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_forward_padding_mask(fa):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 2, 40, 16, jnp.float32)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (2, 40)) > 0.3)
    mask = mask.at[:, 0].set(True).astype(jnp.int32)
    out = fa(q, k, v, mask)
    ref = _reference(q, k, v, mask)
    np.testing.assert_allclose(out, ref, **TOL[jnp.float32])


def test_fully_masked_rows_return_zero(fa):
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 1, 8, 8, jnp.float32)
    mask = jnp.zeros((1, 8), jnp.int32)
    out = fa(q, k, v, mask)
    np.testing.assert_allclose(out, jnp.zeros_like(out), atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(dtype, causal, fa):
    q, k, v = _qkv(jax.random.PRNGKey(4), 2, 2, 48, 16, dtype)
    mask = None
    if not causal:
        mask = (jax.random.uniform(jax.random.PRNGKey(5), (2, 48)) > 0.2)
        mask = mask.at[:, 0].set(True).astype(jnp.int32)

    def loss_flash(q, k, v):
        return (fa(q, k, v, mask, causal=causal)
                .astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference(q, k, v, mask, causal=causal)
                .astype(jnp.float32) ** 2).sum()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    tol = dict(atol=1e-3, rtol=1e-3) if dtype == jnp.float32 else \
        dict(atol=0.1, rtol=0.1)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_cross_attention_seq_lengths(fa):
    """sq != sk (encoder-decoder shape, ref encdec_multihead_attn)."""
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 2, 24, 16))
    k = jax.random.normal(ks[1], (2, 2, 56, 16))
    v = jax.random.normal(ks[2], (2, 2, 56, 16))
    out = fa(q, k, v)
    ref = _reference(q, k, v)
    np.testing.assert_allclose(out, ref, **TOL[jnp.float32])


def test_dropout_statistics_and_determinism(fa):
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 2, 64, 16, jnp.float32)
    rng = jax.random.PRNGKey(8)
    f = functools.partial(flash_attention, dropout_rate=0.5, dropout_rng=rng)
    o1, o2 = f(q, k, v), f(q, k, v)
    # same rng => identical output (saved-mask semantics)
    np.testing.assert_array_equal(o1, o2)
    # different rng => different output
    o3 = fa(q, k, v, dropout_rate=0.5,
                         dropout_rng=jax.random.PRNGKey(9))
    assert not np.allclose(o1, o3)
    # dropout is unbiased-ish: mean magnitude comparable to no-dropout
    o0 = fa(q, k, v)
    ratio = float(jnp.abs(o1).mean() / jnp.abs(o0).mean())
    assert 0.5 < ratio < 2.0, ratio


def test_dropout_backward_uses_same_mask(fa):
    """grad must see the same keep mask as the forward: finite-difference
    check along a random direction."""
    q, k, v = _qkv(jax.random.PRNGKey(10), 1, 1, 32, 8, jnp.float32)
    rng = jax.random.PRNGKey(11)

    def loss(q):
        return (fa(q, k, v, dropout_rate=0.3, dropout_rng=rng)
                ** 2).sum()

    g = jax.grad(loss)(q)
    direction = jax.random.normal(jax.random.PRNGKey(12), q.shape)
    eps = 1e-3
    fd = (loss(q + eps * direction) - loss(q - eps * direction)) / (2 * eps)
    analytic = jnp.vdot(g, direction)
    np.testing.assert_allclose(fd, analytic, rtol=2e-2, atol=2e-2)


def test_softmax_scale_override(fa):
    q, k, v = _qkv(jax.random.PRNGKey(13), 1, 2, 32, 16, jnp.float32)
    out = fa(q, k, v, softmax_scale=0.05)
    ref = _reference(q, k, v, scale=0.05)
    np.testing.assert_allclose(out, ref, **TOL[jnp.float32])


def test_dispatch_paths_agree_with_dropout():
    """Kernel and XLA paths must produce the SAME dropped output for the
    same rng (shared _hash_keep mask) — dispatch never changes training
    randomness."""
    q, k, v = _qkv(jax.random.PRNGKey(14), 1, 2, 64, 16, jnp.float32)
    rng = jax.random.PRNGKey(15)
    a = flash_attention(q, k, v, dropout_rate=0.4, dropout_rng=rng,
                        use_kernel=True)
    b = flash_attention(q, k, v, dropout_rate=0.4, dropout_rng=rng,
                        use_kernel=False)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_auto_dispatch_threshold():
    """Below the crossover the XLA path runs (no pallas_call in the jaxpr);
    above it the kernel runs."""
    q, k, v = _qkv(jax.random.PRNGKey(16), 1, 1, 64, 8, jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v))(
        q, k, v))
    assert "pallas_call" not in jaxpr
    q2, k2, v2 = _qkv(jax.random.PRNGKey(17), 1, 1, 512, 8, jnp.float32)
    jaxpr2 = str(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v))(
        q2, k2, v2))
    assert "pallas_call" in jaxpr2


@pytest.mark.parametrize("s", [384, 1024])
def test_dropout_mask_independent_of_tiling(s):
    """The keep mask is addressed by GLOBAL (head, q, k) position, so it
    cannot depend on how the kernel tiles the scores: a 3x3 grid of
    128-tiles (s=384) and a 2x2 grid of 512-tiles (s=1024) must drop
    what the untiled XLA path drops for the same rng, forward and in
    all three gradients (causal, so tiles on, under and over the
    diagonal all run)."""
    q, k, v = _qkv(jax.random.PRNGKey(21), 1, 2, s, 16, jnp.float32)
    rng = jax.random.PRNGKey(22)

    def fwdbwd(use_kernel):
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, use_kernel=use_kernel,
                dropout_rate=0.3, dropout_rng=rng) ** 2)
        l, grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v)
        return (l, *grads)

    for a, b in zip(fwdbwd(True), fwdbwd(False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
