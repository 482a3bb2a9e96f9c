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


def _dispatched(f, *shapes):
    """Which path a call takes, read off the kernels' names in its jaxpr."""
    jaxpr = str(jax.make_jaxpr(f)(
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)))
    assert ("pallas_call" in jaxpr) == ("apex_f" in jaxpr)
    return ("fmha" if "apex_fmha_fwd" in jaxpr else
            "tiled" if "apex_flash_fwd" in jaxpr else "xla")


@pytest.mark.parametrize("path, s, d, kw", [
    # at a padded sequence of 256 and under: plain XLA, whatever the call
    ("xla", 64, 8, {}), ("xla", 128, 64, {}), ("xla", 256, 64, {}),
    ("xla", 128, 128, {}), ("xla", 128, 64, dict(causal=True)),
    ("xla", 256, 64, dict(causal=True)),
    ("xla", 128, 64, dict(causal=True, window=16)),
    ("xla", 128, 64, dict(precision=jax.lax.Precision.HIGHEST)),
    # above it the tiled kernels, bidirectional or not
    ("tiled", 512, 8, {}), ("tiled", 512, 64, {}),
    ("tiled", 384, 64, dict(causal=True)),
    # and a forced path stays forced
    ("tiled", 128, 64, dict(use_kernel=True)),
    ("xla", 512, 64, dict(use_kernel=False)),
], ids=lambda x: x if isinstance(x, (str, int)) else "_".join(x) or "plain")
def test_auto_dispatch_threshold(path, s, d, kw):
    """``flash_attention``'s own two paths: below the crossover the XLA
    path runs (no pallas_call in the jaxpr), above it the tiled kernels. It
    never takes the whole-sequence pair ``apex_fmha_*`` by itself: on
    ``(b, h, s, d)`` operands that pair lost to XLA on the chip (PERF.md,
    PR 41)."""
    shape = (1, 2, s, d)
    assert _dispatched(lambda q, k, v: flash_attention(q, k, v, **kw),
                       shape, shape, shape) == path


@pytest.mark.parametrize("path, s, h, d", [
    ("fmha", 128, 16, 64), ("fmha", 256, 2, 64), ("fmha", 128, 1, 128),
    ("fmha", 256, 4, 128),
    ("xla", 64, 2, 64), ("xla", 128, 4, 32), ("xla", 128, 8, 16),
    ("xla", 128, 3, 64), ("xla", 200, 2, 64),
    ("tiled", 512, 2, 64)])
def test_packed_dispatch(path, s, h, d):
    """``flash_attention_packed`` takes the whole-sequence pair for a
    projection of one tile (128 or 256 positions) whose heads of 64 or 128
    fill whole lane blocks: BERT at s128, bidirectional by construction.
    Any other shape is ``flash_attention``'s."""
    from apex_tpu.transformer.functional import flash_attention_packed

    assert _dispatched(flash_attention_packed, (2, s, 3, h, d)) == path


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


# ---------------------------------------------------------------------------
# a sliding window: a band below the diagonal, its tiles and no others
# ---------------------------------------------------------------------------

def _band_reference(q, k, v, window, mask=None):
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (i - j >= 0) & (i - j < window)
    if mask is not None:
        seen = seen[None, None] & (mask[:, None, None, :] != 0)
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize("padded", [False, True], ids=["whole", "key_mask"])
@pytest.mark.parametrize("s, window", [
    (512, 8), (1024, 128), (640, 128), (700, 128), (300, 8), (1536, 600)],
    ids=lambda x: str(x))
def test_window_kernel_matches_the_band_masked_reference(s, window, padded):
    """Sequences that are and are not multiples of the tile (512 / 128), a
    band narrower and wider than a tile, with and without a key mask (a
    bucket's pad)."""
    from apex_tpu.transformer.functional import flash_attention

    q, k, v = (jax.random.normal(key, (1, 2, s, 32))
               for key in jax.random.split(jax.random.PRNGKey(s), 3))
    real = s - 37 if padded else s
    mask = (jnp.arange(s) < real).astype(jnp.int32)[None] if padded else None
    got = flash_attention(q, k, v, mask, causal=True, window=window,
                          use_kernel=True)
    want = _band_reference(q, k, v, window, mask)
    np.testing.assert_allclose(np.asarray(got)[:, :, :real],
                               np.asarray(want)[:, :, :real], atol=2e-6)
    # short sequences take the plain path, under the same band
    plain = flash_attention(q, k, v, mask, causal=True, window=window,
                            use_kernel=False)
    np.testing.assert_allclose(np.asarray(plain)[:, :, :real],
                               np.asarray(want)[:, :, :real], atol=2e-6)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "plain"])
@pytest.mark.parametrize("s, window, key", [
    (512, 8, 100), (640, 128, 127), (640, 128, 128), (1024, 128, 511),
    (1024, 128, 512), (1024, 512, 3)], ids=lambda x: str(x))
def test_window_mask_is_exact_at_its_edges(s, window, key, use_kernel):
    """With equal scores everywhere a query's output is the mean of the
    values it sees: a value that is 1 at ONE key and 0 elsewhere comes out
    non-zero at exactly the queries that see that key, ``0 <= i - j <
    window``: not at ``i - j = -1``, at ``0`` and ``window - 1``, not at
    ``window``."""
    from apex_tpu.transformer.functional import flash_attention

    q = k = jnp.zeros((1, 1, s, 8))
    v = jnp.zeros((1, 1, s, 8)).at[0, 0, key, 0].set(1.0)
    got = np.asarray(flash_attention(q, k, v, causal=True, window=window,
                                     use_kernel=use_kernel))[0, 0, :, 0]
    sees = np.flatnonzero(got)
    assert sees.tolist() == list(range(key, min(key + window, s)))
    np.testing.assert_allclose(
        got[sees], 1.0 / np.minimum(sees + 1, window), rtol=1e-6)


def _k_extent(s, **kw):
    """The k extent of the forward kernel's grid over a sequence of ``s``."""
    from apex_tpu.transformer.functional import flash_attention

    x = jax.ShapeDtypeStruct((1, 1, s, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, use_kernel=True, **kw))(x, x, x)
    def calls(jaxpr):       # the kernel's call, inside custom_vjp or not
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    call, = calls(jaxpr.jaxpr)
    return call.params["grid_mapping"].grid[2]


def test_visited_k_tiles_grow_with_the_band_and_not_with_the_sequence():
    from apex_tpu.transformer.functional.flash_attention import _band_tiles

    assert [_k_extent(s) for s in (1024, 2048, 8192)] == [2, 4, 16]
    assert [_k_extent(s, window=128) for s in (1024, 2048, 8192)] == [2] * 3
    assert [_k_extent(8192, window=w) for w in (1, 513, 514, 1025, 1026)] \
        == [1, 2, 3, 3, 4]
    # tiles of 128 (a sequence of 640): a band of 128 touches two of five
    assert _k_extent(640) == 5 and _k_extent(640, window=128) == 2
    assert _band_tiles(128, 512, 128, 4) == 2      # q tiles within a k tile
    assert _band_tiles(512, 128, 128, 10) == 5     # a q tile over four


def test_window_is_a_band_of_square_causal_attention_and_nothing_else():
    from apex_tpu.transformer.functional import flash_attention

    q = jnp.zeros((1, 1, 300, 8))
    with pytest.raises(ValueError, match="band below the diagonal"):
        flash_attention(q, q, q, window=8)
    with pytest.raises(ValueError, match="band below the diagonal"):
        flash_attention(q, q[:, :, :200], q[:, :, :200], causal=True,
                        window=8)
    with pytest.raises(ValueError, match="band below the diagonal"):
        flash_attention(q, q, q, causal=True, window=0)


def test_without_a_window_the_forward_lowers_to_what_it_did():
    from apex_tpu.transformer.functional import flash_attention

    x = jax.ShapeDtypeStruct((1, 2, 512, 16), jnp.float32)

    def step(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def step_none(q, k, v):
        return flash_attention(q, k, v, causal=True, window=None)

    step_none.__name__ = step.__name__
    assert jax.jit(step).lower(x, x, x).as_text() \
        == jax.jit(step_none).lower(x, x, x).as_text()


# ---------------------------------------------------------------------------
# precision: float32 operands at the MXU's full precision, forward only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [True, False], ids=["kernel", "xla"])
@pytest.mark.parametrize("window", [None, 128], ids=["full", "band"])
def test_precision_reaches_both_products_and_changes_nothing_else(window,
                                                                  use_kernel):
    """With ``precision`` the two products of the forward (the scores and
    P.V) carry it, in the kernel and on the plain path, banded or not; the
    probabilities and the output stay float32; the values are the reference's
    (on the CPU every precision is exact: what the chip does with it is the
    benchmark's comparison)."""
    from apex_tpu.transformer.functional import flash_attention

    s = 640
    q, k, v = (jax.random.normal(key, (1, 2, s, 32))
               for key in jax.random.split(jax.random.PRNGKey(7), 3))
    attend = lambda precision: lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, use_kernel=use_kernel,
        precision=precision)
    got = attend(jax.lax.Precision.HIGHEST)(q, k, v)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, _band_reference(q, k, v, window or s), atol=2e-6)
    dots = lambda precision: [
        str(e.params.get("precision")) for e in _equations(
            jax.make_jaxpr(attend(precision))(q, k, v).jaxpr)
        if e.primitive.name == "dot_general"]
    # two products a tile body (the kernel has a masked and a plain one)
    exact = dots(jax.lax.Precision.HIGHEST)
    assert len(dots(None)) == len(exact) >= 2 and not len(exact) % 2
    assert "HIGHEST" not in "".join(dots(None))
    assert all("HIGHEST" in d for d in exact)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (a kernel's
    body, a ``cond``'s branches)."""
    for e in jaxpr.eqns:
        yield e
        for value in e.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_without_a_precision_the_forward_lowers_to_what_it_did():
    from apex_tpu.transformer.functional import flash_attention

    x = jax.ShapeDtypeStruct((1, 2, 512, 16), jnp.float32)

    def step(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def step_none(q, k, v):
        return flash_attention(q, k, v, causal=True, precision=None)

    step_none.__name__ = step.__name__
    assert jax.jit(step).lower(x, x, x).as_text() \
        == jax.jit(step_none).lower(x, x, x).as_text()
    # forward only, as a window: no gradient is defined through it
    with pytest.raises(Exception):
        jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, use_kernel=True,
            precision=jax.lax.Precision.HIGHEST).sum())(
                jnp.ones((1, 1, 512, 16)))
