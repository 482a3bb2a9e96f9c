"""The whole-sequence attention kernel pair (``apex_fmha_fwd`` /
``apex_fmha_bwd``, reached through ``flash_attention_packed``) against the
plain XLA path it replaces at sequences of 128 and 256
(``_unfused_attention``, ``use_kernel=False`` over the transposed slices):
the forward and the gradient of the whole projection, interpret mode on the
CPU. The chipless compile at BERT-Large's shapes is
``tests/L0/test_aot_v5e.py`` (slow tier)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import (flash_attention,
                                             flash_attention_packed)

# the package exports the function under the module's name
fa = importlib.import_module(
    "apex_tpu.transformer.functional.flash_attention")

FWD = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}
BWD = {jnp.float32: dict(atol=2e-4, rtol=2e-4),
       jnp.bfloat16: dict(atol=0.1, rtol=0.1)}
PAIR = {"apex_fmha_fwd", "apex_fmha_bwd"}


def _tails(b, s):
    """A key mask whose rows end 3, 8, 13... positions short."""
    lengths = s - 3 - 5 * jnp.arange(b)
    return (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.int32)


def _projection(seed, b, s, h, d, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, 3, h, d),
                             dtype)


def _both(qkv, mask=None, **kw):
    """(context, gradient of the projection) by ``flash_attention_packed``
    and by the plain path over the transposed slices."""
    b, s, _, h, d = qkv.shape

    def packed(qkv):
        out = flash_attention_packed(qkv, mask, **kw)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def plain(qkv):
        q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
        out = flash_attention(q, k, v, mask, use_kernel=False, **kw)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    run = lambda f: jax.jit(jax.value_and_grad(f, has_aux=True))(qkv)
    ((_, got), dgot), ((_, want), dwant) = run(packed), run(plain)
    return (got, dgot), (want, dwant)


def _close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def _kernels(f, *args):
    """The names of the Pallas kernels in ``f``'s forward + backward."""
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        f(*a).astype(jnp.float32)))).lower(*args).as_text(debug_info=True)
    return {name for name in ("apex_fmha_fwd", "apex_fmha_bwd",
                              "apex_flash_fwd", "apex_flash_bwd_dq")
            if name in text}


@pytest.mark.parametrize("b", [4, 5], ids=["b4", "b5"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h, d", [(2, 64), (1, 128)], ids=["d64", "d128"])
@pytest.mark.parametrize("s", [128, 256])
def test_forward_and_gradient_match_the_plain_path(s, h, d, dtype, b):
    """One lane block a row: two heads of 64 picked apart inside it, or one
    head of 128. A grid step takes all the rows, or 4 of 5 and then a ragged
    1; they are laid side by side by fours, by twos or singly."""
    group = fa._fmha_group(b, s, h * d, jnp.dtype(dtype).itemsize)
    assert group in (b, 4), group
    qkv, mask = _projection(s + d, b, s, h, d, dtype), _tails(b, s)
    assert _kernels(lambda x: flash_attention_packed(x, mask), qkv) == PAIR
    (got, dgot), (want, dwant) = _both(qkv, mask)
    assert got.shape == (b, s, h * d) and dgot.shape == qkv.shape
    _close(got, want, FWD[dtype])
    _close(dgot, dwant, BWD[dtype])
    # a masked key's gradients are exactly zero, as on the plain path
    dead = np.asarray(mask) == 0
    assert not np.asarray(dgot, np.float32)[:, :, 1:][dead].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("b, s, h, d", [
    (3, 128, 4, 64),        # two lane blocks a row, two rows a float32 step
    (2, 256, 2, 128),       # one head a lane block, two blocks
    (2, 256, 6, 64),        # one row a step in float32
    (2, 128, 16, 64)])      # BERT-Large's row: 16 heads, 8 lane blocks
def test_rows_of_several_lane_blocks(b, s, h, d, dtype):
    qkv, mask = _projection(s + h, b, s, h, d, dtype), _tails(b, s)
    (got, dgot), (want, dwant) = _both(qkv, mask)
    _close(got, want, FWD[dtype])
    _close(dgot, dwant, BWD[dtype])


def test_no_mask_is_every_key():
    qkv = _projection(1, 2, 128, 2, 64)
    (got, dgot), (want, dwant) = _both(qkv)
    _close(got, want, FWD[jnp.float32])
    _close(dgot, dwant, BWD[jnp.float32])


def test_a_fully_masked_row_returns_zero_and_zero_gradients():
    qkv = _projection(5, 3, 128, 2, 64)
    mask = _tails(3, 128).at[1].set(0)
    (got, dgot), (want, dwant) = _both(qkv, mask)
    assert not np.asarray(got[1]).any() and not np.asarray(dgot[1]).any()
    assert np.isfinite(np.asarray(got)).all()
    _close(got, want, FWD[jnp.float32])
    _close(dgot, dwant, BWD[jnp.float32])


@pytest.mark.parametrize("s, h, d", [(128, 4, 64), (256, 1, 128)])
def test_the_same_key_drops_the_same_probabilities(s, h, d):
    """The dropout mask is ``_hash_keep`` at the same global (head, q, k)
    positions as on the plain path, forward and in the backward's replay:
    outputs and gradients agree to rounding; another key, or none, gives
    another output."""
    qkv, mask = _projection(7, 2, s, h, d), _tails(2, s)
    drop = dict(dropout_rate=0.3, dropout_rng=jax.random.PRNGKey(8))
    (got, dgot), (want, dwant) = _both(qkv, mask, **drop)
    _close(got, want, FWD[jnp.float32])
    _close(dgot, dwant, BWD[jnp.float32])
    other = flash_attention_packed(qkv, mask, dropout_rate=0.3,
                                   dropout_rng=jax.random.PRNGKey(9))
    assert not np.allclose(got, other)
    assert not np.allclose(got, flash_attention_packed(qkv, mask))
    # and no key means no dropout, whatever the rate
    np.testing.assert_array_equal(
        flash_attention_packed(qkv, mask, dropout_rate=0.3),
        flash_attention_packed(qkv, mask))


def test_softmax_scale_reaches_the_kernel():
    qkv = _projection(11, 1, 128, 2, 64)
    (got, dgot), (want, dwant) = _both(qkv, softmax_scale=0.05)
    _close(got, want, FWD[jnp.float32])
    _close(dgot, dwant, BWD[jnp.float32])
    assert not np.allclose(got, flash_attention_packed(qkv))


# -- who takes the pair, and who keeps the path it had ------------------------

@pytest.mark.parametrize("b, s, h, d, kernels", [
    (2, 128, 16, 64, PAIR), (2, 256, 2, 64, PAIR), (2, 128, 1, 128, PAIR),
    (2, 64, 4, 32, set()),              # the BERT cell's rehearsal shape
    (2, 64, 2, 64, set()),              # a sequence under one tile
    (2, 128, 4, 32, set()),             # a head width it is not built for
    (2, 128, 3, 64, set()),             # 192 columns: half a lane block
    (1, 200, 2, 64, set()),             # no whole tile
    (1, 512, 2, 64, {"apex_flash_fwd", "apex_flash_bwd_dq"})])
def test_which_projections_take_the_pair(b, s, h, d, kernels):
    """...and every other shape is transposed and takes
    ``flash_attention``'s own path for it, whichever that is; the values
    are the plain path's."""
    qkv = _projection(s, b, s, h, d)
    assert _kernels(flash_attention_packed, qkv) == kernels
    if kernels != PAIR:
        (got, dgot), (want, dwant) = _both(qkv)
        _close(got, want, FWD[jnp.float32])
        _close(dgot, dwant, BWD[jnp.float32])


def test_bert_attention_runs_the_pair_once_a_layer():
    """``models/bert.py`` hands the projection's output over as it lies: at
    s128 and a head width of 64 each layer is one forward and one backward
    call, both under the ``attention`` region, and nothing score-shaped is
    in the step."""
    from apex_tpu.models import apply_bert, init_bert, mlm_loss
    from apex_tpu.models.bert import BertConfig

    cfg = BertConfig(vocab_size=256, hidden_size=128, num_layers=2,
                     num_heads=2, intermediate_size=256,
                     max_position_embeddings=128)
    params = jax.eval_shape(lambda: init_bert(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def loss(p, ids, mask):
        return mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"], ids,
                        mask)

    lowered = jax.jit(jax.grad(loss)).lower(params, ids, ids)
    # each launch is a jit of its own, lowered once for all the layers...
    assert lowered.as_text().count("func.func private @_fmha_") == 2
    assert "x2x128x128x" not in lowered.as_text()
    # ...and every layer's call keeps its own path, under its region
    text = lowered.compile().as_text()
    calls = lambda name: len(set(re.findall(       # noqa: E731
        rf"(layer\d+)\)*/attention/jit\(_fmha_\w+\)/{name}/", text)))
    assert calls("apex_fmha_fwd") == 2 and calls("apex_fmha_bwd") == 2


@pytest.mark.parametrize("s", [128, 256])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masked", [False, True], ids=["bare", "key_mask"])
def test_a_short_call_of_flash_attention_lowers_to_what_it_did(s, causal,
                                                               masked):
    """``flash_attention`` itself never takes the pair: serving's prefill
    buckets of 128 and 256 (causal calls at batch 1, ``models/gpt.py``,
    ``nemotron_h.py``) and every other ``(b, h, s, d)`` caller lower to the
    text of the plain path, as before the pair existed, so no
    ``jit_prefill`` moves."""
    x = jax.ShapeDtypeStruct((1, 16, s, 64), jnp.bfloat16)
    m = jax.ShapeDtypeStruct((1, s), jnp.int32)

    def step(q, k, v, mask):
        return flash_attention(q, k, v, mask if masked else None,
                               causal=causal, softmax_scale=0.125)

    def plain(q, k, v, mask):
        return fa._unfused_attention(
            q, k, v, mask if masked else None, jnp.zeros((2,), jnp.uint32),
            causal=causal, scale=0.125, rate=0.0)

    plain.__name__ = step.__name__
    assert jax.jit(step).lower(x, x, x, m).as_text() \
        == jax.jit(plain).lower(x, x, x, m).as_text()
