"""Fused softmax-cross-entropy golden tests (ref pattern:
``apex/contrib/test/xentropy`` compares against ``F.cross_entropy``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.xentropy import (
    SoftmaxCrossEntropyLoss,
    softmax_cross_entropy_loss,
)


def _ref_loss(logits, labels, smoothing=0.0):
    x = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(x, axis=-1)
    n, v = x.shape
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                               1)[:, 0]
    smooth = -logp.mean(-1)
    loss = (1 - smoothing) * nll + smoothing * smooth
    return jnp.where(labels < 0, 0.0, loss)


# (rows, vocabulary): the first is the shape these tests always had; the
# others leave a ragged last block on the vocabulary axis (blocks of 2,048
# lanes, or the vocabulary rounded up to 128 under that) and on the rows
# (blocks of 256). 5,946 = 4,096 + 1,850: BERT's last block.
RAGGED = [(64, 1000), (48, 130), (100, 2047), (300, 2049), (260, 5946)]


@pytest.mark.parametrize("n,v", RAGGED)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_forward_matches_reference(dtype, smoothing, n, v):
    logits = jax.random.normal(jax.random.PRNGKey(0), (n, v), dtype) * 3
    labels = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, v)
    out = softmax_cross_entropy_loss(logits, labels, smoothing)
    ref = _ref_loss(logits, labels, smoothing)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)


def test_ignored_labels_zero_loss_and_grad():
    n, v = 32, 257
    logits = jax.random.normal(jax.random.PRNGKey(2), (n, v))
    labels = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, v)
    labels = labels.at[::4].set(-1)

    def total(x):
        return softmax_cross_entropy_loss(x, labels).sum()

    loss = softmax_cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(loss[::4], 0.0, atol=0)
    g = jax.grad(total)(logits)
    np.testing.assert_allclose(g[::4], 0.0, atol=0)
    assert float(jnp.abs(g[1]).sum()) > 0


@pytest.mark.parametrize("n,v", [(48, 500)] + RAGGED[1:])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_grads_match_reference(smoothing, dtype, n, v):
    logits = (jax.random.normal(jax.random.PRNGKey(4), (n, v)) * 2
              ).astype(dtype)
    labels = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, v)
    w = jax.random.normal(jax.random.PRNGKey(6), (n,))

    g = jax.grad(lambda x: (softmax_cross_entropy_loss(x, labels,
                                                       smoothing) * w).sum()
                 )(logits)
    gr = jax.grad(lambda x: (_ref_loss(x, labels, smoothing) * w).sum()
                  )(logits)
    assert g.shape == logits.shape and g.dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(g, gr, atol=1e-5, rtol=1e-4)
    else:   # both sides round a float32 gradient to bfloat16
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   gr.astype(jnp.float32),
                                   atol=1e-4, rtol=2e-2)


@pytest.mark.parametrize("n,v", [(300, 2049), (48, 130)])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_out_of_bounds_lanes_reach_no_result(smoothing, n, v):
    """The input holds no NaN; the part of a ragged block that lies out of
    bounds does (the interpreter fills it with NaN, the chip with whatever
    was there). An unmasked lane would carry it into a running max, a sum
    or ``dx``; a ragged row block must not leak into the rows that count."""
    logits = jax.random.normal(jax.random.PRNGKey(10), (n, v)) * 3
    labels = jax.random.randint(jax.random.PRNGKey(11), (n,), 0, v)
    labels = labels.at[::5].set(-1)
    loss, vjp = jax.vjp(
        lambda x: softmax_cross_entropy_loss(x, labels, smoothing), logits)
    dx, = vjp(jnp.ones_like(loss))
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert loss.shape == (n,) and bool(jnp.all(jnp.isfinite(loss)))
    assert dx.shape == logits.shape and bool(jnp.all(jnp.isfinite(dx)))
    np.testing.assert_allclose(loss, _ref_loss(logits, labels, smoothing),
                               atol=1e-5, rtol=1e-5)


def test_mlm_loss_stages_nothing_between_decoder_and_kernel():
    """From the decoder product to the loss kernel and back, no equation
    pads the (b*s, vocab) logits or slices a tensor of their rows: the
    kernels take the model's shape (vocabulary 1,000 and 48 rows, both
    ragged)."""
    import dataclasses

    from apex_tpu.lint.traced.jaxprlib import all_eqns
    from apex_tpu.models import apply_bert, bert_tiny, init_bert, mlm_loss

    cfg = dataclasses.replace(bert_tiny(), vocab_size=1000)
    b, s = 2, 24
    params = init_bert(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, 1000)
    mask = jnp.ones((b, s), jnp.int32)

    def loss(p):
        return mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"], ids,
                        mask)

    def logit_sized(var):
        shape = getattr(var.aval, "shape", ())
        # 256: what the 48 rows would be padded to, were they padded
        return len(shape) == 2 and shape[0] in (b * s, 256) \
            and shape[1] >= 1000

    eqns = list(all_eqns(jax.make_jaxpr(jax.grad(loss))(params),
                         into_pallas=False))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"
             and any(logit_sized(x) for x in e.invars)]
    assert len(calls) == 2      # forward and backward saw (48, 1000) itself
    assert all(x.aval.shape == (b * s, 1000)
               for e in calls for x in (*e.invars, *e.outvars)
               if logit_sized(x))
    staged = [e for e in eqns
              if (e.primitive.name == "pad"
                  and any(logit_sized(x) for x in e.outvars))
              or (e.primitive.name in ("slice", "dynamic_slice")
                  and any(logit_sized(x) for x in e.invars))]
    assert not staged, staged


def test_padding_idx_api():
    n, v = 16, 128
    logits = jax.random.normal(jax.random.PRNGKey(7), (n, v))
    labels = jnp.zeros((n,), jnp.int32)
    out = SoftmaxCrossEntropyLoss.apply(logits, labels, padding_idx=0)
    np.testing.assert_allclose(out, 0.0, atol=0)


def test_large_vocab_multi_tile():
    """Vocab spanning several lane tiles (BERT's 30522)."""
    n, v = 16, 30522
    logits = jax.random.normal(jax.random.PRNGKey(8), (n, v),
                               jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(9), (n,), 0, v)
    out = softmax_cross_entropy_loss(logits, labels)
    ref = _ref_loss(logits, labels)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)
