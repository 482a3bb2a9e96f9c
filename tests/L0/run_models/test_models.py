"""Model-zoo tests: shapes, dtype policies, and a few-step loss decrease
(the reference's L1 convergence tests in miniature)."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.models import (
    apply_bert, apply_resnet, bert_tiny,
    cross_entropy_loss, init_bert, init_resnet, mlm_loss,
)
from apex_tpu.optimizers import FusedAdam, FusedSGD


def test_bert_forward_shapes():
    cfg = bert_tiny()
    params = init_bert(jax.random.PRNGKey(0), cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    out = apply_bert(params, cfg, ids)
    assert out["hidden"].shape == (2, 16, cfg.hidden_size)
    assert out["mlm_logits"].shape == (2, 16, cfg.vocab_size)
    assert out["pooled"].shape == (2, cfg.hidden_size)
    assert out["mlm_logits"].dtype == jnp.float32


def test_bert_bf16_compute():
    cfg = bert_tiny()
    params = init_bert(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    ids = jnp.zeros((2, 16), jnp.int32)
    out = apply_bert(params, cfg, ids, compute_dtype=jnp.bfloat16)
    assert out["hidden"].dtype == jnp.bfloat16
    assert np.all(np.isfinite(np.asarray(out["mlm_logits"], np.float32)))


def test_bert_mask_changes_output():
    cfg = bert_tiny()
    params = init_bert(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 100)
    full = apply_bert(params, cfg, ids,
                      jnp.ones((2, 16), jnp.int32))["hidden"]
    half = apply_bert(params, cfg, ids,
                      jnp.concatenate([jnp.ones((2, 8), jnp.int32),
                                       jnp.zeros((2, 8), jnp.int32)], 1)
                      )["hidden"]
    assert not np.allclose(np.asarray(full[:, 0]), np.asarray(half[:, 0]),
                           atol=1e-5)


def test_bert_train_step_decreases_loss():
    cfg = bert_tiny()
    params = init_bert(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=1e-3)
    state = opt.init(params)
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                             cfg.vocab_size)
    mask = jnp.ones((4, 32), jnp.int32)

    @jax.jit
    def step(params, state):
        def f(p):
            return mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"],
                            ids, mask)
        loss, grads = jax.value_and_grad(f)(params)
        params, state = opt.step(grads, params, state)
        return params, state, loss

    losses = []
    for _ in range(8):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_bert_amp_o2_train_step():
    cfg = bert_tiny()
    h = amp.initialize(opt_level="O2", loss_scale="dynamic")
    params = init_bert(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=1e-3)
    opt_state = opt.init(params)
    sstate = h.init_state()
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                             cfg.vocab_size)
    mask = jnp.ones((2, 16), jnp.int32)

    @jax.jit
    def step(master, opt_state, sstate):
        p = h.cast_model(master)
        loss, grads, found_inf, sstate = h.value_and_grad(
            lambda p: mlm_loss(apply_bert(p, cfg, ids, mask,
                                          compute_dtype=jnp.bfloat16)
                               ["mlm_logits"], ids, mask))(p, sstate)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        return master, opt_state, sstate, loss, found_inf

    for _ in range(3):
        params, opt_state, sstate, loss, found_inf = step(
            params, opt_state, sstate)
    assert np.isfinite(float(loss)) and not bool(found_inf)
    # master params stay fp32
    assert params["encoder"][0]["attention"]["qkv"]["kernel"].dtype \
        == jnp.float32


def test_resnet18_forward_and_step():
    params, stats = init_resnet(jax.random.PRNGKey(0), 18, num_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (2,), 0, 10)
    # jitted: eager per-op dispatch of the whole stack costs ~11 s on
    # the 1-core host; the compiled program lands in the persistent
    # test cache
    logits, new_stats = jax.jit(
        lambda p, s, x: apply_resnet(p, s, x, 18, train=True))(
        params, stats, x)
    assert logits.shape == (2, 10)
    # running stats updated
    assert not np.allclose(np.asarray(new_stats["stem_bn"]["mean"]),
                           np.asarray(stats["stem_bn"]["mean"]))
    # eval mode leaves stats untouched
    _, same = jax.jit(
        lambda p, s, x: apply_resnet(p, s, x, 18, train=False))(
        params, stats, x)
    np.testing.assert_array_equal(np.asarray(same["stem_bn"]["mean"]),
                                  np.asarray(stats["stem_bn"]["mean"]))

    opt = FusedSGD(lr=5e-3, momentum=0.9)
    state = opt.init(params)

    @jax.jit
    def step(params, stats, state):
        def f(p):
            logits, ns = apply_resnet(p, stats, x, 18, train=True)
            return cross_entropy_loss(logits, y), ns
        (loss, ns), grads = jax.value_and_grad(f, has_aux=True)(params)
        params, state = opt.step(grads, params, state)
        return params, ns, state, loss

    losses = []
    for _ in range(6):
        params, stats, state, loss = step(params, stats, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_resnet50_builds():
    params, stats = init_resnet(jax.random.PRNGKey(0), 50, num_classes=10)
    x = jnp.ones((1, 64, 64, 3))
    logits, _ = jax.jit(
        lambda p, s, x: apply_resnet(p, s, x, 50, train=False))(
        params, stats, x)
    assert logits.shape == (1, 10)
