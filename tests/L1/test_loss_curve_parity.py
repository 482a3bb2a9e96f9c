"""L1 convergence tier: multi-step loss-curve parity (SURVEY §4/§7 —
the reference's L1 ``cross_product`` suite trains fp16 vs fp32 pairs and
compares loss curves per step; the north star's "loss parity" clause).

The reference publishes no numbers (``BASELINE.json``: "published": {}),
so the golden curve is the package's own fp32 (O0) run: every amp level
must track it within mixed-precision tolerance step by step, and
training must actually converge (final < initial)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.models import (
    apply_bert, bert_tiny, gpt_loss_unsharded, gpt_tiny, init_bert,
    init_gpt, mlm_loss,
)
from apex_tpu.optimizers import FusedAdam

STEPS = 20


def bert_curve(opt_level, loss_scale="dynamic", seed=0,
               m_dtype=jnp.float32, emit_compute=False):
    """Loss curve of a full amp train loop on deterministic data.

    ``m_dtype``/``emit_compute`` exercise the reduced-precision optimizer
    state modes: bf16 first moment, and the fused bf16 cast-out consumed
    by ``cast_model(precast=...)`` instead of the per-step master cast."""
    cfg = bert_tiny()
    h = amp.initialize(opt_level=opt_level, loss_scale=loss_scale,
                       verbosity=0)
    params = init_bert(jax.random.PRNGKey(seed), cfg)
    opt = FusedAdam(lr=5e-4, weight_decay=0.01, m_dtype=m_dtype,
                    emit_compute_params=emit_compute)
    opt_state = opt.init(params)
    scaler_state = h.init_state()

    def batch(i):
        k = jax.random.PRNGKey(10_000 + i)
        ids = jax.random.randint(k, (4, 32), 0, cfg.vocab_size)
        return ids, jnp.ones_like(ids)

    @jax.jit
    def step(master, opt_state, scaler_state, compute, ids, mask):
        p = h.cast_model(master, precast=compute)

        def loss_fn(p):
            out = apply_bert(p, cfg, ids, mask)
            return mlm_loss(out["mlm_logits"], ids, mask)

        with h.autocast():
            loss, grads, found_inf, scaler_state = h.value_and_grad(
                loss_fn)(p, scaler_state)
        if emit_compute:
            master, opt_state, compute = opt.step(
                grads, master, opt_state, found_inf=found_inf,
                compute_params=p)
        else:
            master, opt_state = opt.step(grads, master, opt_state,
                                         found_inf=found_inf)
            compute = None
        return master, opt_state, scaler_state, compute, loss

    compute = h.cast_model(params) if emit_compute else None
    losses = []
    for i in range(STEPS):
        ids, mask = batch(i)
        params, opt_state, scaler_state, compute, loss = step(
            params, opt_state, scaler_state, compute, ids, mask)
        losses.append(float(loss))
    return np.array(losses)


@pytest.fixture(scope="module")
def golden_curve():
    return bert_curve("O0", loss_scale=1.0)


def test_golden_run_converges(golden_curve):
    assert np.all(np.isfinite(golden_curve))
    assert golden_curve[-1] < golden_curve[0] - 0.1, golden_curve


@pytest.mark.parametrize("opt_level", ["O1", "O2", "O3"])
def test_amp_curve_tracks_fp32(golden_curve, opt_level):
    """Per-step parity: |amp - fp32| relative error bounded along the
    WHOLE curve (bf16 matmul noise compounds; 5% absorbs it at toy
    scale), and the amp run converges on its own."""
    curve = bert_curve(opt_level)
    assert np.all(np.isfinite(curve))
    np.testing.assert_allclose(curve, golden_curve, rtol=0.05)
    assert curve[-1] < curve[0] - 0.1
    # the curves must NOT be identical — proof reduced precision ran
    assert np.any(curve != golden_curve)


def test_state_dtype_bf16_m_curve_tracks_fp32(golden_curve):
    """L1 gate for the reduced-precision optimizer state: O2 with bf16
    Adam first moments must track the fp32 golden curve within the same
    mixed-precision tolerance as plain O2."""
    curve = bert_curve("O2", m_dtype=jnp.bfloat16)
    assert np.all(np.isfinite(curve))
    np.testing.assert_allclose(curve, golden_curve, rtol=0.05)
    assert curve[-1] < curve[0] - 0.1


def test_state_dtype_castout_curve_tracks_fp32(golden_curve):
    """Full HBM-saving recipe: bf16 m AND the fused bf16 cast-out feeding
    ``cast_model(precast=...)`` — the train loop never re-casts master."""
    curve = bert_curve("O2", m_dtype=jnp.bfloat16, emit_compute=True)
    assert np.all(np.isfinite(curve))
    np.testing.assert_allclose(curve, golden_curve, rtol=0.05)
    assert curve[-1] < curve[0] - 0.1


def test_gpt_converges():
    # overfit ONE fixed batch — the unambiguous convergence smoke
    losses = gpt_curve(None, lr=1e-3, weight_decay=0.0,
                       batch_key=20_000)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.5, losses


def gpt_curve(compute_dtype, seed=0, lr=5e-4, weight_decay=0.01,
              batch_key=30_000):
    """GPT loss curve (fixed-batch overfit) — the decoder-side analogue
    of the BERT amp-level curves; also backs the convergence smoke."""
    cfg = gpt_tiny()
    params = init_gpt(jax.random.PRNGKey(seed), cfg)
    opt = FusedAdam(lr=lr, weight_decay=weight_decay)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss_unsharded(p, cfg, ids, ids,
                                         compute_dtype=compute_dtype))(
            params)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    # one FIXED batch (overfit) so the learning assertion is unambiguous
    ids = jax.random.randint(jax.random.PRNGKey(batch_key), (4, 32),
                             0, cfg.vocab_size)
    losses = []
    for _ in range(STEPS):
        params, opt_state, loss = step(params, opt_state, ids)
        losses.append(float(loss))
    return np.array(losses)


def test_gpt_bf16_curve_tracks_fp32():
    """bf16 compute over fp32 master weights (the O2-shaped GPT recipe
    used by the TP bench) must track the fp32 curve — the L1 guarantee
    for the decoder stack, incl. the fused xentropy loss path."""
    fp32 = gpt_curve(None)
    bf16 = gpt_curve(jnp.bfloat16)
    assert np.all(np.isfinite(bf16))
    np.testing.assert_allclose(bf16, fp32, rtol=0.05)
    assert bf16[-1] < bf16[0] - 0.1       # actually learning
    assert np.any(bf16 != fp32)           # reduced precision really ran
