"""GPT parity tests (ref: ``apex/transformer/testing/standalone_gpt.py``,
exercised upstream by ``tests/L0/run_transformer/test_pipeline_parallel_fwd_bwd``):
the TP=8 shard_map model must match the unsharded jnp golden path in loss
AND gradients; the pipeline adapter must match both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt import (
    GPTModel,
    gpt_loss_unsharded,
    gpt_partition_specs,
    gpt_pipeline_model,
    gpt_tiny,
    gpt_to_pipeline_params,
    init_gpt,
)
from apex_tpu.transformer import parallel_state as ps

# S=16 halves the attention/scan work of every config vs the original
# 32 and keeps the SP divisibility (tp=2 | S) intact (d=64 PR); B drops
# 4->2 with num_microbatches 4->2 (B must stay divisible — the
# schedules mask the extra warmup ticks, so M < pp is fine) — suite-time
# satellite of the optimizer-state PR. S can't shrink further: the cp=8
# ring needs 2 causal chunks per rank (16 | S).
B, S, MICROBATCHES = 2, 16, 2


def _data(cfg):
    k1, k2 = jax.random.split(jax.random.PRNGKey(42))
    ids = jax.random.randint(k1, (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(k2, (B, S), 0, cfg.vocab_size)
    return ids, labels


@pytest.mark.parametrize("use_rope,sequence_parallel", [
    (False, False), (True, False), (False, True)])
def test_tp8_loss_and_grads_match_unsharded(use_rope, sequence_parallel):
    cfg = gpt_tiny()
    cfg = type(cfg)(**{**cfg.__dict__, "use_rope": use_rope,
                       "sequence_parallel": sequence_parallel})
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=8)
    model = GPTModel(cfg, tp_size=8)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids, labels = _data(cfg)

    want_loss, want_grads = jax.value_and_grad(
        lambda p: gpt_loss_unsharded(p, cfg, ids, labels))(params)

    specs = model.partition_specs()

    def loss_and_grads(p, ids, labels):
        loss, grads = jax.value_and_grad(model.loss, argnums=0)(
            p, ids, labels)
        # SP: LN/Row-bias grads are per-rank partial sums (ref: Megatron
        # allreduces sequence-parallel grads after backward)
        return loss, model.allreduce_sequence_parallel_grads(grads)

    got_loss, got_grads = ps.shard_map(
        loss_and_grads,
        in_specs=(specs, P(), P()), out_specs=(P(), specs))(
        params, ids, labels)

    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        got_grads, want_grads)


def test_tp1_runs_without_sharding_surprises():
    """tp=1 mesh: the same TP code path must reproduce the golden loss."""
    cfg = gpt_tiny()
    ps.initialize_model_parallel(tensor_model_parallel_size_=1)
    model = GPTModel(cfg, tp_size=1)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids, labels = _data(cfg)
    want = gpt_loss_unsharded(params, cfg, ids, labels)
    got = ps.shard_map(model.loss, in_specs=(model.partition_specs(),
                                             P(), P()),
                       out_specs=P())(params, ids, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize("pp,vpp,tp,sp,rope", [
    (2, None, 1, False, False), (4, None, 1, False, False),
    (2, 2, 1, False, False), (2, None, 2, True, False),
    (2, None, 2, True, True)])
def test_pipeline_gpt_matches_unsharded(pp, vpp, tp, sp, rope):
    """GPT through the collective pipeline schedules — loss parity with
    the unsharded model and grad parity for the stages (incl. the
    tp=2 + sequence-parallel combination riding the pipe, with and
    without RoPE — the rotary table must span the GLOBAL sequence even
    though stage_fn sees the seq-sharded hidden)."""
    from apex_tpu.transformer.pipeline_parallel import schedules

    cfg = gpt_tiny()
    cfg = type(cfg)(**{**cfg.__dict__, "sequence_parallel": sp,
                       "use_rope": rope})
    ps.initialize_model_parallel(
        tensor_model_parallel_size_=tp,
        pipeline_model_parallel_size_=pp,
        virtual_pipeline_model_parallel_size_=vpp)
    model = GPTModel(cfg, tp_size=tp)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids, labels = _data(cfg)
    batch = {"input_ids": ids, "labels": labels}

    pipe_params = gpt_to_pipeline_params(params, cfg, pp, vpp)
    pipe_model = gpt_pipeline_model(model)
    fwd_bwd = (schedules.forward_backward_pipelining_with_interleaving
               if vpp else
               schedules.forward_backward_pipelining_without_interleaving)

    from apex_tpu.models.gpt import gpt_pipeline_partition_specs

    specs = gpt_pipeline_partition_specs(cfg, vpp)

    kw = {"virtual_pipeline_size": vpp} if vpp else {}

    def run(p, b):
        loss, grads = fwd_bwd(pipe_model, p, b,
                              num_microbatches=MICROBATCHES, **kw)
        return loss, model.allreduce_sequence_parallel_grads(grads)

    loss, grads = jax.jit(ps.shard_map(
        run, in_specs=(specs, P()), out_specs=(P(), specs)))(
        pipe_params, batch)

    # golden: microbatched unsharded loss (same microbatch mean-of-means)
    want_loss = gpt_loss_unsharded(params, cfg, ids, labels)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)

    # grads: tied embedding table accumulates from BOTH the embed lookup
    # and the LM head (the reference's shared-embedding allreduce adds the
    # two stage copies); everything else maps 1:1
    want_grads = jax.grad(
        lambda p: gpt_loss_unsharded(p, cfg, ids, labels))(params)
    want_pipe = gpt_to_pipeline_params(want_grads, cfg, pp, vpp)
    got_word = (grads["embed"]["word"]["embedding"]
                + grads["head"]["word"]["embedding"])
    np.testing.assert_allclose(
        np.asarray(got_word),
        np.asarray(want_pipe["embed"]["word"]["embedding"]),
        rtol=2e-4, atol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        grads["stages"], want_pipe["stages"])
    np.testing.assert_allclose(
        np.asarray(grads["head"]["final_ln"]["weight"]),
        np.asarray(want_grads["final_ln"]["weight"]),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("use_rope,tp,cp,impl", [
    (False, 1, 8, "ring"), (True, 1, 8, "ring"), (False, 2, 4, "ring"),
    (True, 1, 8, "ulysses"), (False, 2, 4, "ulysses")])
def test_context_parallel_matches_unsharded(use_rope, tp, cp, impl):
    """Long-context GPT: ids/labels sequence-sharded over the context
    axis, ring OR Ulysses attention inside — loss AND grads must match
    the unsharded model (incl. composed with tp=2)."""
    cfg = gpt_tiny()
    cfg = type(cfg)(**{**cfg.__dict__, "use_rope": use_rope,
                       "context_parallel": True,
                       "context_parallel_impl": impl})
    ps.initialize_model_parallel(tensor_model_parallel_size_=tp,
                                 context_parallel_size_=cp)
    model = GPTModel(cfg, tp_size=tp)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids, labels = _data(cfg)

    want_loss, want_grads = jax.value_and_grad(
        lambda p: gpt_loss_unsharded(p, cfg, ids, labels))(params)

    specs = model.partition_specs()
    seq_sharded = P(None, ps.CONTEXT_AXIS)

    def run(p, ids, labels):
        loss, grads = jax.value_and_grad(model.loss, argnums=0)(
            p, ids, labels)
        # CP shards TOKENS the way DP shards the batch: each rank's AD
        # yields d(local token mean)/dp, so the closure is the standard
        # DDP one — pmean the grads over the context axis (psum alone
        # measured exactly cp× too big)
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(g, ps.CONTEXT_AXIS), grads)
        return loss, grads

    got_loss, got_grads = jax.jit(ps.shard_map(
        run, in_specs=(specs, seq_sharded, seq_sharded),
        out_specs=(P(), specs)))(params, ids, labels)

    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5),
        got_grads, want_grads)


def test_pipeline_param_roundrobin_layout():
    """chunk c lives at [lane c//pp, dev c%pp] — reference round-robin."""
    cfg = type(gpt_tiny())(**{**gpt_tiny().__dict__, "num_layers": 8})
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    flat = params["layers"]["fc1"]["kernel"]  # (8, h, f)
    pp, vpp = 2, 2
    stacked = gpt_to_pipeline_params(params, cfg, pp, vpp)
    got = stacked["stages"]["fc1"]["kernel"]  # (vpp, pp, 2, h, f)
    # chunk 3 (= lane 1, dev 1) holds layers 6, 7
    np.testing.assert_array_equal(np.asarray(got[1, 1, 0]),
                                  np.asarray(flat[6]))
    np.testing.assert_array_equal(np.asarray(got[1, 1, 1]),
                                  np.asarray(flat[7]))


def test_dropout_active_and_deterministic():
    cfg = gpt_tiny()
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids, labels = _data(cfg)
    l1 = gpt_loss_unsharded(params, cfg, ids, labels,
                            dropout_rng=jax.random.PRNGKey(7))
    l2 = gpt_loss_unsharded(params, cfg, ids, labels,
                            dropout_rng=jax.random.PRNGKey(7))
    l3 = gpt_loss_unsharded(params, cfg, ids, labels,
                            dropout_rng=jax.random.PRNGKey(8))
    assert float(l1) == float(l2)
    assert float(l1) != float(l3)


def test_remat_policy_selective_matches_and_validates():
    """remat_policy='dots_saveable' (selective recompute) must be loss-
    AND grad-identical to full remat — jax.checkpoint changes only WHAT
    is stored, never the math; a bad policy name fails loudly."""
    cfg = gpt_tiny()
    full = type(cfg)(**{**cfg.__dict__, "remat": True})
    sel = type(cfg)(**{**cfg.__dict__, "remat": True,
                       "remat_policy": "dots_saveable"})
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids, labels = _data(cfg)

    def lg(c):
        return jax.value_and_grad(
            lambda p: gpt_loss_unsharded(p, c, ids, labels))(params)

    l1, g1 = lg(full)
    l2, g2 = lg(sel)
    assert float(l1) == float(l2)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), g1, g2)

    bad = type(cfg)(**{**cfg.__dict__, "remat": True,
                       "remat_policy": "not_a_policy"})
    with pytest.raises(ValueError, match="not_a_policy"):
        gpt_loss_unsharded(params, bad, ids, labels)

    # factory members of jax.checkpoint_policies ARE callable but take
    # names/policies, not residuals — the allowlist must reject them at
    # config time, not let jax.checkpoint fail deep inside the scan
    factory = type(cfg)(**{**cfg.__dict__, "remat": True,
                           "remat_policy": "save_only_these_names"})
    with pytest.raises(ValueError, match="save_only_these_names"):
        gpt_loss_unsharded(params, factory, ids, labels)
