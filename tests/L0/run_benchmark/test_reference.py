"""The two plain references against the program at tiny size on the CPU,
and each shown to refuse a lower precision at the limits the configuration
ships with."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")


def tiny(config):
    full = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    return full, harness.rehearsal_view(full)


# -- BERT --------------------------------------------------------------------

@pytest.fixture(scope="module")
def bert():
    full, cfg = tiny("bert_large")
    ref = harness.load_module("reference", "bert_large", BENCH)
    sz = ref.sizes_of(cfg)
    rng = np.random.RandomState(0)
    batches = [(rng.randint(0, sz["vocab"], (4, 32)).astype(np.int32),
                np.ones((4, 32), np.int32)) for _ in range(3)]
    return full, cfg, ref, sz, batches


def test_bert_reference_logits_match_apply_bert(bert):
    from apex_tpu.models import apply_bert
    from apex_tpu.models.bert import BertConfig

    _, _, ref, sz, batches = bert
    cfg = BertConfig(vocab_size=sz["vocab"], hidden_size=sz["hidden"],
                     num_layers=sz["layers"], num_heads=sz["heads"],
                     intermediate_size=sz["ffn"],
                     max_position_embeddings=sz["positions"],
                     layer_norm_eps=sz["eps"])
    params = jax.jit(lambda k: ref.make_weights(sz, k))(ref.seed_key(11))
    ids, mask = batches[0]
    with jax.default_matmul_precision("highest"):
        want = ref.mlm_logits(params, sz, ids, mask)
        got = apply_bert(params, cfg, ids, mask)["mlm_logits"]
    # float32 on both sides: only the order of summation differs
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4 * float(jnp.std(want))


def test_bert_reference_adam_is_fused_adam(bert):
    """One reference step against ``FusedAdam`` on the reference's own
    gradient: the update rule is the same one."""
    from apex_tpu.optimizers import FusedAdam

    full, _, ref, sz, batches = bert
    o = full["training"]["optimizer"]
    got = ref.train(sz, 11, batches[:1], o, row_block=2)
    params = jax.jit(lambda k: ref.make_weights(sz, k))(ref.seed_key(11))
    ids, mask = batches[0]
    grads = jax.grad(lambda p: ref.loss_sum(p, sz, ids, mask)
                     / mask.sum())(params)
    opt = FusedAdam(lr=o["lr"], weight_decay=o["weight_decay"],
                    betas=tuple(o["betas"]), eps=o["eps"])
    new, _ = opt.step(grads, params, opt.init(params))
    want = np.asarray([float(jnp.linalg.norm((a - b).ravel()))
                       for a, b in zip(jax.tree.leaves(new),
                                       jax.tree.leaves(params))])
    np.testing.assert_allclose(got["update_norms"], want, rtol=2e-3)


def test_bert_limits_pass_the_program_and_refuse_bfloat16(bert):
    """amp O2 through the runner's own step is inside the shipped limits;
    the reference in bfloat16 throughout (weights, statistics, loss, Adam
    state) is outside at least one."""
    full, cfg, ref, sz, batches = bert
    runner = harness.load_module("runners", "bert_pretrain", BENCH)
    limits = full["correct"]["limits"]
    o = full["training"]["optimizer"]
    want = ref.train(sz, 11, batches, o, row_block=2)
    low = ref.train(sz, 11, batches, o, row_block=2, precision="bfloat16")
    rows, _ = runner.compare((low["losses"], low["grad_norms"],
                              low["update_norms"]), want, limits)
    ok, numbers = harness.comparison(rows)
    assert not ok, numbers
    same, _ = runner.compare((want["losses"], want["grad_norms"],
                              want["update_norms"]), want, limits)
    assert harness.comparison(same)[0]


# -- GPT-2 -------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt():
    from apex_tpu.models.gpt import GPTConfig

    full, cfg = tiny("gpt2_medium")
    ref = harness.load_module("reference", "gpt2_medium", BENCH)
    sz = ref.sizes_of(cfg)
    gcfg = GPTConfig(vocab_size=sz["padded_vocab"], hidden_size=sz["hidden"],
                     num_layers=sz["layers"], num_heads=sz["heads"],
                     ffn_hidden_size=sz["ffn"],
                     max_position_embeddings=sz["positions"],
                     layer_norm_eps=sz["eps"])
    params = jax.jit(lambda k: ref.make_weights(sz, k))(ref.seed_key(5))
    rng = np.random.RandomState(1)
    prompt = [int(t) for t in rng.randint(2, sz["vocab"], size=21)]
    return full, cfg, ref, sz, gcfg, params, prompt


def served_greedy(params, gcfg, cfg, prompt, n):
    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, Request)

    s = cfg["serving"]
    eng = PagedDecodeEngine(
        params, gcfg, num_slots=s["slots"], max_len=s["max_len"],
        num_pages=PagedDecodeEngine.full_pool_pages(
            s["slots"], s["max_len"], s["page_size"]),
        page_size=s["page_size"])
    sched = ContinuousBatchingScheduler(eng, eos_id=-1)
    sched.submit(Request(prompt=tuple(prompt), max_new_tokens=n))
    return sched.run()[0]


def test_gpt_reference_agrees_with_the_paged_serving_path(gpt):
    """Prefill and decoding through the paged cache serve, greedily, tokens
    that the reference's full forward also puts first, or all but first:
    the served tokens' logits lie within a bfloat16 rounding of its best."""
    _, cfg, ref, sz, gcfg, params, prompt = gpt
    served = served_greedy(params, gcfg, cfg, prompt, 12)
    gaps, best = ref.Scorer(sz, 5).gaps(prompt, served)
    logits_std = 0.05      # tiny model: logits spread about this much
    assert float(gaps.max()) < 0.1 * logits_std
    assert np.mean(best == np.asarray(served)) > 0.7


def test_gpt_scorer_refuses_wrong_tokens(gpt):
    _, cfg, ref, sz, gcfg, params, prompt = gpt
    served = served_greedy(params, gcfg, cfg, prompt, 12)
    wrong = [(t + 1) % sz["vocab"] for t in served]
    gaps, _ = ref.Scorer(sz, 5).gaps(prompt, wrong)
    assert float(gaps.max()) > 0.05


def test_gpt_controls_read_further_from_the_reference_than_the_program(gpt):
    """The lower precisions, put in the program's place: at each position of
    the same prompt and served tokens the token they put first lies further
    below the float32 reference's best than what the bfloat16 program
    served; fp8 (weights and activations) further than int8 weights. The
    limits themselves are set from chip readings at full size (PERF.md)."""
    _, cfg, ref, sz, gcfg, params, prompt = gpt
    served = served_greedy(params, gcfg, cfg, prompt, 40)
    scorer = ref.Scorer(sz, 5)
    sound, _ = scorer.gaps(prompt, served)
    read = {}
    for precision in ("int8w", "fp8"):
        _, best = ref.Scorer(sz, 5, precision).gaps(prompt, served)
        read[precision], _ = scorer.gaps(prompt, served, judged=best)
    assert float(read["fp8"].mean()) > 3 * float(sound.mean())
    assert float(read["fp8"].mean()) > float(read["int8w"].mean())
    assert float(read["fp8"].max()) > float(sound.max())


def test_gpt_program_with_int8_weights_runs_through_the_scorer(gpt):
    """The program's own lower-precision tier (``apex_tpu.quant``) served
    through the same engine: its tokens are judged the same way."""
    from apex_tpu.quant import quantize_params

    _, cfg, ref, sz, gcfg, params, prompt = gpt
    served = served_greedy(quantize_params(params), gcfg, cfg, prompt, 40)
    sound = served_greedy(params, gcfg, cfg, prompt, 40)
    scorer = ref.Scorer(sz, 5)
    assert float(scorer.gaps(prompt, served)[0].mean()) >= \
        float(scorer.gaps(prompt, sound)[0].mean())
