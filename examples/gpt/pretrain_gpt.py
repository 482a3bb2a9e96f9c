#!/usr/bin/env python
"""Megatron-style GPT pretraining CLI — the full parallel stack in one
script (analogue of the reference's ``tests/L0/run_transformer`` pretrain
drivers built on ``apex/transformer/testing``).

Composes: Megatron flag parsing (``transformer.testing.arguments``) →
global mesh (dp × tp × pp) → tensor-parallel GPT through the collective
1F1B schedule → DDP grad mean → FusedAdam, or ZeRO
(``DistributedFusedAdam``) when ``--use-distributed-optimizer`` is set
(grads reduce-scatter over data instead of averaging; optimizer state is
1/dp per device).

Synthetic data; run on the CPU test rig with e.g.::

    python examples/gpt/pretrain_gpt.py --tensor-model-parallel-size 2 \\
        --pipeline-model-parallel-size 2 --num-layers 4 --steps 10
"""

import sys

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

from apex_tpu.contrib.optimizers import DistributedFusedAdam  # noqa: E402
from apex_tpu.models.gpt import (  # noqa: E402
    GPTConfig, GPTModel, accumulate_tied_word_grads, gpt_pipeline_model,
    gpt_pipeline_partition_specs, gpt_to_pipeline_params, init_gpt,
)
from apex_tpu.optimizers import FusedAdam  # noqa: E402
from apex_tpu.transformer import parallel_state as ps  # noqa: E402
from apex_tpu.transformer.pipeline_parallel.schedules import (  # noqa: E402
    forward_backward_no_pipelining,
    forward_backward_pipelining_with_interleaving,
    forward_backward_pipelining_without_interleaving,
)
from apex_tpu.transformer.testing import arguments  # noqa: E402
from apex_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def extra_flags(p):
    g = p.add_argument_group("pretrain")
    g.add_argument("--steps", type=int, default=10)
    g.add_argument("--use-distributed-optimizer", action="store_true")
    g.add_argument("--gradient-accumulation-fusion", action="store_true",
                   help="per-layer fp32 wgrad emission in the TP linears "
                        "(Megatron --gradient-accumulation-fusion)")
    g.add_argument("--seed", type=int, default=0)
    return p


def main():
    enable_compile_cache()
    ns = arguments.parse_args(extra_args_provider=extra_flags)
    tp_sz, pp = ns.tensor_model_parallel_size, \
        ns.pipeline_model_parallel_size
    mesh = arguments.initialize_from_args(ns)
    dp = ps.get_data_parallel_world_size()
    print(f"mesh: dp={dp} tp={tp_sz} pp={pp}", flush=True)

    if ns.context_parallel_size > 1:
        raise SystemExit(
            "this script does not drive context parallelism — use "
            "transformer.context_parallel.ring_attention directly")
    cfg = GPTConfig(
        vocab_size=ns.padded_vocab_size, hidden_size=ns.hidden_size,
        num_layers=ns.num_layers, num_heads=ns.num_attention_heads,
        ffn_hidden_size=4 * ns.hidden_size,
        max_position_embeddings=ns.max_position_embeddings,
        sequence_parallel=ns.sequence_parallel,
        gradient_accumulation_fusion=ns.gradient_accumulation_fusion)
    vpp = ns.virtual_pipeline_model_parallel_size
    model = GPTModel(cfg, tp_size=tp_sz)
    params = init_gpt(jax.random.PRNGKey(ns.seed), cfg)
    pipe_params = gpt_to_pipeline_params(params, cfg, pp, vpp)
    pipe_model = gpt_pipeline_model(model)
    pspecs = gpt_pipeline_partition_specs(cfg, vpp)

    if ns.use_distributed_optimizer:
        if tp_sz > 1 or pp > 1:
            raise SystemExit(
                "--use-distributed-optimizer composes with pure data "
                "parallelism (the reference's DistributedFusedAdam is "
                "likewise the MLPerf DDP-BERT tool): the ZeRO flat "
                "layout is built from the full param tree, which inside "
                "a tp/pp mesh no longer matches the rank-local shapes. "
                "Drop --tensor/pipeline-model-parallel-size or use the "
                "replicated FusedAdam.")
        opt = DistributedFusedAdam(lr=ns.lr, weight_decay=0.01)
        opt_state = opt.init(pipe_params)
        ospecs = opt.partition_spec()
    else:
        opt = FusedAdam(lr=ns.lr, weight_decay=0.01)
        opt_state = opt.init(pipe_params)
        ospecs = type(opt_state)(step=P(), m=pspecs, v=pspecs)

    # microbatches are per DATA-rank: local batch = global / dp
    if ns.global_batch_size % dp:
        raise SystemExit(f"--global-batch-size {ns.global_batch_size} "
                         f"not divisible by dp {dp}")
    local_batch = ns.global_batch_size // dp
    if local_batch % ns.micro_batch_size:
        raise SystemExit(
            f"local batch {local_batch} (global/dp) not divisible by "
            f"--micro-batch-size {ns.micro_batch_size} (Megatron errors "
            "here too; silent re-sizing would train a different config)")
    M = local_batch // ns.micro_batch_size
    if pp > 1 and vpp:
        fwd_bwd = forward_backward_pipelining_with_interleaving
    elif pp > 1:
        fwd_bwd = forward_backward_pipelining_without_interleaving
    else:
        fwd_bwd = forward_backward_no_pipelining

    def train_step(p, ostate, batch):
        loss, grads = fwd_bwd(pipe_model, p, batch, num_microbatches=M)
        loss = lax.pmean(loss, ps.DATA_AXIS)
        # tied embedding: the pipeline layout holds the word table twice
        # (embed lookup + LM head); sum the partial grads so both copies
        # take identical updates (Megatron's shared-embedding allreduce)
        grads = accumulate_tied_word_grads(grads)
        # SP: LN/Row-bias grads are per-rank partials over the model axis
        grads = model.allreduce_sequence_parallel_grads(grads)
        if ns.use_distributed_optimizer:
            # ZeRO: rank-local grads in, reduce-scatter inside the step
            p, ostate = opt.step(grads, p, ostate)
        else:
            grads = jax.tree.map(lambda g: lax.pmean(g, ps.DATA_AXIS),
                                 grads)
            p, ostate = opt.step(grads, p, ostate)
        return p, ostate, loss

    bspecs = {"input_ids": P(ps.DATA_AXIS), "labels": P(ps.DATA_AXIS)}
    # donate params + optimizer state (threaded through the loop):
    # halves peak state memory vs keeping input and output copies live
    step = jax.jit(ps.shard_map(
        train_step, mesh=mesh,
        in_specs=(pspecs, ospecs, bspecs),
        out_specs=(pspecs, ospecs, P())), donate_argnums=(0, 1))

    b, s = ns.global_batch_size, ns.seq_length
    for i in range(ns.steps):
        k = jax.random.PRNGKey(1000 + i)
        ids = jax.random.randint(k, (b, s), 0, cfg.vocab_size)
        batch = {"input_ids": ids, "labels": ids}
        pipe_params, opt_state, loss = step(pipe_params, opt_state, batch)
        if i % 2 == 0 or i == ns.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.6f}", flush=True)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
