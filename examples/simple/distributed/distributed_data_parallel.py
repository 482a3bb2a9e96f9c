#!/usr/bin/env python
"""Minimal DDP example — TPU analogue of the reference acceptance script
``examples/simple/distributed/distributed_data_parallel.py`` (a linear
model trained under ``apex.parallel.DistributedDataParallel`` +
``amp.initialize``, launched with ``torch.distributed.launch``).

TPU translation: data parallelism is a mesh axis, not processes — the
script runs single-controller over however many local devices exist
(``--dp``, default all; under the test rig that is the 8-virtual-device
CPU world) and scales to multi-host unchanged when launched via
``python -m apex_tpu.parallel.multiproc`` (jax.distributed rendezvous).
The DDP wrapper contributes exactly what the reference's does: grad
averaging over the data group and initial param broadcast.

Run: python examples/simple/distributed/distributed_data_parallel.py
"""

import argparse
import sys

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

from apex_tpu import amp  # noqa: E402
from apex_tpu.optimizers import FusedSGD  # noqa: E402
from apex_tpu.parallel import DistributedDataParallel  # noqa: E402
from apex_tpu.transformer import parallel_state as ps  # noqa: E402
from apex_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel degree (0: all local devices)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("-b", "--batch-size", type=int, default=64,
                   help="GLOBAL batch size")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    enable_compile_cache()
    args = parse_args()
    dp = args.dp or jax.device_count()
    mesh = ps.initialize_model_parallel(devices=jax.devices()[:dp])
    ddp = DistributedDataParallel()
    h = amp.initialize(opt_level=args.opt_level, loss_scale="dynamic")

    # the reference's toy model: 4096 -> 2048 -> 16 with two linears
    k1, k2, kd = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    params = {
        "fc1": {"w": jax.random.normal(k1, (4096, 2048)) * 0.01,
                "b": jnp.zeros((2048,))},
        "fc2": {"w": jax.random.normal(k2, (2048, 16)) * 0.01,
                "b": jnp.zeros((16,))},
    }
    opt = FusedSGD(lr=args.lr)
    opt_state = opt.init(params)
    scaler_state = h.init_state()

    def loss_fn(p, x, y):
        h1 = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
        out = h1 @ p["fc2"]["w"] + p["fc2"]["b"]
        return jnp.mean((out.astype(jnp.float32) - y) ** 2)

    def train_step(master, opt_state, scaler_state, x, y):
        # rank-0 params everywhere first (the DDP constructor broadcast)
        master = ddp.broadcast_params(master)
        p = h.cast_model(master)
        # the DDP hook: mean over dp, on the scaled grads, so every
        # replica sees the same overflow
        loss, grads, found_inf, scaler_state = h.value_and_grad(
            lambda p: loss_fn(p, h.cast_input(x), y),
            reduce_grads=ddp.allreduce_grads)(p, scaler_state)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        loss = jax.lax.pmean(loss, ps.DATA_AXIS)
        return master, opt_state, scaler_state, loss

    step = jax.jit(ps.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P(ps.DATA_AXIS), P(ps.DATA_AXIS)),
        out_specs=(P(), P(), P(), P())))

    for i in range(args.steps):
        k = jax.random.PRNGKey(100 + i)
        x = jax.random.normal(k, (args.batch_size, 4096))
        y = jax.random.normal(k, (args.batch_size, 16))
        params, opt_state, scaler_state, loss = step(
            params, opt_state, scaler_state, x, y)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  dp {dp}  loss {float(loss):.6f}",
                  flush=True)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
