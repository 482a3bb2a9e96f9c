#!/usr/bin/env python
"""ImageNet-style training CLI — TPU equivalent of the reference acceptance
test ``examples/imagenet/main_amp.py`` (argparse flags ``--opt-level``,
``--keep-batchnorm-fp32``, ``--loss-scale``, ``-b``, ``--lr`` … preserved).

Differences from the reference, by design:
- data: synthetic (or NPZ folder) — no torchvision dependency on TPU;
- distributed: ``--dp`` shards the batch over the mesh ``data`` axis with a
  gradient psum (the DDP-wrapper path) instead of NCCL process groups;
- the training step is ONE jitted function (fwd+bwd+optimizer), so AMP,
  FusedSGD and the collectives all fuse into a single XLA program.

Run: python examples/imagenet/main_amp.py --steps 30 -b 64 --opt-level O2
"""

import argparse
import functools
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

from apex_tpu import amp  # noqa: E402
from apex_tpu.models import apply_resnet, cross_entropy_loss, init_resnet  # noqa: E402
from apex_tpu.optimizers import FusedSGD  # noqa: E402
from apex_tpu.utils.checkpoint import (  # noqa: E402
    load_checkpoint, save_checkpoint,
)
from apex_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from apex_tpu.utils.metrics import AverageMeter, Throughput  # noqa: E402


def parse_args():
    p = argparse.ArgumentParser(description="TPU imagenet example")
    p.add_argument("--arch", "-a", default="resnet50",
                   choices=["resnet10", "resnet18", "resnet34", "resnet50"])
    p.add_argument("-b", "--batch-size", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--opt-level", default="O0",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--seed", type=int, default=0)
    # checkpoint/resume (ref: main_amp.py --resume loading model+optimizer
    # +amp.state_dict; here one atomic file holds the whole train state)
    p.add_argument("--checkpoint", default=None,
                   help="path to write checkpoints to")
    p.add_argument("--save-freq", type=int, default=0,
                   help="save every N steps (0: only at the end)")
    p.add_argument("--resume", default=None,
                   help="checkpoint path to resume from")
    return p.parse_args()


def main():
    enable_compile_cache()
    args = parse_args()
    depth = int(args.arch.replace("resnet", ""))
    loss_scale = args.loss_scale
    if loss_scale not in (None, "dynamic"):
        loss_scale = float(loss_scale)
    kbn = args.keep_batchnorm_fp32
    if isinstance(kbn, str):
        kbn = kbn.lower() in ("1", "true", "yes")

    h = amp.initialize(opt_level=args.opt_level, loss_scale=loss_scale,
                       keep_batchnorm_fp32=kbn)
    key = jax.random.PRNGKey(args.seed)
    params, bn_stats = init_resnet(key, depth, args.num_classes)
    opt = FusedSGD(lr=args.lr, momentum=args.momentum,
                   weight_decay=args.weight_decay)
    opt_state = opt.init(params)
    scaler_state = h.init_state()
    start_step = 0
    if args.resume:
        ck = load_checkpoint(args.resume)
        params, bn_stats = ck["params"], ck["bn_stats"]
        opt_state = jax.tree.map(lambda ref, a: jnp.asarray(a),
                                 opt_state, ck["opt_state"])
        scaler_state = jax.tree.map(lambda ref, a: jnp.asarray(a),
                                    scaler_state, ck["scaler_state"])
        start_step = int(ck["step"]) + 1
        print(f"resumed from {args.resume} at step {start_step}",
              flush=True)

    def save(step):
        if not args.checkpoint:
            return
        save_checkpoint(args.checkpoint, {
            "step": step, "params": params, "bn_stats": bn_stats,
            "opt_state": opt_state, "scaler_state": scaler_state})

    def loss_fn(p, stats, images, labels):
        logits, new_stats = apply_resnet(p, stats, images, depth, train=True)
        return cross_entropy_loss(logits, labels), new_stats

    # donate the threaded state: master weights + optimizer moments are
    # the big buffers, and without donation XLA keeps input AND output
    # copies live across the step (2x peak state memory for nothing)
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def train_step(master, bn_stats, opt_state, scaler_state, images, labels):
        p = h.cast_model(master)
        images = h.cast_input(images)
        (loss, new_stats), grads, found_inf, scaler_state = h.value_and_grad(
            lambda p: loss_fn(p, bn_stats, images, labels), has_aux=True)(
                p, scaler_state)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        # skipped steps keep the old batch stats too
        new_stats = amp.apply_if_finite(new_stats, bn_stats, found_inf)
        return master, new_stats, opt_state, scaler_state, loss

    # synthetic data (deterministic per-step)
    def batch(i):
        k = jax.random.PRNGKey(1000 + i)
        images = jax.random.normal(
            k, (args.batch_size, args.image_size, args.image_size, 3),
            jnp.float32)
        labels = jax.random.randint(k, (args.batch_size,), 0,
                                    args.num_classes)
        return images, labels

    losses = AverageMeter("Loss", ":.4e")
    speed = Throughput()
    if start_step >= args.steps:
        print(f"nothing to do: resumed step {start_step} >= --steps "
              f"{args.steps}")
        return
    for i in range(start_step, args.steps):
        images, labels = batch(i)
        params, bn_stats, opt_state, scaler_state, loss = train_step(
            params, bn_stats, opt_state, scaler_state, images, labels)
        if i == start_step:
            jax.block_until_ready(loss)
            speed.start()
            t0 = time.perf_counter()
        else:
            speed.tick(args.batch_size)
        if i % args.print_freq == 0 or i == args.steps - 1:
            losses.update(float(loss))
            print(f"step {i:4d}  loss {losses.val:.6f}  "
                  f"speed {speed.per_sec:8.1f} img/s", flush=True)
        if args.save_freq and (i + 1) % args.save_freq == 0:
            save(i)
    jax.block_until_ready(loss)
    save(args.steps - 1)
    dt = time.perf_counter() - t0
    done = args.steps - start_step
    n = (done - 1) * args.batch_size
    print(f"FINAL speed {n / max(dt, 1e-9):.1f} img/s  "
          f"step_time {1000 * dt / max(done - 1, 1):.2f} ms")


if __name__ == "__main__":
    main()
