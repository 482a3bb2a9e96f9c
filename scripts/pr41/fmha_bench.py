"""Device time of one attention layer's forward + backward at the BERT
cells' shape (and at s256, and at heads of 128), by path: XLA from the
packed projection (what BERT ran until PR 41) and from ``(b, h, s, d)``
operands, the tiled flash kernels, and the whole-sequence pair on the packed
projection. Run on the chip: ``chiprun -- python3 scripts/pr41/fmha_bench.py
[path ...]``. Times are the device's own (``XLA Modules`` of a traced run,
medians of ten executions, and the two kernels' own per call) beside the
host's clock. (Until the pair's ``(b, h, s, d)`` layout was taken out, PR
41, the script also ran it as ``flat`` / ``flat4d``: PERF.md has those
readings.)"""

import functools
import importlib
import json
import os
import shutil
import sys

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

# the package exports a function under the module's name
fa = importlib.import_module(
    "apex_tpu.transformer.functional.flash_attention")
from benchmark import trace as tr


def programs(b, h, s, d, dtype):
    """Losses by path. The first two take the packed projection ``(b, s, 3,
    h, d)`` (what a model has in hand: XLA pays for its transposes); the
    ``4d`` ones take ``(3, b, h, s, d)`` as it is."""
    key = jax.random.PRNGKey(0)
    qkv = jax.random.normal(key, (b, s, 3, h, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d), dtype)
    mask = jnp.ones((b, s), jnp.int32)

    def split(qkv, **kw):
        q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
        ctx = fa.flash_attention(q, k, v, mask, **kw)
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def loss(attend):
        return lambda qkv: jnp.sum(
            (attend(qkv) * w).astype(jnp.float32))

    w4 = w.reshape(b, s, h, d).transpose(0, 2, 1, 3)

    def loss4(**kw):
        return lambda x: jnp.sum((fa.flash_attention(
            x[0], x[1], x[2], mask, **kw) * w4).astype(jnp.float32))

    x4 = qkv.transpose(2, 0, 3, 1, 4)
    return {
        "xla": (loss(functools.partial(split, use_kernel=False)), qkv),
        "packed": (loss(lambda qkv: fa.flash_attention_packed(qkv, mask)),
                   qkv),
        "xla4d": (loss4(use_kernel=False), x4),
        "tiled4d": (loss4(use_kernel=True), x4),
    }


def main():
    out = {"device": jax.devices()[0].device_kind}
    todo = []
    only = [a for a in sys.argv[1:] if not a.startswith("--")]
    shapes = [(64, 16, 128, 64), (16, 16, 256, 64), (32, 8, 128, 128)]
    if "--tiny" in sys.argv:        # the rehearsal on the CPU
        shapes = [(2, 2, 128, 64)]
    for shape in shapes:
        for name, (f, x) in programs(*shape, jnp.bfloat16).items():
            if only and name not in only:
                continue
            tag = f"p41_{name}_" + "x".join(map(str, shape))
            for kind, g in (("fwd", f), ("fwdbwd", jax.grad(f))):
                g = jax.jit(g)
                # the trace names a program after its function
                g.__wrapped__.__name__ = f"{tag}_{kind}"
                c = g.lower(x).compile()
                jax.block_until_ready(c(x))
                todo.append((f"{tag}_{kind}", c, x))
    where = os.path.join("chiprun_out", "p41", "fmha_bench_trace")
    shutil.rmtree(where, ignore_errors=True)
    jax.profiler.start_trace(where)
    for _, c, x in todo:
        for _ in range(10):
            r = c(x)
        jax.block_until_ready(r)
    jax.profiler.stop_trace()
    import time
    for name, c, x in todo:         # the host's clock, beside the device's
        jax.block_until_ready(c(x))
        t0 = time.perf_counter()
        for _ in range(20):
            r = c(x)
        jax.block_until_ready(r)
        out[name] = {"wall_us": round(1e6 * (time.perf_counter() - t0) / 20,
                                      1)}
    seen = {}
    try:
        chip = tr.reduce_dir(where).chips[0]
        for p, _, _, dur in chip.modules:
            seen.setdefault(p, []).append(dur)
        # the kernels' own time over all programs that ran them, per call
        for kernel in ("apex_fmha_fwd", "apex_fmha_bwd"):
            for n in chip.op_time:
                if n.startswith(f"%{kernel}"):
                    out[tr.short(n, 80)] = {
                        "calls": chip.op_count[n], "us_per_call": round(
                            1e6 * chip.op_time[n] / chip.op_count[n], 1)}
    except ValueError as e:         # the rehearsal: no device plane
        print(e)
    for name, _, _ in todo:
        times = [v for k, v in seen.items() if name in k]
        out[name]["device_us"] = round(
            1e6 * float(np.median(times[0])), 1) if times else None
    shutil.rmtree(where, ignore_errors=True)
    os.makedirs(os.path.join("chiprun_out", "p41"), exist_ok=True)
    with open(os.path.join("chiprun_out", "p41", "fmha_bench.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    for k, v in out.items():
        print(k, v)


if __name__ == "__main__":
    main()
