"""Where the cell's set-up and its comparison spend their time: the weights'
draw, one prefill of the largest bucket and one forward of the reference,
each under the profiler, their device operations summed by HLO text.

    PYTHONPATH=. python scripts/pr47/where.py [--tokens 8192]
"""

import argparse
import os
import sys
import time
import types

sys.path.insert(0, os.getcwd())

from benchmark import harness, trace, traffic  # noqa: E402

OUT = "chiprun_out/pr47/where"


def top(name, n=45):
    import shutil
    try:
        red = trace.reduce_dir(os.path.join(OUT, name))
    except ValueError as err:       # a rehearsal: no device plane
        print(f"== {name}: {err}")
        return
    finally:
        shutil.rmtree(os.path.join(OUT, name), ignore_errors=True)
    chip = red.chips[0]
    print(f"== {name}: busy {red.busy_s:.3f} s of {red.window_s:.3f}",
          flush=True)
    print("   programs", red.programs_summary())
    for text, s in chip.op_time.most_common(n):
        label = trace.short(text, 150)
        if label.split(" ")[1:2] in (["while"], ["conditional"], ["call"]):
            label = "(holds others) " + label
        print(f"   {s:9.4f} s {chip.op_count[text]:7d} x  {label}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--traced-tokens", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=2147483901)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    import numpy as np

    harness.enable_compile_cache()
    cell = harness.Cell("glm_5_3_flash.long_resident_sparse_decode")
    runner, ref = cell.runner(), cell.reference()
    ctx = types.SimpleNamespace(seed=args.seed)
    t = time.perf_counter()
    config, _ = harness.views(cell, args.rehearsal)
    engine, sched, deliveries, sz, _ = runner.build(ctx, config, ref)
    jax.block_until_ready(engine.params)
    print(f"built {time.perf_counter() - t:.1f} s", flush=True)
    rng = np.random.RandomState(7)

    def prompt(n):
        return tuple(int(x) for x in rng.randint(2, sz["vocab"], size=n))

    def prefill(n, name=None):
        rid = sched.submit(runner.gpt._request(traffic.Arrival(
            0.0, prompt(n), 2, 0.0, 7, None)))
        if name:
            jax.profiler.start_trace(os.path.join(OUT, name))
        t = time.perf_counter()
        while rid not in deliveries:
            sched.step()
        dt = time.perf_counter() - t
        if name:
            jax.profiler.stop_trace()
        while sched.busy:
            sched.step()
        return dt

    big = max(engine.buckets)
    print(f"prefill {big} first {prefill(big - 3):.1f} s", flush=True)
    print(f"prefill {big} again {prefill(big - 3, 'prefill'):.3f} s",
          flush=True)
    top("prefill")
    del engine, sched
    jax.clear_caches()
    scorer = ref.Scorer(sz, args.seed)
    judged = sz["judged_tokens"]
    for n, name in ((args.tokens, None), (args.tokens, None),
                    (args.traced_tokens, None),
                    (args.traced_tokens, "reference")):
        seq = prompt(n + judged)
        if name:
            jax.profiler.start_trace(os.path.join(OUT, name))
        t = time.perf_counter()
        scorer.gaps(seq[:n], seq[n:])
        print(f"reference {n} + {judged}: {time.perf_counter() - t:.1f} s",
              flush=True)
        if name:
            jax.profiler.stop_trace()
    top("reference")


if __name__ == "__main__":
    main()
