"""Device time of the LayerNorm kernels (forward and backward) per training
step. NOT a roofline share: on the v5e the compiler stages a kernel's
operands into on-chip memory with asynchronous copies (``S(1)`` in the HLO
layouts), so a LayerNorm call takes less time than its bytes would need from
HBM (28 us forward against 41 us, PR 23) and a share of the HBM roofline
reads 150-180%; the HBM traffic is in copies that run beside other work.
``kernels/fused_layer_norm.py`` keeps the least bytes for whoever wants the
comparison (PERF.md has it).

The kernels are the Mosaic ``custom-call``s of the trace that do not touch
the ``[rows, padded vocab]`` logits (those are the cross-entropy's): the
program gives its kernels no stable name yet (PERF.md, for the tracing
issue). Their count is checked: (2 * layers + 2) forward and as many backward
calls per step, or nothing is reported."""

from benchmark.harness import load_module


def read(run):
    counts, trace = run["counts"], run["trace"]
    sz = counts["sizes"]
    rows = counts["rows_per_chip"] * counts["seq"]
    steps = trace.executions("jit_train_step")
    if not steps:
        return None
    xent = load_module("kernels", "xentropy", run["cell"].bench_dir)
    seconds, calls = trace.mosaic_kernels(
        lambda n: not xent.touches_logits(n, rows, sz["vocab"]))
    if calls != 2 * (2 * sz["layers"] + 2) * steps:
        return None
    return 1e3 * seconds / steps
