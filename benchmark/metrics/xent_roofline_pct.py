"""Cross-entropy kernels' share of their roofline: bytes the algorithm needs
(``kernels/xentropy.py``: the logits read once forward, read once and their
gradient written once backward; a gigabyte, which no on-chip memory holds)
over the HBM bandwidth, over the traced time of the two Mosaic
``custom-call``s that touch the ``[rows, padded vocab]`` logits. Bound by
memory. One forward and one backward call per step, or nothing is reported."""

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
        lambda n: xent.touches_logits(n, rows, sz["vocab"]))
    if calls != 2 * steps or seconds <= 0:
        return None
    need = xent.bytes_needed(rows, sz["vocab"])
    least = steps * (need["fwd"] + need["bwd"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
