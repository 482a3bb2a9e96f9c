"""Model FLOP/s utilisation: the operations the forward and backward passes
require per token (``kernels/train_flops.py``; recomputation not counted)
times the tokens per second per chip over the traced span (the steps the
trace holds, over its length), over the chip's peak. An end-to-end rate of
the traced span, not a kernel's roofline share; the traced run's own
end-to-end rate is not used, because stopping the profiler inside the window
takes seconds."""

from benchmark.harness import load_module


def read(run):
    counts, trace = run["counts"], run["trace"]
    steps = trace.executions("jit_train_step")
    if not steps:
        return None
    flops = load_module("kernels", "train_flops", run["cell"].bench_dir)
    per_token = flops.flops_per_token(counts["sizes"], counts["seq"])
    rate = steps * counts["tokens_per_step_per_chip"] / trace.window_s
    return 100.0 * per_token * rate / run["peaks"]["bf16_flops_per_s"]
