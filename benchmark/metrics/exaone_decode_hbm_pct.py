"""The ``exaone_moe`` model's decode program against its bandwidth bound: the
bytes one step needs (``kernels/exaone_decode_step.py``: every weight of the
layers held once but of the routed experts only those hit, by the program's
own ``moe_hit`` counter over the window; the K and V rows of the positions
mapped at the middle of the traced span in the full layers, and of the
positions the window leaves in the sliding ones) over the HBM bandwidth, over
the median device time of a decode step: the share of the whole step.
Nothing is reported for a program without the bounded attention call (no
``apex_paged_window_decode_fwd`` in its decode program) or these counters."""

import os

from benchmark.harness import load_module, median

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    counts = run["counts"]
    # the engagement counter: this model's decode program holds the kernel
    if not load_module("metrics", "window_attn_kernel_ms_per_decode",
                       BENCH).per_decode(run):
        return None
    times = run["trace"].program_times("jit_decode")
    step = load_module("metrics", "moe_gmm_roofline_pct",
                       BENCH).per_step(run) if times else None
    if not step or not counts.get("window_positions"):
        return None
    need = load_module("kernels", "exaone_decode_step", BENCH).bytes_needed(
        counts["sizes"], counts["mapped_positions"],
        counts["window_positions"], sum(step[1]))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(times)
