"""The hybrid model's decode program against its bandwidth bound: the bytes
one step needs (``kernels/hybrid_decode_step.py``: every weight once, the
recurrent state and convolution tails read and written for the slots the
engine says it steps (the ``exec`` spans' ``state_slots``), the K and V of the
positions mapped in the full layers at the middle of the traced span) over
the HBM bandwidth, over the median device time of a decode step. Nothing is
reported for a program without recurrent layers."""

import os

from benchmark.harness import load_module, median

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    counts = run["counts"]
    sz = counts.get("sizes", {})
    if "linear_layers" not in sz:
        return None
    times = run["trace"].program_times("jit_decode")
    slots = load_module("metrics", "gdn_decode_kernel_ms_per_decode",
                        BENCH).state_slots(run) if times else None
    if not slots:
        return None
    need = load_module("kernels", "hybrid_decode_step", BENCH).bytes_needed(
        sz, counts["mapped_positions"], slots)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(times)
