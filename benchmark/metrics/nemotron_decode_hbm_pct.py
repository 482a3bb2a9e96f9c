"""The ``nemotron_h`` model's decode program against its bandwidth bound: the
bytes one step needs (``kernels/nemotron_decode_step.py``: every weight of
the layers held once but of the routed experts only those hit, by the
program's own ``moe_hit`` counter over the window; the Mamba-2 state and
convolution tails read and written for the slots the engine says it steps,
the ``exec`` spans' ``state_slots``; the K and V of the positions mapped in
the attention layers at the middle of the traced span) over the HBM bandwidth,
over the median device time of a decode step. Nothing is reported for a
program without these layers (no ``apex_ssd_decode_fwd`` in its decode
program) or these counters."""

import os

from benchmark.harness import load_module, median

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    counts = run["counts"]
    sz = counts.get("sizes", {})
    if "mamba_layers" not in sz:
        return None
    times = run["trace"].program_times("jit_decode")
    # the engagement counter: this model's decode program holds the kernel
    if not times or not load_module(
            "metrics", "ssd_decode_kernel_ms_per_decode",
            BENCH).per_decode(run):
        return None
    step = load_module("metrics", "moe_gmm_roofline_pct", BENCH).per_step(run)
    slots = load_module("metrics", "gdn_decode_kernel_ms_per_decode",
                        BENCH).state_slots(run) if step else None
    if not slots:
        return None
    need = load_module("kernels", "nemotron_decode_step", BENCH).bytes_needed(
        sz, counts["mapped_positions"], slots, sum(step[1]))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(times)
