"""The ``glm5_next_text`` model's decode program against its bandwidth bound:
the bytes one step needs (``kernels/glm_decode_step.py``: every weight of the
layers held once but of the routed experts only those hit, by the program's
own ``moe_hit`` counter over the window; the recurrent state of the slots the
engine steps, read and written; the index keys of the positions mapped and
the latent rows of the positions ATTENDED, once, at the middle of the traced
span) over the HBM bandwidth, over the median device time of a decode step:
the share of the WHOLE step, which bounds any later claim. Nothing is reported
for a program without the index kernel in its decode program, these counters
or the ``exec`` spans' ``state_slots``."""

import os

from benchmark.harness import load_module, median

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    counts = run["counts"]
    attended = counts.get("attended_positions")
    # the engagement counter: this model's decode program holds the kernel
    if not attended or not load_module(
            "metrics", "dsa_index_kernel_ms_per_decode",
            BENCH).per_decode(run):
        return None
    times = run["trace"].program_times("jit_decode")
    step = load_module("metrics", "moe_gmm_roofline_pct",
                       BENCH).per_step(run) if times else None
    slots = load_module("metrics", "gdn_decode_kernel_ms_per_decode",
                        BENCH).state_slots(run) if step else None
    if not slots:
        return None
    need = load_module("kernels", "glm_decode_step", BENCH).bytes_needed(
        counts["sizes"], counts["mapped_positions"], attended, sum(step[1]),
        slots)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(times)
