"""The Gated DeltaNet decode kernel's share of its roofline: the bytes its
``linear_layers`` calls of one decode step need (``kernels/gated_delta.py``:
the float32 state of the active slots read and written, and the rows beside
it) over the HBM bandwidth, over their traced time. Bound by memory: a few
vector operations per state element. The state (all but 0.3% of the bytes,
0.4-0.6 GB, which no on-chip memory holds) is an HBM operand in the compiled
program; the chip tiles its 192 lanes as 256, so the DMA moves a third more
than is counted and the share cannot pass 75%. The slots counted are those
the engine says it steps (the ``exec`` spans' ``state_slots``), all of them
under a backlog."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    kernel = load_module("metrics", "gdn_decode_kernel_ms_per_decode", BENCH)
    got = kernel.per_decode(run)
    slots = kernel.state_slots(run) if got else None
    if not slots:
        return None
    seconds, layers = got
    need = layers * load_module("kernels", "gated_delta", BENCH).decode_bytes(
        run["counts"]["sizes"], slots)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
