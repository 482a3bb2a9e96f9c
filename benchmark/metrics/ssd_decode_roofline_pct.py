"""The Mamba-2 decode kernel's share of its roofline: the bytes its
``mamba_layers`` calls of one decode step need (``kernels/ssd.py``: the
float32 state of the active slots read and written, and the rows beside it)
over the HBM bandwidth, over their traced time. Bound by memory: five vector
operations per state element. The state (all but 0.2% of the bytes, 2.7 GB,
which no on-chip memory holds) is an HBM operand in the compiled program,
aliased to its result; its last axis is 128 lanes, so the DMA moves what is
counted. The slots counted are those the engine says it steps (the ``exec``
spans' ``state_slots``), all of them under a backlog."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    got = load_module("metrics", "ssd_decode_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    slots = load_module("metrics", "gdn_decode_kernel_ms_per_decode",
                        BENCH).state_slots(run) if got else None
    if not slots:
        return None
    seconds, layers = got
    need = layers * load_module("kernels", "ssd", BENCH).decode_bytes(
        run["counts"]["sizes"], slots)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
