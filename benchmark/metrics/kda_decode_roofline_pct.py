"""The Kimi-Delta-Attention decode kernel's share of its roofline: the bytes
its ``kda_layers`` calls of one decode step need (``kernels/kda.py``: the
float32 state of every slot that decodes read and written once a layer, 4 MiB
a slot, and the rows beside it) over the HBM bandwidth, over their traced
time. Bound by memory: a few vector operations per state element. The slots
counted are those the engine says it steps (the ``exec`` spans'
``state_slots``, read as ``gdn_decode_kernel_ms_per_decode`` reads them), all
of them under a backlog."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    got = load_module("metrics", "kda_decode_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    slots = load_module("metrics", "gdn_decode_kernel_ms_per_decode",
                        BENCH).state_slots(run) if got else None
    if not slots:
        return None
    seconds, layers = got
    need = layers * load_module("kernels", "kda", BENCH).decode_bytes(
        run["counts"]["sizes"], slots)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
