"""The bounded paged decode-attention calls' share of their roofline: what the
sliding layers' calls of one decode step need (``kernels/window_attention.py``:
the K and V rows of the positions each slot's window leaves, read once, and
every query head's scores and value updates against them), the LARGER of
bytes over the HBM bandwidth and operations over the bfloat16 peak, over
their traced time. The positions are ``counts["window_positions"]``: the sum
over the running requests of ``min(positions held + 1, window)`` at the
middle of the traced span, from the deliveries' stamps. At 128 positions a
slot a call moves half a megabyte a slot and is a dependent chain of one
partly filled chunk: expect the share well under the full layer's."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    got = load_module("metrics", "window_attn_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    positions = run["counts"].get("window_positions") if got else None
    if not positions:
        return None
    seconds, layers = got
    need = layers * load_module("kernels", "window_attention",
                                BENCH).seconds_needed(
        run["counts"]["sizes"], positions, run["peaks"])
    return 100.0 * need / seconds
