"""The latent-attention decode kernel's share of its roofline: what its
``layers`` calls of one decode step need (``kernels/mla.py``: every mapped
position's latent row read once, and all heads' scores and value updates
against it), the LARGER of operations over the bfloat16 peak and bytes over
the HBM bandwidth, over their traced time. At 128 heads the two bounds are
within 1% of each other on a v5e (the ridge). The positions are those the
running requests hold at the middle of the traced span
(``counts["mapped_positions"]``, from the deliveries' stamps; the resident
requests' tokens served during set-up included)."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    got = load_module("metrics", "mla_decode_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    positions = run["counts"].get("mapped_positions") if got else None
    if not positions:
        return None
    seconds, layers = got
    count = load_module("kernels", "mla", BENCH)
    sz, peaks = run["counts"]["sizes"], run["peaks"]
    need = layers * max(
        count.decode_flops(sz, positions) / peaks["bf16_flops_per_s"],
        count.decode_bytes(sz, positions) / peaks["hbm_bytes_per_s"])
    return 100.0 * need / seconds
