"""The index kernel's share of its roofline: what its calls of one decode
step need (``kernels/dsa.py``: one pooled key of 256 bytes for every whole
group of the positions mapped, and all index heads' products against it), the
LARGER of bytes over the HBM bandwidth and operations over the bfloat16 peak
(the bytes, at 32 operations a byte), over their traced time. The positions
are those the running requests hold at the middle of the traced span
(``counts["mapped_positions"]``). The keys come a page at a time (4 of them,
1 KB): short fetches, so the share is expected well under 100."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    got = load_module("metrics", "dsa_index_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    positions = run["counts"].get("mapped_positions") if got else None
    if not positions:
        return None
    seconds, layers = got
    count = load_module("kernels", "dsa", BENCH)
    sz, peaks = run["counts"]["sizes"], run["peaks"]
    need = layers * max(
        count.index_bytes(sz, positions) / peaks["hbm_bytes_per_s"],
        count.index_flops(sz, positions) / peaks["bf16_flops_per_s"])
    return 100.0 * need / seconds
