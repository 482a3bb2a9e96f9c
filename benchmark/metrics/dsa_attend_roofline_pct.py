"""The latent-attention decode kernel's share of its roofline where the
attention PICKS its rows: what its calls of one decode step need over the rows
the kernel is GIVEN (``kernels/dsa.py``: every attended position's latent row
read once, and all heads' scores and value updates against it), the larger of
bytes over the HBM bandwidth and operations over the bfloat16 peak, over their
traced time (``mla_decode_kernel_ms_per_decode``'s reading).
``counts["attended_positions"]`` are the rows the sparse layers attended a
step at the middle of the traced span: the mapped positions there times the
program's own counters' ratio over the window (``dsa_rows_read`` over
``dsa_rows_mapped``). ``mla_decode_roofline_pct`` reckons every MAPPED
position, several times what this kernel moves here, and is not reported in a
cell whose attention selects. Nothing is reported for sizes without an
indexer or a run without the counters."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    counts = run["counts"]
    attended = counts.get("attended_positions") \
        if "index_width" in counts.get("sizes", {}) else None
    got = load_module("metrics", "mla_decode_kernel_ms_per_decode",
                      BENCH).per_decode(run) if attended else None
    if not got:
        return None
    seconds, layers = got
    count = load_module("kernels", "dsa", BENCH)
    sz, peaks = counts["sizes"], run["peaks"]
    need = layers * max(
        count.attend_bytes(sz, attended) / peaks["hbm_bytes_per_s"],
        count.attend_flops(sz, attended) / peaks["bf16_flops_per_s"])
    return 100.0 * need / seconds
