"""The grouped expert product's share of its roofline in a decode step of the
``bailing_hybrid`` model: what the two products of every expert layer need
(``kernels/deepseek_decode_step.py``'s counts at hidden 2560 and experts of
768: the fused gate and up matrix and the down matrix of every expert HIT,
once each), the larger of bytes over the HBM bandwidth and operations over
the bfloat16 peak, over their traced time inside ``jit_decode``
(``moe_gmm_kernel_ms_per_decode``'s reading: two calls an expert layer). The
rows and the experts hit of a step are the program's own counters over the
window. With 4 rows an expert of 11.8 MB the bytes are the bound. Nothing is
reported for a model without KDA layers (``deepseek_moe_gmm_roofline_pct``
and ``exaone_moe_gmm_roofline_pct`` read the other families')."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    sz = run["counts"].get("sizes", {})
    if not sz.get("kda_layers"):
        return None
    got = load_module("metrics", "moe_gmm_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    step = load_module("metrics", "moe_gmm_roofline_pct",
                       BENCH).per_step(run) if got else None
    if not step:
        return None
    seconds, _ = got
    count = load_module("kernels", "deepseek_decode_step", BENCH)
    peaks = run["peaks"]
    need = sum(max(count.gmm_layer_bytes(sz, rows, hit)
                   / peaks["hbm_bytes_per_s"],
                   count.gmm_layer_flops(sz, rows) / peaks["bf16_flops_per_s"])
               for rows, hit in zip(*step))
    return 100.0 * need / seconds
