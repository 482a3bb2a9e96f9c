"""The grouped expert product's share of its roofline in a decode step of the
``glm5_next_text`` model: what the two products of every expert layer need
(``kernels/deepseek_decode_step.py``'s counts at hidden 4096 and experts of
2048: the fused gate and up matrix and the down matrix of every expert HIT,
once each; the SwiGLU's clamp between them is XLA's and not the kernel's),
the larger of bytes over the HBM bandwidth and operations over the bfloat16
peak, over their traced time inside ``jit_decode``
(``moe_gmm_kernel_ms_per_decode``'s reading: two calls an expert layer). The
rows and the experts hit of a step are the program's own counters over the
window. With 1.8 rows an expert of 50 MB the bytes are the bound. Nothing is
reported for a model without residual streams (the other families have their
own readers)."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    sz = run["counts"].get("sizes", {})
    if not sz.get("streams"):
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
