"""The grouped expert product's share of its roofline in a decode step: what
the two products of every expert layer need (``kernels/moe.py``), the larger
of bytes over the HBM bandwidth and operations over the bfloat16 peak, over
their traced time inside ``jit_decode``. The rows and the experts HIT of a
step are the program's own counters over the window (``moe_load``, ``moe_hit``
over ``moe_steps``, kept on the device and read before and after the window:
``counts["moe"]``), never the experts held: a share computed for weights that
were not read would pass 100%. With 5.5 rows an expert the bytes are the
bound, by a factor of forty."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def per_step(run):
    """(rows, experts hit) of one decode step, each a list by expert layer:
    the window's means. None where the program counted nothing."""
    moe = run["counts"].get("moe")
    if not moe or not moe.get("steps"):
        return None
    steps = float(moe["steps"])
    return ([sum(layer) / steps for layer in moe["load"]],
            [hit / steps for hit in moe["hit"]])


def read(run):
    got = load_module("metrics", "moe_gmm_kernel_ms_per_decode",
                      BENCH).per_decode(run)
    step = per_step(run) if got else None
    if not step:
        return None
    seconds, _ = got
    count = load_module("kernels", "moe", BENCH)
    sz, peaks = run["counts"]["sizes"], run["peaks"]
    need = sum(max(count.layer_bytes(sz, rows, hit)
                   / peaks["hbm_bytes_per_s"],
                   count.layer_flops(sz, rows) / peaks["bf16_flops_per_s"])
               for rows, hit in zip(*step))
    return 100.0 * need / seconds
