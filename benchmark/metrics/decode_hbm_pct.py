"""The decode program's share of its bandwidth bound: the bytes one step
needs (``kernels/decode_step.py``: every weight once and the K and V of the
cache positions mapped at the middle of the traced span) over the HBM bandwidth, over
the median device time of a decode step. Decode attention is a gather and an
einsum inside the program, not a kernel, so this is the whole program's."""

from benchmark.harness import load_module, median


def read(run):
    counts = run["counts"]
    times = run["trace"].program_times("jit_decode")
    if not times:
        return None
    need = load_module("kernels", "decode_step",
                       run["cell"].bench_dir).bytes_needed(
        counts["sizes"], counts["mapped_positions"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / median(times)
