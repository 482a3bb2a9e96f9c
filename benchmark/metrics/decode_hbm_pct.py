"""The decode program's share of its bandwidth bound: the bytes one step
needs (``kernels/decode_step.py``: every weight once and the K and V of the
cache positions mapped at the middle of the traced span) over the HBM bandwidth, over
the median device time of a decode step. The whole program's share: decode
attention is the kernel ``apex_paged_decode_fwd`` inside it since PR 25, and
``paged_attn_kernel_ms_per_decode`` gives that kernel's own time."""

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
