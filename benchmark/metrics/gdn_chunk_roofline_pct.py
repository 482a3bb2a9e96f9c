"""The chunked Gated DeltaNet kernel's share of its roofline over the
prefills of the traced span: the larger of its operations over the bfloat16
peak and its bytes over the HBM bandwidth (``kernels/gated_delta.py``), over
its traced time. Its operands are float32 arrays of the whole bucket (tens to
hundreds of megabytes a call), HBM operands in the compiled program. By the
counts the bytes bound it (2.9 KB against 135 k operations a token and head),
and every product is a float32 one that the MXU makes in six bfloat16
passes, so the share of the bfloat16 peak is the smaller of the two."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    got = load_module("metrics", "gdn_chunk_kernel_ms_per_ktok",
                      BENCH).traced_prefills(run)
    if got is None:
        return None
    seconds, tokens, prefills = got
    sz = run["counts"]["sizes"]
    gd = load_module("kernels", "gated_delta", BENCH)
    layers = int(sz["linear_layers"])
    least = layers * max(
        gd.chunk_flops(sz, tokens) / run["peaks"]["bf16_flops_per_s"],
        gd.chunk_bytes(sz, tokens, prefills)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
