"""The paged decode-attention kernel's share of its roofline at query heads
over FEWER K/V heads (64 over 8 as published), in the full layers of a model
that also has sliding ones: what the ``full_layers`` calls of one decode step
need (``kernels/window_attention.py``: every mapped position's K and V rows
read once, every query head's scores and value updates), the larger of bytes
over the HBM bandwidth and operations over the bfloat16 peak, over the time
``hybrid_paged_attn_kernel_ms_per_decode`` reads (the
``apex_paged_decode_fwd`` calls, one a full layer a step; the sliding layers'
calls have another name). The positions are ``counts["mapped_positions"]``.
Nothing is reported for a model without sliding layers or K/V heads in its
sizes, or whose decode program does not hold the bounded call (the engagement
counter: ``window_attn_kernel_ms_per_decode`` reads it)."""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    sz = run["counts"].get("sizes", {})
    if not sz.get("window_layers") or "kv_heads" not in sz \
            or not load_module("metrics", "window_attn_kernel_ms_per_decode",
                               BENCH).per_decode(run):
        return None
    reader = load_module("metrics", "hybrid_paged_attn_kernel_ms_per_decode",
                         BENCH)
    ms = reader.per_execution(run, reader.PAGED_DECODE_FWD.match)
    positions = run["counts"].get("mapped_positions") if ms else None
    if not positions:
        return None
    need = int(sz["full_layers"]) * load_module(
        "kernels", "window_attention", BENCH).seconds_needed(
        sz, positions, run["peaks"])
    return 100.0 * need / (1e-3 * ms)
