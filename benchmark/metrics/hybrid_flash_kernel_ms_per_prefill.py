"""Device time of the flash-attention forward kernel per prefill of the
hybrid model: the Mosaic ``custom-call``s named ``apex_flash_fwd`` in the
trace, one per FULL-attention layer (``sizes["full_layers"]``) per
``jit_prefill`` whose bucket lies above ``flash_attention``'s sequence
threshold (every bucket of 512 and more). ``flash_kernel_ms_per_prefill``
reads the same kernel for a model whose every layer calls it. The kernel's
time grows with the square of the bucket, so the reading follows the buckets
of the few prefills a traced span holds. Nothing is reported for a model
without ``full_layers``, when there is no call, or when the calls are no
multiple of ``full_layers`` (a prefill cut by the session)."""

import os
import re

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLASH_FWD = re.compile(r"^%apex_flash_fwd(\.\d+)? = ")


def read(run):
    return load_module("metrics", "hybrid_paged_attn_kernel_ms_per_decode",
                       BENCH).per_execution(run, FLASH_FWD.match)
