"""Device time of the paged decode-attention kernel per execution of the
hybrid model's decode program: the Mosaic ``custom-call``s named
``apex_paged_decode_fwd`` in the trace, one per FULL-attention layer
(``sizes["full_layers"]``) per ``jit_decode``, at that model's row of
``heads * head_dim`` lanes. ``paged_attn_kernel_ms_per_decode`` reads the same
kernel for a model whose every layer calls it (``sizes["layers"]``). Nothing
is reported for such a model (no ``full_layers``), when there is no call, or
when the calls are no multiple of ``full_layers`` (an execution cut by the
session)."""

import re

PAGED_DECODE_FWD = re.compile(r"^%apex_paged_decode_fwd(\.\d+)? = ")


def per_execution(run, match):
    """Milliseconds of the kernel ``match`` names per ``full_layers`` calls,
    or None."""
    layers = int(run["counts"].get("sizes", {}).get("full_layers", 0))
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return 1e3 * seconds / (calls // layers)


def read(run):
    return per_execution(run, PAGED_DECODE_FWD.match)
