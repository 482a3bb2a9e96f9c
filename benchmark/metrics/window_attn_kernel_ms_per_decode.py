"""Device time of the paged decode-attention kernel's BOUNDED calls per
execution of the decode program: the Mosaic ``custom-call``s named
``apex_paged_window_decode_fwd`` in the trace, one per sliding layer
(``sizes["window_layers"]``) per ``jit_decode`` (no other program holds the
kernel: the prompt path runs a banded flash attention). The full layers'
calls run under another name and are ``hybrid_paged_attn_kernel_ms_per_decode``'s.
Nothing is reported for a model without sliding layers (no
``window_layers``), when there is no call, or when the calls are no multiple
of ``window_layers`` (an execution cut by the session)."""

import re

WINDOW_DECODE_FWD = re.compile(r"^%apex_paged_window_decode_fwd(\.\d+)? = ")


def per_decode(run):
    """(seconds per decode execution, sliding layers), or None."""
    layers = int(run["counts"].get("sizes", {}).get("window_layers", 0))
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(WINDOW_DECODE_FWD.match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return seconds / (calls // layers), layers


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
