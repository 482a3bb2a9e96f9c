"""Device time of the absorbed latent-attention decode kernel per execution
of the decode program: the Mosaic ``custom-call``s named
``apex_mla_decode_fwd`` in the trace, ``layers`` of them per ``jit_decode``
(no other program holds the kernel: the prompt path expands). Nothing is
reported when there is none (a program with no latent attention), or when
the calls are no multiple of ``layers`` (an execution cut by the session)."""

import re

MLA_DECODE_FWD = re.compile(r"^%apex_mla_decode_fwd(\.\d+)? = ")


def per_decode(run):
    """(seconds per decode execution, layers), or None."""
    sz = run["counts"].get("sizes", {})
    layers = int(sz.get("layers", 0)) if "latent_width" in sz else 0
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(MLA_DECODE_FWD.match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return seconds / (calls // layers), layers


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
