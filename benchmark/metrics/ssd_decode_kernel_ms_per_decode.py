"""Device time of the Mamba-2 decode kernel per execution of the decode
program: the Mosaic ``custom-call``s named ``apex_ssd_decode_fwd`` in the
trace, ``mamba_layers`` of them per ``jit_decode`` (no other program holds
the kernel). Nothing is reported when there is none (a program with no such
layer), or when the calls are no multiple of ``mamba_layers`` (an execution
cut by the session)."""

import re

SSD_DECODE_FWD = re.compile(r"^%apex_ssd_decode_fwd(\.\d+)? = ")


def per_decode(run):
    """(seconds, calls) per decode execution, or None."""
    layers = int(run["counts"].get("sizes", {}).get("mamba_layers", 0))
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(SSD_DECODE_FWD.match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return seconds / (calls // layers), layers


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
