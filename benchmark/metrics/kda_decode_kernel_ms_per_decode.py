"""Device time of the Kimi-Delta-Attention decode kernel per execution of the
decode program: the Mosaic ``custom-call``s named ``apex_kda_decode_fwd`` in
the trace, ``kda_layers`` of them per ``jit_decode``. Nothing is reported
when the sizes name no such layer, when there is no call, or when the calls
are no multiple of ``kda_layers`` (an execution cut by the session)."""

import re

KDA_DECODE_FWD = re.compile(r"^%apex_kda_decode_fwd(\.\d+)? = ")


def per_decode(run):
    """(seconds per decode execution, KDA layers), or None."""
    layers = int(run["counts"].get("sizes", {}).get("kda_layers", 0))
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(KDA_DECODE_FWD.match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return seconds / (calls // layers), layers


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
