"""Device time of the sparse attention's index kernel per execution of the
decode program: the Mosaic ``custom-call``s named ``apex_dsa_index_fwd`` in the
trace, one per sparse layer per ``jit_decode`` (no other program holds the
kernel: the prompt path scores by query blocks in XLA). Nothing is reported
for sizes without an indexer, when there is no such call, or when the calls
are no multiple of the sparse layers (an execution cut by the session)."""

import re

DSA_INDEX_FWD = re.compile(r"^%apex_dsa_index_fwd(\.\d+)? = ")


def per_decode(run):
    """(seconds per decode execution, sparse layers), or None."""
    sz = run["counts"].get("sizes", {})
    layers = int(sz.get("mla_layers", 0)) if "index_width" in sz else 0
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(DSA_INDEX_FWD.match)
    if not calls or calls % layers or seconds <= 0:
        return None
    return seconds / (calls // layers), layers


def read(run):
    got = per_decode(run)
    return None if got is None else 1e3 * got[0]
