"""Device time of the paged decode-attention kernel per execution of the
decode program: the Mosaic ``custom-call``s named ``apex_paged_decode_fwd``
in the trace (the program names its kernels since PR 24), ``layers`` of
them per ``jit_decode``. Nothing is reported when there is none (a program
whose decode attention is no kernel), or when the calls are no multiple of
``layers`` (an execution cut by the session)."""

import re

_PAGED_DECODE_FWD = re.compile(r"^%apex_paged_decode_fwd(\.\d+)? = ")


def read(run):
    layers = int(run["counts"]["sizes"]["layers"])
    seconds, calls = run["trace"].kernel_time(_PAGED_DECODE_FWD.match)
    if not calls or calls % layers:
        return None
    return 1e3 * seconds / (calls // layers)
