"""Device time of the flash-attention forward kernel per prefill execution
that ran it: the Mosaic ``custom-call``s named ``apex_flash_fwd`` in the
trace (the program names its kernels since PR 24), ``layers`` of them per
execution. Only the prefill buckets above ``flash_attention``'s sequence
threshold take the kernel (512 and 1024: about half of ``prompt_backlog``'s
prompts), so it reads the prefills of the traced span, 4 s from t = 2 s
there, in which admissions run all through. Nothing is reported when there
is none, or when the calls are no multiple of ``layers`` (an execution cut
by the session)."""

import re

_FLASH_FWD = re.compile(r"^%apex_flash_fwd(\.\d+)? = ")


def read(run):
    layers = int(run["counts"]["sizes"]["layers"])
    seconds, calls = run["trace"].kernel_time(_FLASH_FWD.match)
    if not calls or calls % layers:
        return None
    return 1e3 * seconds / (calls // layers)
