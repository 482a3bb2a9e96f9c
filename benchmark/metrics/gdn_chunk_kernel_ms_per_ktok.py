"""Device time of the chunked Gated DeltaNet kernel (``apex_gdn_chunk_fwd``,
``linear_layers`` calls per prefill) per thousand bucket tokens prefilled in
the traced span: the ``bucket`` stat of the ``apex:sched/prefill`` spans that
begin there (padding is computed). Nothing is reported without such a kernel
or such a span, or when the calls are not ``linear_layers`` per prefill span
(a prefill cut by the session)."""

import re

from benchmark import spans

GDN_CHUNK_FWD = re.compile(r"^%apex_gdn_chunk_fwd(\.\d+)? = ")


def traced_prefills(run):
    """(kernel seconds, bucket tokens, prefills) of the traced span, or
    None."""
    layers = int(run["counts"].get("sizes", {}).get("linear_layers", 0))
    if not layers:
        return None
    seconds, calls = run["trace"].kernel_time(GDN_CHUNK_FWD.match)
    if not calls or seconds <= 0:
        return None
    buckets = [int(s.stats["bucket"]) for s in spans.in_window(run, "prefill")
               if "bucket" in s.stats]
    if not buckets or calls != layers * len(buckets):
        return None
    return seconds, sum(buckets), len(buckets)


def read(run):
    got = traced_prefills(run)
    return None if got is None else 1e3 * got[0] / (got[1] / 1e3)
