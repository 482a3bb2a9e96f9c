"""The prefix cache's hit share where admission decides it: over the
``apex:sched/prefill`` spans that begin in the traced window, the prompt
tokens whose pages were found in the cache (``shared_pages`` x ``page_size``,
at most the prompt: an exact match shares a partly filled last page) over
the prompt tokens admitted. In ``prompt_backlog`` that window is 4 s from
t = 2 s (the traffic file's ``trace_start_s``): the four shared prefixes are
in the cache by then, and the backlog keeps an admission or more in most
ticks. A window without a ``prefill`` span gives nothing."""

from benchmark import spans


def read(run):
    shared = total = 0
    for s in spans.in_window(run, "prefill"):
        n = int(s.stats["prompt_tokens"])
        shared += min(int(s.stats.get("shared_pages", 0)) * int(
            s.stats.get("page_size", 0)), n)     # a dense cache has no pages
        total += n
    return 100.0 * shared / total if total else None
