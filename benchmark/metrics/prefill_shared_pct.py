"""The prefix cache's hit share where admission decides it: over the
``apex:sched/prefill`` spans that begin in the traced window, the prompt
tokens whose pages were found in the cache (``shared_pages`` x ``page_size``,
at most the prompt: an exact match shares a partly filled last page) over
the prompt tokens admitted."""

from benchmark import spans


def read(run):
    shared = total = 0
    for s in spans.in_window(run, "prefill"):
        n = int(s.stats["prompt_tokens"])
        shared += min(int(s.stats.get("shared_pages", 0)) * int(
            s.stats.get("page_size", 0)), n)     # a dense cache has no pages
        total += n
    return 100.0 * shared / total if total else None
