"""Device time of the prefill programs (``jit_prefill``, every bucket) in the
traced span per thousand prompt tokens computed there: each prompt counts as
its bucket (padding is computed; so are cached-prefix tokens today, the
monolithic prefill runs the whole bucket and only redirects the writes). The
prompts counted are those whose first token was delivered inside the traced
span, which the traffic file places (``trace_start_s``, ``trace_seconds``)
where admissions run beside decode: in ``prompt_backlog`` 4 s from t = 2 s of
a backlog that outlasts the window, about a hundred prefills."""


def bucket_for(n: int, buckets) -> int:
    return min(b for b in buckets if b >= n)


def read(run):
    counts = run["counts"]
    times = run["trace"].program_times("jit_prefill")
    span = counts.get("traced")
    if not times or not span:
        return None
    tokens = sum(bucket_for(counts["prompt_tokens"][i], counts["buckets"])
                 for i, t in counts["first_delivery"].items()
                 if span[0] <= t <= span[1])
    return 1e3 * sum(times) / (tokens / 1e3) if tokens else None
