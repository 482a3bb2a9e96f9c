"""GPT serving through the server's normal path: ``PagedDecodeEngine`` (paged
bfloat16 KV cache, prefix sharing) under ``ContinuousBatchingScheduler`` at
the program's defaults for everything the configuration file does not size,
tokens taken where a client sees them: the ``StreamMux`` sink, stamped with
the benchmark's own wall clock when a flush delivers.

One thread drives it: submit what is due, step the scheduler, repeat. A
request is timed from when it was DUE, so a tick that overruns an arrival
shows in its time to first token, and how late the generator submitted is
reported beside it.
"""

import gc
import sys
import time

import numpy as np


def build(ctx, config, ref):
    """(engine, scheduler, deliveries, sizes): the server a user runs."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig
    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, StreamMux)

    sz = ref.sizes_of(config)
    cfg = GPTConfig(vocab_size=sz["padded_vocab"], hidden_size=sz["hidden"],
                    num_layers=sz["layers"], num_heads=sz["heads"],
                    ffn_hidden_size=sz["ffn"],
                    max_position_embeddings=sz["positions"],
                    layer_norm_eps=sz["eps"])
    params = jax.jit(lambda key: ref.make_weights(sz, key))(
        ref.seed_key(ctx.seed))
    weights = ctx.options.get("weights", "bfloat16")
    if weights == "int8":       # the program's own lower-precision tier
        from apex_tpu.quant import quantize_params
        params = quantize_params(params)
    serving = config["serving"]
    slots, page, max_len = (int(serving["slots"]), int(serving["page_size"]),
                            int(serving["max_len"]))
    cache_dtype = {"bfloat16": jnp.bfloat16}[serving["cache_dtype"]]
    engine = PagedDecodeEngine(
        params, cfg, num_slots=slots, max_len=max_len,
        num_pages=PagedDecodeEngine.full_pool_pages(slots, max_len, page),
        page_size=page, cache_dtype=cache_dtype)
    deliveries = {}            # rid -> [(wall, n tokens), ...]

    def sink(rid, tenant, tokens):
        deliveries.setdefault(rid, []).append(
            (time.perf_counter(), len(tokens)))

    mux = StreamMux(injector=engine.injector, tracer=engine.tracer,
                    stats=engine.stats, sink=sink)
    # eos_id=-1: no token ends a request early, every stream runs to its
    # max_new_tokens, so the amount of work does not depend on the weights
    sched = ContinuousBatchingScheduler(engine, eos_id=-1, streams=mux)
    return engine, sched, deliveries, sz


def _request(a):
    from apex_tpu.serving import Request
    return Request(prompt=a.prompt, max_new_tokens=a.max_new_tokens,
                   temperature=a.temperature, seed=a.seed)


def warm_up(ctx, engine, sched, mix, sz):
    """Run every program the mix can reach once, as a server that has been
    up for a while has: each prefill bucket its prompt lengths can hit,
    decode, both samplers, and the page copy (two identical prompts share a
    partial last page, and the second to decode copies it). Nothing here
    depends on how many requests the mix offers: the engine pads a prompt to
    its bucket on the host, and ``compiles_in_window`` 0 is part of
    ``correct``."""
    from benchmark import traffic

    lo = mix["prompt_tokens"].get("lo", mix["prompt_tokens"].get("value"))
    hi = mix["prompt_tokens"].get("hi", mix["prompt_tokens"].get("value"))
    if mix.get("shared_prefix"):
        hi = max(hi, int(mix["shared_prefix"]["tokens"]) + 1)
    buckets = [b for i, b in enumerate(engine.buckets)
               if b >= lo and (i == 0 or engine.buckets[i - 1] < hi)]
    rng = traffic.seeded(ctx.seed, 3)
    page = engine.page_size
    warm = []
    for b in buckets:
        n = max(min(b, hi, engine.max_len - 8) - 3, 1)
        prompt = tuple(int(t) for t in rng.randint(2, sz["vocab"], size=n))
        for j in range(2):      # the same prompt twice: prefix hit + copy
            warm.append(traffic.Arrival(
                0.0, prompt, 4 + page, (0.0, 0.8)[j], 7 + j, None))
    for a in warm:
        sched.submit(_request(a))
    steps = 0
    while sched.busy:
        sched.step()
        steps += 1
    return {"buckets": buckets, "requests": len(warm), "steps": steps}


def drive(ctx, sched, arrivals, mix, deliveries):
    """The window. Returns the clock readings it took.

    What set-up left alive stays out of the window's garbage collections, as
    in a server that froze its heap once it was up: a full collection of
    this process takes 0.15-0.2 s, several ticks, and whether one or three
    of them fell into the window moved ``serve_tokens_per_s`` by 1.5%
    between runs whose median tick was the same (PERF.md, section 6, PR 27).
    """
    gc.collect()
    gc.freeze()
    try:
        return _drive(ctx, sched, arrivals, mix, deliveries)
    finally:
        gc.unfreeze()


def _drive(ctx, sched, arrivals, mix, deliveries):
    grace = float(mix.get("grace_s", 0.0))
    backlog = mix["arrivals"]["process"] == "backlog"
    rid_of, submitted_at, step_walls, depth = {}, {}, [], []
    nxt, n = 0, len(arrivals)
    drained_at = None
    trace_from = float(mix.get("trace_start_s", 0.0))
    tracing, traced = False, not ctx.trace
    t0 = time.perf_counter()
    end = t0 + ctx.seconds
    while True:
        now = time.perf_counter()
        if not traced and now - t0 >= trace_from:
            tracing, traced = ctx.start_trace(), True
            t_trace = time.perf_counter()
        if tracing and now - t_trace >= ctx.trace_seconds:
            tracing = ctx.stop_trace()
        if now < end:
            with ctx.span("submit"):
                while nxt < n and t0 + arrivals[nxt].due_s <= now:
                    rid_of[nxt] = sched.submit(_request(arrivals[nxt]))
                    submitted_at[nxt] = time.perf_counter()
                    nxt += 1
        elif backlog or now >= end + grace or all(
                rid_of[i] in deliveries for i in range(nxt)):
            break
        if sched.busy:
            t = time.perf_counter()
            with ctx.span("sched_step"):
                sched.step()
            t_after = time.perf_counter()
            step_walls.append((t, t_after - t))
            depth.append(len(sched._queue))
            if backlog and drained_at is None and nxt == n \
                    and not depth[-1]:
                drained_at = t_after - t0    # the last request has a slot
        else:
            wake = min(end, t0 + arrivals[nxt].due_s) if nxt < n else end
            with ctx.span("idle_no_request"):
                time.sleep(max(0.0, min(wake - time.perf_counter(), 0.05)))
    t1 = time.perf_counter()
    if tracing:
        ctx.stop_trace()
    return {"t0": t0, "t1": t1, "end": end, "rid_of": rid_of,
            "submitted_at": submitted_at, "step_walls": step_walls,
            "queue_depth": depth, "submitted": nxt,
            "backlog_left": n - nxt + len(sched._queue),
            "drained_at_s": drained_at}


def measures(ctx, arrivals, clock, deliveries, sched, mix):
    """The end-to-end values and the counts, from the deliveries' stamps."""
    from benchmark.harness import quantile

    t0, end = clock["t0"], clock["end"]
    backlog = mix["arrivals"]["process"] == "backlog"
    # the offline window closes with the tick in flight at its end; the
    # open loop's closes at its end (the grace only lets first tokens land)
    close = clock["t1"] if backlog else end
    window = close - t0
    gaps, ttft, late, tokens = [], [], [], 0
    failed = attempted = 0
    finished = []
    for i in range(clock["submitted"]):
        rid = clock["rid_of"][i]
        a = arrivals[i]
        got = deliveries.get(rid, [])
        due = t0 + a.due_s
        late.append(clock["submitted_at"][i] - due)
        tokens += sum(k for t, k in got if t <= close)
        stamps = [t for t, _ in got]
        gaps += [b - a_ for a_, b in zip(stamps, stamps[1:]) if b <= close]
        out = sched.outcomes.get(rid)
        bad = out is not None and (
            out.error is not None or len(out.tokens) != a.max_new_tokens)
        if not got and out is None and backlog:
            continue        # still queued: the backlog outlasts the window
        attempted += 1
        if bad or not got:
            failed += 1
            ttft.append(ctx.seconds)
        else:
            ttft.append(got[0][0] - due)
        if out is not None and not bad:
            finished.append(i)
    values = {"setup_s": t0 - ctx.t_start}
    if tokens:
        values["serve_tokens_per_s"] = tokens / window
    if len(gaps) >= 2:
        values["itl_ms_p95"] = 1e3 * quantile(gaps, 0.95)
    if ttft:
        values["ttft_ms_p95"] = 1e3 * quantile(ttft, 0.95)
    counts = {
        "window_s": window, "tokens_delivered": tokens,
        "requests_submitted": clock["submitted"],
        "requests_attempted": attempted,
        "requests_finished": len(finished), "gaps": len(gaps),
        "itl_ms_p50": 1e3 * quantile(gaps, 0.5) if gaps else None,
        "ttft_ms_p50": 1e3 * quantile(ttft, 0.5) if ttft else None,
        "gen_late_ms": [1e3 * x for x in late],
        "step_walls": clock["step_walls"],
        "queue_depth_first": clock["queue_depth"][:1],
        "queue_depth_last": clock["queue_depth"][-1:],
        "backlog_left": clock["backlog_left"],
        "drained_at_s": clock["drained_at_s"],
    }
    return values, counts, failed, finished


def say_window(ctx, engine, clock, counts, deliveries, arrivals, sz, values,
               failed, compiles_in_window, **more):
    """The ``window`` line, and what the metric readers take from the window
    into ``counts``. A backlog that the window drained says so, here and on
    standard error: from ``drained_at_s`` on slots stood empty, and
    ``serve_tokens_per_s`` reads what the traffic file offered and not what
    the server can do."""
    from benchmark import harness

    walls = [w for _, w in clock["step_walls"]]
    left, drained = counts["backlog_left"], counts["drained_at_s"]
    drained = {} if left or drained is None else {"drained_at_s": drained}
    ctx.say(stage="window", window_s=counts["window_s"],
            steps=len(walls), step_ms_p50=1e3 * harness.median(walls),
            tokens_delivered=counts["tokens_delivered"],
            requests_submitted=counts["requests_submitted"],
            requests_finished=counts["requests_finished"], failed=failed,
            backlog_left=left, **drained,
            itl_ms_p50=counts["itl_ms_p50"], ttft_ms_p50=counts["ttft_ms_p50"],
            gen_late_ms_p95=harness.quantile(counts["gen_late_ms"], 0.95),
            queue_depth_first=counts["queue_depth_first"],
            queue_depth_last=counts["queue_depth_last"],
            slowest_steps_at_s_ms=[
                [round(t - clock["t0"], 2), round(1e3 * w, 1)] for t, w in
                sorted(clock["step_walls"], key=lambda r: -r[1])[:3]],
            values=values, compiles_in_window=compiles_in_window,
            peak_bytes_in_use=(ctx.devices[0].memory_stats() or {}).get(
                "peak_bytes_in_use"), **more)
    if drained:
        print(f"benchmark: {ctx.cell.name} drained its backlog "
              f"{drained['drained_at_s']:.1f} s into a window of "
              f"{ctx.seconds:g} s; benchmark/traffic/"
              f"{ctx.cell.traffic_name}.json needs more than "
              f"{len(arrivals)} arrivals.requests", file=sys.stderr)
    counts["buckets"] = list(engine.buckets)
    counts["slots"] = engine.num_slots
    counts["page_size"] = engine.page_size
    counts["first_delivery"] = {
        i: deliveries[clock["rid_of"][i]][0][0]
        for i in range(clock["submitted"])
        if clock["rid_of"][i] in deliveries}
    counts["prompt_tokens"] = [len(a.prompt) for a in arrivals]
    counts["traced"] = ctx.traced
    counts["traced_from_s"] = ctx.traced and ctx.traced[0] - clock["t0"]
    counts["sizes"] = sz
    span = ctx.traced or (clock["t0"], clock["t1"])
    counts["mapped_positions"] = mapped_positions(
        arrivals, clock, deliveries, 0.5 * (span[0] + span[1]))


def check_outputs(ctx, config, ref, sz, arrivals, clock, finished,
                  delivered_tokens):
    """Once the window has closed and the engine is freed: a sample, drawn
    from the seed, of the greedy requests it finished, with the longest in
    it; the reference runs once over each prompt with its served tokens,
    and the number compared is the widest gap by which a served token's
    logit lies below the reference's best."""
    from benchmark import traffic

    spec = config["correct"]
    greedy = [i for i in finished if arrivals[i].temperature <= 0]
    if not greedy:      # nothing finished: judge what was delivered so far
        greedy = [i for i in range(clock["submitted"])
                  if arrivals[i].temperature <= 0
                  and delivered_tokens.get(clock["rid_of"][i])]
    if not greedy:
        return [("served_tokens_judged_short_of",
                 int(spec["min_tokens_judged"]), 0)], {}, None
    total = lambda i: len(arrivals[i].prompt) + len(
        delivered_tokens[clock["rid_of"][i]])
    longest = max(greedy, key=total)
    rest = [i for i in greedy if i != longest]
    rng = traffic.seeded(ctx.seed, 4)
    picks = [longest] + [rest[j] for j in rng.permutation(len(rest))[
        :int(spec["sample_requests"]) - 1]]
    scorer = ref.Scorer(sz, ctx.seed)
    lows = {p: ref.Scorer(sz, ctx.seed, p)
            for p in (("fp8", "int8w") if ctx.control else ())}
    gaps_of = {name: [] for name in ("served", *lows)}
    where, worst = None, -1.0
    for i in picks:
        served = list(delivered_tokens[clock["rid_of"][i]])
        gaps, _ = scorer.gaps(arrivals[i].prompt, served)
        gaps_of["served"].append(gaps)
        if float(gaps.max()) > worst:
            worst, where = float(gaps.max()), (i, int(gaps.argmax()))
        for name, low in lows.items():
            # the token the lower precision puts first at each position of
            # the same prompt and tokens, judged by the float32 reference
            _, low_best = low.gaps(arrivals[i].prompt, served)
            gaps_of[name].append(scorer.gaps(arrivals[i].prompt, served,
                                             judged=low_best)[0])
    limits = spec["limits"]

    def rows_of(gaps):
        g = np.concatenate(gaps)
        return [("served_logit_gap_max", float(g.max()),
                 limits["logit_gap_max"]),
                ("served_logit_gap_mean", float(g.mean()),
                 limits["logit_gap_mean"])]

    judged = int(sum(len(g) for g in gaps_of["served"]))
    rows = rows_of(gaps_of["served"]) + [
        ("served_tokens_judged_short_of",
         max(0, int(spec["min_tokens_judged"]) - judged), 0)]
    info = {"requests_judged": len(picks), "tokens_judged": judged,
            "longest_sequence": total(longest), "worst_at": where}
    control = {name: rows_of(gaps_of[name]) for name in lows}
    return rows, info, control


def run(ctx):
    import jax

    from benchmark import harness, traffic

    config, mix = harness.views(ctx.cell, ctx.rehearsal)
    if "rate_per_s" in ctx.options:     # the sweep that placed the cell
        mix = {**mix, "arrivals": {**mix["arrivals"], "rate_per_s": float(
            ctx.options["rate_per_s"])}}
    ref = ctx.cell.reference()
    engine, sched, deliveries, sz = build(ctx, config, ref)
    ctx.say(stage="built", buckets=list(engine.buckets),
            num_pages=engine.pool.num_pages, slots=engine.num_slots)
    arrivals = traffic.requests(mix, ctx.seed, ctx.seconds, sz["vocab"],
                                engine.max_len)
    warm = warm_up(ctx, engine, sched, mix, sz)
    mem = {name: harness.program_bytes(traced.lower().compile())
           for name, traced in engine.trace_programs().items()
           if name == "decode"}
    deliveries.clear()
    ctx.say(stage="warm", **warm, program_bytes=mem,
            compile_events=ctx.counter.n)
    if ctx.options.get("break_tokens"):   # the harness's own test: a
        real = sched.streams.stage        # token altered where it is staged
        sched.streams.stage = lambda rid, tok: real(rid, (tok + 1) % 7 + 2)
    compiles_before = ctx.counter.n
    clock = drive(ctx, sched, arrivals, mix, deliveries)
    compiles_in_window = ctx.counter.n - compiles_before

    values, counts, failed, finished = measures(
        ctx, arrivals, clock, deliveries, sched, mix)
    invariants = bool(engine.check_invariants())
    program = mem["decode"]["arguments"] + mem["decode"]["temp"]
    peak = harness.memory_peak_bytes(ctx.devices[:1], program)
    say_window(ctx, engine, clock, counts, deliveries, arrivals, sz, values,
               failed, compiles_in_window, pages_cached=engine.pool.num_cached)
    # tokens as the client got them (the stream), not the outcome's copy
    delivered_tokens = {rid: list(st.delivered)
                        for rid, st in sched.streams.streams.items()}

    # -- free the server, then the reference judges what it served ----------
    del engine, sched
    jax.clear_caches()
    t_ref = time.perf_counter()
    rows, info, control = check_outputs(
        ctx, config, ref, sz, arrivals, clock, finished, delivered_tokens)
    rows.append(("compiles_in_window", compiles_in_window, 0))
    rows.append(("pool_invariants_broken", 0 if invariants else 1, 0))
    ok, numbers = harness.comparison(rows)
    ctx.say(stage="correct", numbers=numbers, **info,
            reference_s=time.perf_counter() - t_ref)
    for name, c_rows in (control or {}).items():
        ctx.say(stage="control",
                what=f"the reference in {name}: its best token at each "
                     "position of the same prompts and served tokens, "
                     "judged by the float32 reference",
                numbers=harness.comparison(c_rows)[1])
    return {"correct": ok, "numbers": numbers,
            "attempted": counts["requests_attempted"], "failed": failed,
            "values": values, "memory_peak_bytes": peak, "counts": counts}


def mapped_positions(arrivals, clock, deliveries, at: float) -> int:
    """Cache positions the running requests hold at wall time ``at`` (the
    bytes a decode step has to read): prompt plus tokens delivered, for
    every request that has its first token and not yet its last."""
    total = 0
    for i in range(clock["submitted"]):
        got = [k for t, k in deliveries.get(clock["rid_of"][i], [])
               if t <= at]
        if got and sum(got) < arrivals[i].max_new_tokens:
            total += len(arrivals[i].prompt) + sum(got)
    return total
