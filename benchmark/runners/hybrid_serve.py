"""Serving a model with recurrent layers (``apex_tpu.models.hybrid``) through
the server's normal path, exactly as ``gpt_serve`` serves GPT-2: the same
``PagedDecodeEngine`` under ``ContinuousBatchingScheduler`` with a
``StreamMux`` sink, the same window, clock readings and comparison, which are
IMPORTED from ``runners/gpt_serve.py`` (``drive``, ``measures``,
``check_outputs``, ``warm_up``, ``mapped_positions``). What is this file's
own: ``build`` (another config object, and ``prefix_sharing`` off, which the
engine refuses for recurrent state) and the glue of ``run`` (the memory of
both timed programs, the hybrid's sizes in ``counts``, and the control: the
reference with its recurrent state in bfloat16), and ``same_work_every_seed``:
the sizes of the backlog come in ONE order, whatever ``--seed`` is.
"""

import dataclasses
import gc
import time
import types

from benchmark import harness, traffic

gpt = harness.load_module("runners", "gpt_serve")


def build(ctx, config, ref):
    """(engine, scheduler, deliveries, sizes): the server a user runs."""
    import jax.numpy as jnp

    from apex_tpu.models.hybrid import HybridConfig
    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, StreamMux)

    sz = ref.sizes_of(config)
    cfg = HybridConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_periods=sz["periods"],
        linear_per_period=sz["linear_per_period"], num_heads=sz["heads"],
        ffn_hidden_size=sz["ffn"], linear_heads=sz["linear_heads"],
        linear_key_dim=sz["linear_key_dim"],
        linear_value_dim=sz["linear_value_dim"],
        conv_kernel=sz["conv_kernel"], rms_norm_eps=sz["eps"],
        max_position_embeddings=int(config["max_position_embeddings"]))
    # the one bfloat16 tree of this seed: the reference's scorer reads the
    # same arrays after the server is freed
    params = ref.served_weights(sz, ctx.seed)
    serving = config["serving"]
    slots, page, max_len = (int(serving["slots"]), int(serving["page_size"]),
                            int(serving["max_len"]))
    cache_dtype = {"bfloat16": jnp.bfloat16}[serving["cache_dtype"]]
    engine = PagedDecodeEngine(
        params, cfg, num_slots=slots, max_len=max_len,
        num_pages=PagedDecodeEngine.full_pool_pages(slots, max_len, page),
        page_size=page, cache_dtype=cache_dtype, prefix_sharing=False)
    deliveries = {}            # rid -> [(wall, n tokens), ...]

    def sink(rid, tenant, tokens):
        deliveries.setdefault(rid, []).append(
            (time.perf_counter(), len(tokens)))

    mux = StreamMux(injector=engine.injector, tracer=engine.tracer,
                    stats=engine.stats, sink=sink)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1, streams=mux)
    return engine, sched, deliveries, sz


def same_work_every_seed(arrivals, mix):
    """The generator gives every seed one multiset of sizes and permutes it
    by ``--seed``. That is the same work only where the window drains the
    backlog; this cell's never does (it prefills about 35 of 256 prompts),
    so the window is a SAMPLE of the order, and which prompts it held moved
    ``serve_tokens_per_s`` by 2-5% from seed to seed (PERF.md, section 6).
    So the sizes come in one order, drawn from the mix's own ``sizes_seed``:
    request ``i`` has the same prompt length, ``max_new_tokens`` and
    temperature on every seed, and ``--seed`` gives the token contents and
    the samplers' seeds, as before."""
    n = len(arrivals)
    by_size = sorted(arrivals,
                     key=lambda a: (len(a.prompt), a.max_new_tokens))
    order = traffic.seeded(n, int(mix["sizes_seed"]), 5).permutation(n)
    temps = mix["temperatures"]
    return [dataclasses.replace(by_size[j],
                                temperature=float(temps[i % len(temps)]))
            for i, j in enumerate(order)]


def control_reference(ref):
    """What ``check_outputs`` takes for ``ref`` to give the CONTROL's rows:
    the tokens the reference with its recurrent state in bfloat16 puts first
    at each position of the same prompts and served tokens, judged by the
    float32 reference."""

    class Scorer:
        def __init__(self, sz, seed):
            self.sound = ref.Scorer(sz, seed)
            self.low = ref.Scorer(sz, seed, "bfloat16_state")

        def gaps(self, prompt, served):
            _, low_best = self.low.gaps(prompt, served)
            return self.sound.gaps(prompt, served, judged=low_best)

    return types.SimpleNamespace(Scorer=Scorer)


def run(ctx):
    import jax

    config, mix = harness.views(ctx.cell, ctx.rehearsal)
    ref = ctx.cell.reference()
    engine, sched, deliveries, sz = build(ctx, config, ref)
    ctx.say(stage="built", buckets=list(engine.buckets),
            num_pages=engine.pool.num_pages, slots=engine.num_slots,
            state_bytes_per_slot=engine.cfg.state_bytes_per_slot())
    arrivals = same_work_every_seed(
        traffic.requests(mix, ctx.seed, ctx.seconds, sz["vocab"],
                         engine.max_len), mix)
    # no arrivals: ``warm_up`` takes them only to compile a device padding
    # program per distinct prompt length, and the engine pads on the host
    warm = gpt.warm_up(ctx, engine, sched, mix, sz, [])
    # both timed programs: the largest prefill bucket holds the most
    mem = {name: harness.program_bytes(traced.lower().compile())
           for name, traced in engine.trace_programs().items()}
    deliveries.clear()
    ctx.say(stage="warm", **warm, program_bytes=mem,
            compile_events=ctx.counter.n)
    if ctx.options.get("break_tokens"):   # the harness's own test: a
        real = sched.streams.stage        # token altered where it is staged
        sched.streams.stage = lambda rid, tok: real(rid, (tok + 1) % 7 + 2)
    compiles_before = ctx.counter.n
    # what set-up left alive stays out of the window's collections, as in a
    # server that froze its heap once it was up: a full collection of this
    # process takes 0.15-0.2 s, three or four ticks, and whether one or
    # three of them fell into the window moved serve_tokens_per_s by 1.5%
    # between runs whose median tick was the same (PERF.md, section 6)
    gc.collect()
    gc.freeze()
    clock = gpt.drive(ctx, sched, arrivals, mix, deliveries)
    gc.unfreeze()
    compiles_in_window = ctx.counter.n - compiles_before

    values, counts, failed, finished = gpt.measures(
        ctx, arrivals, clock, deliveries, sched, mix)
    invariants = bool(engine.check_invariants())
    program = max(m["arguments"] + m["temp"] for m in mem.values())
    peak = harness.memory_peak_bytes(ctx.devices[:1], program)
    walls = [w for _, w in clock["step_walls"]]
    # the tick over the window, five seconds at a time: a run whose host
    # slowed or sped up half way shows here and not in one median
    by_5s = {}
    for t, w in clock["step_walls"]:
        by_5s.setdefault(int((t - clock["t0"]) // 5), []).append(w)
    ctx.say(stage="window", window_s=counts["window_s"],
            steps=len(walls), step_ms_p50=1e3 * harness.median(walls),
            step_ms_p50_by_5s=[round(1e3 * harness.median(by_5s[k]), 2)
                               for k in sorted(by_5s)],
            tokens_delivered=counts["tokens_delivered"],
            requests_submitted=counts["requests_submitted"],
            requests_finished=counts["requests_finished"], failed=failed,
            itl_ms_p50=counts["itl_ms_p50"], ttft_ms_p50=counts["ttft_ms_p50"],
            queue_depth_first=counts["queue_depth_first"],
            queue_depth_last=counts["queue_depth_last"],
            slowest_steps_at_s_ms=[
                [round(t - clock["t0"], 2), round(1e3 * w, 1)] for t, w in
                sorted(clock["step_walls"], key=lambda r: -r[1])[:3]],
            values=values, compiles_in_window=compiles_in_window,
            peak_bytes_in_use=(ctx.devices[0].memory_stats() or {}).get(
                "peak_bytes_in_use"))
    counts["buckets"] = list(engine.buckets)
    counts["slots"] = engine.num_slots
    counts["page_size"] = engine.page_size
    counts["first_delivery"] = {
        i: deliveries[clock["rid_of"][i]][0][0]
        for i in range(clock["submitted"])
        if clock["rid_of"][i] in deliveries}
    counts["prompt_tokens"] = [len(a.prompt) for a in arrivals]
    counts["traced"] = ctx.traced
    counts["sizes"] = sz
    delivered_tokens = {rid: list(st.delivered)
                        for rid, st in sched.streams.streams.items()}
    span = ctx.traced or (clock["t0"], clock["t1"])
    middle = 0.5 * (span[0] + span[1])
    counts["mapped_positions"] = gpt.mapped_positions(
        arrivals, clock, deliveries, middle)

    # -- free the server, then the reference judges what it served ----------
    del engine, sched
    jax.clear_caches()
    t_ref = time.perf_counter()
    judge = types.SimpleNamespace(control=False, seed=ctx.seed)
    rows, info, _ = gpt.check_outputs(
        judge, config, ref, sz, arrivals, clock, finished, delivered_tokens)
    rows.append(("compiles_in_window", compiles_in_window, 0))
    rows.append(("pool_invariants_broken", 0 if invariants else 1, 0))
    ok, numbers = harness.comparison(rows)
    ctx.say(stage="correct", numbers=numbers, **info,
            reference_s=time.perf_counter() - t_ref)
    if ctx.control:
        c_rows = gpt.check_outputs(
            judge, config, control_reference(ref), sz, arrivals, clock,
            finished, delivered_tokens)[0]
        ctx.say(stage="control",
                what="the reference with its recurrent state and scan in "
                     "bfloat16: its best token at each position of the same "
                     "prompts and served tokens, judged by the float32 "
                     "reference",
                numbers=harness.comparison(c_rows)[1])
    return {"correct": ok, "attempted": counts["requests_attempted"],
            "failed": failed, "values": values, "memory_peak_bytes": peak,
            "counts": counts}
