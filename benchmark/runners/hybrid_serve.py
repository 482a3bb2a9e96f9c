"""Serving a model with recurrent layers (``apex_tpu.models.hybrid``) through
the server's normal path, exactly as ``gpt_serve`` serves GPT-2: the same
``PagedDecodeEngine`` under ``ContinuousBatchingScheduler`` with a
``StreamMux`` sink, the same window, clock readings and comparison, which are
IMPORTED from ``runners/gpt_serve.py`` (``warm_up``, ``drive``, ``measures``,
``say_window``, ``check_outputs``). What is this file's own: ``build``
(another config object, and ``prefix_sharing`` off, which the engine refuses
for recurrent state) and the glue of ``run`` (the memory of both timed
programs, the tick five seconds at a time, and the control: the reference
with its recurrent state in bfloat16).
"""

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
    arrivals = traffic.requests(mix, ctx.seed, ctx.seconds, sz["vocab"],
                                engine.max_len)
    warm = gpt.warm_up(ctx, engine, sched, mix, sz)
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
    clock = gpt.drive(ctx, sched, arrivals, mix, deliveries)
    compiles_in_window = ctx.counter.n - compiles_before

    values, counts, failed, finished = gpt.measures(
        ctx, arrivals, clock, deliveries, sched, mix)
    invariants = bool(engine.check_invariants())
    program = max(m["arguments"] + m["temp"] for m in mem.values())
    peak = harness.memory_peak_bytes(ctx.devices[:1], program)
    # the tick over the window, five seconds at a time: a run whose host
    # slowed or sped up half way shows here and not in one median
    by_5s = {}
    for t, w in clock["step_walls"]:
        by_5s.setdefault(int((t - clock["t0"]) // 5), []).append(w)
    gpt.say_window(
        ctx, engine, clock, counts, deliveries, arrivals, sz, values, failed,
        compiles_in_window,
        step_ms_p50_by_5s=[round(1e3 * harness.median(by_5s[k]), 2)
                           for k in sorted(by_5s)])
    delivered_tokens = {rid: list(st.delivered)
                        for rid, st in sched.streams.streams.items()}

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
    return {"correct": ok, "numbers": numbers,
            "attempted": counts["requests_attempted"], "failed": failed,
            "values": values, "memory_peak_bytes": peak, "counts": counts}
