"""Serving an ``exaone_moe`` model (``apex_tpu.models.exaone_moe``: sliding
layers three in four, their ``window`` positions a slot in a cyclic page
table beside the full layers' pool; a leading dense layer and sparse expert
layers) through the server's normal path, exactly as ``gpt_serve`` serves
GPT-2: the same ``PagedDecodeEngine`` (prefix sharing ON, over the full
layers' pages) under ``ContinuousBatchingScheduler`` with a ``StreamMux``
sink, the same window, clock readings and comparison, which are IMPORTED from
``runners/gpt_serve.py`` (``warm_up``, ``drive``, ``measures``,
``say_window``, ``check_outputs``), the counters' differences from
``runners/nemotron_serve.py`` (``counted``), and the RESIDENT phase and the
control's scorer from ``runners/deepseek_serve.py`` (``make_resident``,
``with_resident``, ``control_reference``: the traffic file's ``resident``
requests are prefilled during set-up and decode when the window opens; only
deliveries stamped at or after it count). What is this file's own: ``build``
(the config object from the configuration file's keys), ``window_positions``,
and the glue of ``run``.

Two controls, both with ``--control 1`` (``--option control=<name>`` reads
one): ``bfloat16_activations`` (the reference with what the configuration
states as float32 in bfloat16) and ``full_window`` (the float32 reference
with no band: every layer attends causally): ``correct`` has to refuse each.
"""

import contextlib
import gc
import time
import types

import numpy as np

from benchmark import harness, traffic

gpt = harness.load_module("runners", "gpt_serve")
counted = harness.load_module("runners", "nemotron_serve").counted
resident = harness.load_module("runners", "deepseek_serve")

CONTROLS = {
    "bfloat16_activations": "the reference with what the configuration "
    "states as float32 in bfloat16 (one bfloat16 term into every product, "
    "keys, values and attention in bfloat16)",
    "full_window": "the float32 reference with NO band: every layer attends "
    "causally, as a program that forgot the window, or read rows the window "
    "had left, would"}


def model_config(config, sz):
    from apex_tpu.models.exaone_moe import ExaoneMoeConfig

    return ExaoneMoeConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], layer_types=tuple(sz["layer_types"]),
        first_k_dense=sz["dense_layers"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], sliding_window=sz["sliding_window"],
        ffn_size=sz["dense_ffn"], moe_ffn_size=sz["expert_ffn"],
        shared_experts=int(config["num_shared_experts"]),
        num_experts=sz["router_experts"],
        experts_per_token=sz["experts_per_token"], n_group=sz["n_group"],
        topk_group=sz["topk_group"],
        routed_scaling_factor=sz["routed_scale"],
        experts_held=sz["experts_held"], expert_offset=sz["expert_offset"],
        rms_norm_eps=sz["eps"], rope_theta=sz["rope_theta"],
        max_position_embeddings=int(config["max_position_embeddings"]))


def build(ctx, config, ref):
    """(engine, scheduler, deliveries, sizes): the server a user runs."""
    import jax.numpy as jnp

    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, StreamMux)

    sz = ref.sizes_of(config)
    cfg = model_config(config, sz)
    serving = config["serving"]
    slots, page, max_len = (int(serving["slots"]), int(serving["page_size"]),
                            int(serving["max_len"]))
    # the one bfloat16 tree of this seed: the reference's scorer reads the
    # same arrays after the server is freed
    params = ref.served_weights(sz, ctx.seed)
    cache_dtype = {"bfloat16": jnp.bfloat16}[serving["cache_dtype"]]
    engine = PagedDecodeEngine(
        params, cfg, num_slots=slots, max_len=max_len,
        num_pages=PagedDecodeEngine.full_pool_pages(slots, max_len, page),
        page_size=page, cache_dtype=cache_dtype,
        buckets=[int(b) for b in serving["prefill_buckets"]])
    said = (cfg.window, engine.cache.ring, cfg.kv_row_width)
    if said != (int(serving["window"]), sz["ring_pages"], sz["row_width"]):
        raise harness.BenchmarkError(
            f"the program's window, pages of a slot's cycle and cache row "
            f"are {said}; the configuration file says otherwise")
    deliveries = {}            # rid -> [(wall, n tokens), ...]

    def sink(rid, tenant, tokens):
        deliveries.setdefault(rid, []).append(
            (time.perf_counter(), len(tokens)))

    mux = StreamMux(injector=engine.injector, tracer=engine.tracer,
                    stats=engine.stats, sink=sink)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1, streams=mux)
    return engine, sched, deliveries, sz


def window_positions(arrivals, clock, deliveries, at: float,
                     window: int) -> int:
    """Positions a sliding layer's decode call reads at wall time ``at``:
    ``min(positions held, window)`` of every request that
    ``gpt_serve.mapped_positions`` counts (first token out, last not yet),
    the new token's own row among them."""
    total = 0
    for i in range(clock["submitted"]):
        got = [k for t, k in deliveries.get(clock["rid_of"][i], [])
               if t <= at]
        if got and sum(got) < arrivals[i].max_new_tokens:
            total += min(len(arrivals[i].prompt) + sum(got) + 1, window)
    return total


@contextlib.contextmanager
def collections_timed():
    """The garbage collections of the block, as ``[generation, seconds]``
    each: a tick that takes eight ticks' time has to be told from one of
    them."""
    log = []

    def on(phase, info):
        if phase == "start":
            log.append([info["generation"], time.perf_counter()])
        else:
            log[-1][1] = time.perf_counter() - log[-1][1]

    gc.callbacks.append(on)
    try:
        yield log
    finally:
        gc.callbacks.remove(on)


def routes_agree(config, ref, sz, seed, sequences):
    """``deepseek_serve.routes_agree`` for this model's prompt path: the
    share of (token, expert layer) pairs whose chosen experts agree between
    the program's router, run over ``sequences`` teacher-forced, and the
    reference's: (over all pairs, by expert layer)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import exaone_moe

    cfg = model_config(config, sz)
    params = ref.served_weights(sz, seed)
    scorer = ref.Scorer(sz, seed)

    @jax.jit
    def program(params, ids, mask):
        return exaone_moe.prefill_layers(
            params, cfg, exaone_moe.embed(params, ids), mask,
            jnp.bfloat16)[3]

    same, tokens_seen = np.zeros((sz["expert_layers"],)), 0
    for tokens in sequences:
        ids = scorer._padded(list(tokens))
        mask = (np.arange(ids.shape[0]) < len(tokens)).astype(np.int32)
        mine = np.sort(np.asarray(program(
            params, jnp.asarray(ids), jnp.asarray(mask)))[:, :len(tokens)],
            axis=-1)
        same += (mine == scorer.routes(tokens)).all(-1).sum(-1)
        tokens_seen += len(tokens)
    if not tokens_seen:
        return None, None
    return (float(same.sum() / (tokens_seen * len(same))),
            [round(float(x), 4) for x in same / tokens_seen])


def run(ctx):
    import jax

    config, mix = harness.views(ctx.cell, ctx.rehearsal)
    ref = ctx.cell.reference()
    engine, sched, deliveries, sz = build(ctx, config, ref)
    cache = engine.cache
    ctx.say(stage="built", buckets=list(engine.buckets),
            num_pages=engine.pool.num_pages, slots=engine.num_slots,
            row_bytes=cache.k.shape[-1] * cache.k.dtype.itemsize,
            pool_bytes=cache.k.nbytes + cache.v.nbytes,
            window_pool_bytes=cache.wk.nbytes + cache.wv.nbytes,
            window_bytes_per_slot_per_layer=2 * cache.ring * engine.page_size
            * cache.wk.shape[-1] * cache.wk.dtype.itemsize)
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
    n_resident = min(int(mix.get("resident", 0)), engine.num_slots,
                     len(arrivals))
    rids, wave = resident.make_resident(ctx, sched, arrivals[:n_resident],
                                        deliveries)
    ctx.say(stage="resident", **wave, pages_cached=engine.pool.num_cached,
            compile_events=ctx.counter.n)
    counters = engine.read_counters()
    compiles_before = ctx.counter.n
    with collections_timed() as collections:
        clock = resident.with_resident(gpt.drive(
            ctx, sched, arrivals[n_resident:], mix, deliveries), rids)
    compiles_in_window = ctx.counter.n - compiles_before
    moe = counted(counters, engine.read_counters())

    in_window = {rid: [(t, k) for t, k in got if t >= clock["t0"]]
                 for rid, got in deliveries.items()}
    values, counts, failed, finished = gpt.measures(
        ctx, arrivals, clock, in_window, sched, mix)
    counts["moe"] = moe
    invariants = bool(engine.check_invariants())
    program = max(m["arguments"] + m["temp"] for m in mem.values())
    peak = harness.memory_peak_bytes(ctx.devices[:1], program)
    by_5s = {}
    for t, w in clock["step_walls"]:
        by_5s.setdefault(int((t - clock["t0"]) // 5), []).append(w)
    gpt.say_window(
        ctx, engine, clock, counts, deliveries, arrivals, sz, values, failed,
        compiles_in_window, resident=n_resident,
        pages_cached=engine.pool.num_cached,
        block_table_uploads=engine.stats.block_table_uploads,
        gc_over_5_ms=[[g, round(1e3 * d, 1)] for g, d in collections
                      if d > 5e-3], gc_runs=len(collections),
        step_ms_p50_by_5s=[round(1e3 * harness.median(by_5s[k]), 2)
                           for k in sorted(by_5s)],
        moe_steps=moe and moe["steps"],
        moe_rows_per_step=moe and moe["steps"] and [
            round(sum(layer) / moe["steps"], 1) for layer in moe["load"]],
        moe_hit_per_step_of_held=moe and moe["steps"] and [
            [round(hit / moe["steps"], 1) for hit in moe["hit"]],
            sz["experts_held"]])
    span = ctx.traced or (clock["t0"], clock["t1"])
    counts["window_positions"] = window_positions(
        arrivals, clock, deliveries, 0.5 * (span[0] + span[1]), sz["window"])
    ctx.say(stage="mapped", mapped_positions=counts["mapped_positions"],
            window_positions=counts["window_positions"])
    delivered_tokens = {rid: list(st.delivered)
                        for rid, st in sched.streams.streams.items()}

    # -- free the server, then the reference judges what it served ----------
    del engine, sched, cache
    jax.clear_caches()
    t_ref = time.perf_counter()
    judge = types.SimpleNamespace(control=False, seed=ctx.seed)
    rows, info, _ = gpt.check_outputs(
        judge, config, ref, sz, arrivals, clock, finished, delivered_tokens)
    rows.append(("compiles_in_window", compiles_in_window, 0))
    rows.append(("pool_invariants_broken", 0 if invariants else 1, 0))
    ok, numbers = harness.comparison(rows)
    agree = by_layer = None
    if info.get("worst_at"):
        i = info["worst_at"][0]
        agree, by_layer = routes_agree(config, ref, sz, ctx.seed, [
            list(arrivals[i].prompt)
            + list(delivered_tokens[clock["rid_of"][i]])])
    ctx.say(stage="correct", numbers=numbers, **info, routes_agree=agree,
            routes_agree_by_layer=by_layer,
            reference_s=time.perf_counter() - t_ref)
    if ctx.control:
        for low in [ctx.options["control"]] if "control" in ctx.options \
                else list(CONTROLS):
            t_low = time.perf_counter()
            c_rows = gpt.check_outputs(
                judge, config, resident.control_reference(ref, low), sz,
                arrivals, clock, finished, delivered_tokens)[0]
            ctx.say(stage="control", precision=low,
                    what=CONTROLS[low] + ": its best token at each position "
                    "of the same prompts and served tokens, judged by the "
                    "float32 reference",
                    numbers=harness.comparison(c_rows)[1],
                    control_s=time.perf_counter() - t_low)
    return {"correct": ok, "numbers": numbers,
            "attempted": counts["requests_attempted"], "failed": failed,
            "values": values, "memory_peak_bytes": peak, "counts": counts}
