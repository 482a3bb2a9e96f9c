"""Serving a ``deepseek_v3`` model (``apex_tpu.models.deepseek``: multi-head
latent attention over ONE pool of latent rows, a leading dense layer and
sparse expert layers) through the server's normal path, exactly as
``gpt_serve`` serves GPT-2: the same ``PagedDecodeEngine`` (prefix sharing ON)
under ``ContinuousBatchingScheduler`` with a ``StreamMux`` sink, the same
window, clock readings and comparison, which are IMPORTED from
``runners/gpt_serve.py`` (``warm_up``, ``drive``, ``measures``,
``say_window``, ``check_outputs``), the counters' differences from
``runners/nemotron_serve.py`` (``counted``). What is this file's own:
``build`` (the config object from the configuration file's keys, the prefill
buckets from its ``serving`` block), the RESIDENT phase, the control, and the
glue of ``run``.

**The resident phase.** The traffic file's ``resident`` says how many
requests, the first of the backlog's one order, are in their slots and
decoding when the window opens: a session whose cache is built during set-up
can be measured while it decodes. After the warm-up the runner submits them,
steps the scheduler until every one has delivered its first token (inside a
``resident_prefill`` span; all of it is ``setup_s``), and hands the REST of
the backlog to ``drive``, which opens the window. Afterwards it joins the
resident requests' ids into the clock it gives ``measures`` (request ``i`` of
the arrivals is request ``i`` of the clock again) and gives ``measures`` only
the deliveries stamped at or after the window opened: a token delivered during
set-up is not the window's. ``say_window`` gets every delivery, because the
positions a request holds (``counts["mapped_positions"]``, what a step's MLA
kernel reads) include what it was served before the window.

The program counts on the device what its held experts got (``moe_load``,
``moe_hit``, ``moe_steps``); ``engine.read_counters()`` is called before and
after the window, never inside it. The ``correct`` line says ``routes_agree``
as ``nemotron_serve``'s does. The control is the reference with what the
configuration states as float32 in bfloat16: one bfloat16 term into every
product, latents and attention in bfloat16 (``--option
control=bfloat16_attention`` reads the attention's part alone).
"""

import time
import types

import numpy as np

from benchmark import harness, traffic

gpt = harness.load_module("runners", "gpt_serve")
counted = harness.load_module("runners", "nemotron_serve").counted


def model_config(config, sz):
    from apex_tpu.models.deepseek import DeepseekConfig

    return DeepseekConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], first_k_dense=sz["dense_layers"],
        num_heads=sz["heads"], q_lora_rank=sz["q_rank"],
        kv_lora_rank=sz["kv_rank"], qk_nope_head_dim=sz["nope"],
        qk_rope_head_dim=sz["rope"], v_head_dim=sz["v_dim"],
        ffn_size=sz["dense_ffn"], moe_ffn_size=sz["expert_ffn"],
        shared_experts=int(config["n_shared_experts"]),
        num_experts=sz["router_experts"],
        experts_per_token=sz["experts_per_token"], n_group=sz["n_group"],
        topk_group=sz["topk_group"],
        routed_scaling_factor=sz["routed_scale"],
        experts_held=sz["experts_held"], expert_offset=sz["expert_offset"],
        rms_norm_eps=sz["eps"], rope_theta=sz["rope_theta"],
        rope_factor=sz["rope_factor"],
        rope_original_positions=sz["rope_original"],
        rope_beta_fast=sz["rope_beta_fast"],
        rope_beta_slow=sz["rope_beta_slow"], rope_mscale=sz["rope_mscale"],
        rope_mscale_all_dim=sz["rope_mscale_all_dim"],
        max_position_embeddings=int(config["max_position_embeddings"]))


def build(ctx, config, ref):
    """(engine, scheduler, deliveries, sizes): the server a user runs."""
    import jax.numpy as jnp

    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, StreamMux)

    sz = ref.sizes_of(config)
    cfg = model_config(config, sz)
    if cfg.kv_row_width != sz["row_width"]:
        raise harness.BenchmarkError(
            f"the program's cache row is {cfg.kv_row_width} wide, the "
            f"configuration file says {sz['row_width']}")
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
        page_size=page, cache_dtype=cache_dtype,
        buckets=[int(b) for b in serving["prefill_buckets"]])
    deliveries = {}            # rid -> [(wall, n tokens), ...]

    def sink(rid, tenant, tokens):
        deliveries.setdefault(rid, []).append(
            (time.perf_counter(), len(tokens)))

    mux = StreamMux(injector=engine.injector, tracer=engine.tracer,
                    stats=engine.stats, sink=sink)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1, streams=mux)
    return engine, sched, deliveries, sz


def make_resident(ctx, sched, arrivals, deliveries):
    """Submit ``arrivals`` and step until every one has delivered its first
    token. Returns their request ids, in order, and what the wave cost."""
    t = time.perf_counter()
    with ctx.span("resident_prefill"):
        rids = [sched.submit(gpt._request(a)) for a in arrivals]
        steps = 0
        while not all(rid in deliveries for rid in rids):
            sched.step()
            steps += 1
    return rids, {"requests": len(rids), "steps": steps,
                  "prompt_tokens": sum(len(a.prompt) for a in arrivals),
                  "seconds": time.perf_counter() - t}


def with_resident(clock, rids):
    """``drive``'s clock over the rest of the backlog, as a clock over the
    whole of it: the resident requests first (submitted, on time, when the
    window opened), then the rest, renumbered after them."""
    n = len(rids)
    return {**clock,
            "rid_of": {**dict(enumerate(rids)),
                       **{n + i: rid for i, rid in clock["rid_of"].items()}},
            "submitted_at": {**{i: clock["t0"] for i in range(n)},
                             **{n + i: t for i, t in
                                clock["submitted_at"].items()}},
            "submitted": clock["submitted"] + n}


def control_reference(ref, precision="bfloat16_activations"):
    """What ``check_outputs`` takes for ``ref`` to give the CONTROL's rows:
    the tokens the reference in the lower ``precision`` puts first at each
    position of the same prompts and served tokens, judged by the float32
    reference."""

    class Scorer:
        def __init__(self, sz, seed):
            self.sound = ref.Scorer(sz, seed)
            self.low = ref.Scorer(sz, seed, precision)

        def gaps(self, prompt, served):
            _, low_best = self.low.gaps(prompt, served)
            return self.sound.gaps(prompt, served, judged=low_best)

    return types.SimpleNamespace(Scorer=Scorer)


def routes_agree(config, ref, sz, seed, sequences):
    """The share of (token, expert layer) pairs whose chosen experts agree
    between the program's router, run over ``sequences`` (prompt and served
    tokens, teacher-forced) by its prompt path, and the reference's: (over
    all pairs, by expert layer)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import deepseek

    cfg = model_config(config, sz)
    params = ref.served_weights(sz, seed)
    scorer = ref.Scorer(sz, seed)

    @jax.jit
    def program(params, ids, mask):
        return deepseek.prefill_layers(
            params, cfg, deepseek.embed(params, ids), mask, jnp.bfloat16,
            routes=True)[-1]

    same, tokens_seen = np.zeros((sz["expert_layers"],)), 0
    for tokens in sequences:
        block = min(scorer.BLOCK, sz["positions"])
        ids = np.zeros((-(-len(tokens) // block) * block,), np.int32)
        ids[:len(tokens)] = tokens
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
    ctx.say(stage="built", buckets=list(engine.buckets),
            num_pages=engine.pool.num_pages, slots=engine.num_slots,
            row_bytes=engine.cache.k.shape[-1] * engine.cache.k.dtype.itemsize,
            pool_bytes=engine.cache.k.nbytes)
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
    rids, wave = make_resident(ctx, sched, arrivals[:n_resident], deliveries)
    ctx.say(stage="resident", **wave, pages_cached=engine.pool.num_cached,
            compile_events=ctx.counter.n)
    counters = engine.read_counters()
    compiles_before = ctx.counter.n
    clock = with_resident(
        gpt.drive(ctx, sched, arrivals[n_resident:], mix, deliveries), rids)
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
        step_ms_p50_by_5s=[round(1e3 * harness.median(by_5s[k]), 2)
                           for k in sorted(by_5s)],
        moe_steps=moe and moe["steps"],
        moe_rows_per_step=moe and moe["steps"] and [
            round(sum(layer) / moe["steps"], 1) for layer in moe["load"]],
        moe_hit_per_step_of_held=moe and moe["steps"] and [
            [round(hit / moe["steps"], 1) for hit in moe["hit"]],
            sz["experts_held"]])
    ctx.say(stage="mapped", mapped_positions=counts["mapped_positions"])
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
        low = ctx.options.get("control", "bfloat16_activations")
        c_rows = gpt.check_outputs(
            judge, config, control_reference(ref, low), sz, arrivals, clock,
            finished, delivered_tokens)[0]
        ctx.say(stage="control", precision=low,
                what="the reference with what the configuration states as "
                     "float32 in bfloat16 (one bfloat16 term into every "
                     "product, latents and attention in bfloat16): its best "
                     "token at each position of the same prompts and served "
                     "tokens, judged by the float32 reference",
                numbers=harness.comparison(c_rows)[1])
    return {"correct": ok, "numbers": numbers,
            "attempted": counts["requests_attempted"], "failed": failed,
            "values": values, "memory_peak_bytes": peak, "counts": counts}
