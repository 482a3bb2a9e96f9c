"""Serving a ``nemotron_h`` model (``apex_tpu.models.nemotron_h``: Mamba-2,
attention over two K/V heads and latent expert layers, one mixer a layer)
through the server's normal path, exactly as ``gpt_serve`` serves GPT-2 and
``hybrid_serve`` the Gated DeltaNet hybrid: the same ``PagedDecodeEngine``
under ``ContinuousBatchingScheduler`` with a ``StreamMux`` sink, the same
window, clock readings and comparison, which are IMPORTED from
``runners/gpt_serve.py`` (``warm_up``, ``drive``, ``measures``,
``say_window``, ``check_outputs``), and the same control as ``hybrid_serve``
(the reference with its recurrent state and scan in bfloat16). What is this
file's own: ``build`` (the config object from the configuration file's keys:
the pattern string, the experts held and the first of them, the vocabulary
slice), and the glue of ``run``.

Two things the glue adds to ``hybrid_serve``'s. The program counts on the
device what its held experts got (``moe_load``, ``moe_hit``, ``moe_steps``,
in the donated cache); ``engine.read_counters()`` is called before and after
the window, never inside it, and the differences go to the readers as
``counts["moe"]``. And the ``correct`` line says ``routes_agree``: over the
request that read worst and the longest one, the share of (token, expert
layer) pairs for which the program's router (bfloat16 products before it) and
the float32 reference's chose the same ``num_experts_per_tok`` experts. A
choice that flips on a near tie moves a logit by one expert's worth and is no
fault; the limits of ``correct`` were set knowing the share.
"""

import time
import types

import numpy as np

from benchmark import harness, traffic

gpt = harness.load_module("runners", "gpt_serve")
hybrid = harness.load_module("runners", "hybrid_serve")


def model_config(config, sz):
    from apex_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        pattern=sz["pattern"], mamba_heads=sz["mamba_heads"],
        mamba_head_dim=sz["mamba_head_dim"], ssm_groups=sz["ssm_groups"],
        ssm_state=sz["ssm_state"], conv_kernel=sz["conv_kernel"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], num_experts=sz["router_experts"],
        experts_per_token=sz["experts_per_token"],
        moe_latent_size=sz["latent"], moe_ffn_size=sz["expert_ffn"],
        shared_ffn_size=sz["shared_ffn"],
        routed_scaling_factor=sz["routed_scale"],
        experts_held=sz["experts_held"], expert_offset=sz["expert_offset"],
        rms_norm_eps=sz["eps"],
        max_position_embeddings=int(config["max_position_embeddings"]))


def build(ctx, config, ref):
    """(engine, scheduler, deliveries, sizes): the server a user runs."""
    import jax.numpy as jnp

    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, StreamMux)

    sz = ref.sizes_of(config)
    cfg = model_config(config, sz)
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


def counted(before, after):
    """The window's share of the program's counters (int32, kept since the
    engine was built), as plain numbers for the readers."""
    if not before or not after:
        return None
    diff = {k: (after[k].astype(np.int64) - before[k]) for k in after}
    return {"load": diff["moe_load"].tolist(), "hit": diff["moe_hit"].tolist(),
            "steps": int(diff["moe_steps"].reshape(-1)[0])}


def routes_agree(config, ref, sz, seed, sequences):
    """The share of (token, expert layer) pairs whose chosen experts agree
    between the program's router, run over ``sequences`` (prompt and served
    tokens, teacher-forced) by its prompt path, and the reference's: (over
    all pairs, by expert layer)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import nemotron_h

    cfg = model_config(config, sz)
    params = ref.served_weights(sz, seed)
    scorer = ref.Scorer(sz, seed)

    @jax.jit
    def program(params, ids, mask):
        return nemotron_h.prefill_layers(
            params, cfg, nemotron_h.embed(params, ids), mask, jnp.bfloat16,
            routes=True)[-1]

    same, tokens_seen = np.zeros((sz["expert_layers"],)), 0
    for tokens in sequences:
        ids = np.zeros((sz["positions"],), np.int32)
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
    counters = engine.read_counters()
    compiles_before = ctx.counter.n
    clock = gpt.drive(ctx, sched, arrivals, mix, deliveries)
    compiles_in_window = ctx.counter.n - compiles_before
    moe = counted(counters, engine.read_counters())

    values, counts, failed, finished = gpt.measures(
        ctx, arrivals, clock, deliveries, sched, mix)
    counts["moe"] = moe
    invariants = bool(engine.check_invariants())
    program = max(m["arguments"] + m["temp"] for m in mem.values())
    peak = harness.memory_peak_bytes(ctx.devices[:1], program)
    # the tick over the window, five seconds at a time: a run whose host
    # slowed or sped up half way shows here and not in one median
    by_5s = {}
    for t, w in clock["step_walls"]:
        by_5s.setdefault(int((t - clock["t0"]) // 5), []).append(w)
    held = sz["experts_held"]
    gpt.say_window(
        ctx, engine, clock, counts, deliveries, arrivals, sz, values, failed,
        compiles_in_window,
        step_ms_p50_by_5s=[round(1e3 * harness.median(by_5s[k]), 2)
                           for k in sorted(by_5s)],
        moe_steps=moe and moe["steps"],
        moe_rows_per_step=moe and moe["steps"] and [
            round(sum(layer) / moe["steps"], 1) for layer in moe["load"]],
        moe_hit_per_step_of_held=moe and moe["steps"] and [
            [round(hit / moe["steps"], 1) for hit in moe["hit"]], held])
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
        total = lambda i: len(arrivals[i].prompt) + len(
            delivered_tokens.get(clock["rid_of"][i], ()))
        longest = max([i for i in finished if arrivals[i].temperature <= 0]
                      or [info["worst_at"][0]], key=total)
        agree, by_layer = routes_agree(config, ref, sz, ctx.seed, [
            list(arrivals[i].prompt)
            + list(delivered_tokens[clock["rid_of"][i]])
            for i in sorted({info["worst_at"][0], longest})])
    ctx.say(stage="correct", numbers=numbers, **info, routes_agree=agree,
            routes_agree_by_layer=by_layer,
            reference_s=time.perf_counter() - t_ref)
    if ctx.control:
        c_rows = gpt.check_outputs(
            judge, config, hybrid.control_reference(ref), sz, arrivals, clock,
            finished, delivered_tokens)[0]
        ctx.say(stage="control",
                what="the reference with its Mamba-2 state and scan in "
                     "bfloat16: its best token at each position of the same "
                     "prompts and served tokens, judged by the float32 "
                     "reference",
                numbers=harness.comparison(c_rows)[1])
    return {"correct": ok, "numbers": numbers,
            "attempted": counts["requests_attempted"], "failed": failed,
            "values": values, "memory_peak_bytes": peak, "counts": counts}
