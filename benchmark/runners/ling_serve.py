"""Serving a ``bailing_hybrid`` model (``apex_tpu.models.bailing_hybrid``: Kimi
Delta Attention in five layers of six, its per-channel-gated state and
convolution tails a slot, BESIDE one latent (MLA) pool for the sixth; a
leading dense layer and sparse expert layers) through the server's normal
path, exactly as ``gpt_serve`` serves GPT-2: the same ``PagedDecodeEngine``
(prefix sharing OFF: it is refused beside recurrent state) under
``ContinuousBatchingScheduler`` with a ``StreamMux`` sink, the same window,
clock readings and comparison, which are IMPORTED from
``runners/gpt_serve.py`` (``warm_up``, ``drive``, ``measures``,
``say_window``, ``check_outputs``), the counters' differences from
``runners/nemotron_serve.py`` (``counted``), and the RESIDENT phase and the
controls' scorer from ``runners/deepseek_serve.py`` (``make_resident``,
``with_resident``, ``control_reference``: the traffic file's ``resident``
requests are prefilled during set-up and decode when the window opens; only
deliveries stamped at or after it count). What is this file's own: ``build``
(the config object from the configuration file's keys, its ``assumed``
block held to the program's ``ASSUMED``), ``routes_agree`` over this model's
prompt path, and the glue of ``run``.

Two controls, both with ``--control 1`` (``--option control=<name>`` reads
one): ``bfloat16_activations`` (the reference with what the configuration
states as float32 in bfloat16, the recurrent state among it) and
``scalar_gate`` (the float32 reference with each head's decay replaced by its
mean over the channels): ``correct`` has to refuse each.

``counts["sizes"]`` are the reference's sizes and ``layers``, the number of
MLA layers: the accepted latent-attention readers count ``layers`` calls of
``apex_mla_decode_fwd`` a decode step.
"""

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
    "the delta rule's inputs and its state rounded to bfloat16 at every "
    "token, the attention in bfloat16)",
    "scalar_gate": "the float32 reference with each head's decay replaced by "
    "its mean over the channels (Gated DeltaNet's rule), as a program that "
    "dropped the per-channel gate would compute"}


def model_config(config, sz):
    from apex_tpu.models.bailing_hybrid import ASSUMED, BailingHybridConfig

    said = {name: form[0] for name, form in config["assumed"].items()
            if isinstance(form, list)}
    for name in sorted(set(said) | set(ASSUMED)):
        if said.get(name) != ASSUMED.get(name):
            raise harness.BenchmarkError(
                f"assumed {name}: the configuration file says "
                f"{said.get(name)!r}, the program implements "
                f"{ASSUMED.get(name)!r}")
    return BailingHybridConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        layer_types=tuple(sz["layer_types"]),
        first_k_dense=sz["dense_layers"], num_heads=sz["heads"],
        head_dim=sz["head_dim"], conv_kernel=sz["conv_kernel"],
        kda_lower_bound=sz["kda_lower_bound"], kv_lora_rank=sz["kv_rank"],
        qk_nope_head_dim=sz["nope"], qk_rope_head_dim=sz["rope"],
        v_head_dim=sz["v_dim"], ffn_size=sz["dense_ffn"],
        moe_ffn_size=sz["expert_ffn"],
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
        page_size=page, cache_dtype=cache_dtype, prefix_sharing=False,
        buckets=[int(b) for b in serving["prefill_buckets"]])
    deliveries = {}            # rid -> [(wall, n tokens), ...]

    def sink(rid, tenant, tokens):
        deliveries.setdefault(rid, []).append(
            (time.perf_counter(), len(tokens)))

    mux = StreamMux(injector=engine.injector, tracer=engine.tracer,
                    stats=engine.stats, sink=sink)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1, streams=mux)
    return engine, sched, deliveries, sz


def routes_agree(config, ref, sz, seed, sequences):
    """``deepseek_serve.routes_agree`` for this model's prompt path: the
    share of (token, expert layer) pairs whose chosen experts agree between
    the program's router, run over ``sequences`` teacher-forced, and the
    reference's: (over all pairs, by expert layer)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import bailing_hybrid

    cfg = model_config(config, sz)
    params = ref.served_weights(sz, seed)
    scorer = ref.Scorer(sz, seed)

    @jax.jit
    def program(params, ids, mask):
        return bailing_hybrid.prefill_layers(
            params, cfg, bailing_hybrid.embed(params, ids), mask,
            jnp.bfloat16, routes=True)[-1]

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
            pool_bytes=cache.k.nbytes, state_bytes=cache.state.nbytes,
            tail_bytes=cache.conv.nbytes,
            state_bytes_per_slot=engine.cfg.state_bytes_per_slot())
    del cache
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
    ctx.say(stage="resident", **wave,
            ms_per_1000_prompt_tokens=1e6 * wave["seconds"]
            / max(wave["prompt_tokens"], 1), compile_events=ctx.counter.n)
    counters = engine.read_counters()
    compiles_before = ctx.counter.n
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
        block_table_uploads=engine.stats.block_table_uploads,
        step_ms_p50_by_5s=[round(1e3 * harness.median(by_5s[k]), 2)
                           for k in sorted(by_5s)],
        moe_steps=moe and moe["steps"],
        moe_rows_per_step=moe and moe["steps"] and [
            round(sum(layer) / moe["steps"], 1) for layer in moe["load"]],
        moe_hit_per_step_of_held=moe and moe["steps"] and [
            [round(hit / moe["steps"], 1) for hit in moe["hit"]],
            sz["experts_held"]])
    counts["sizes"] = {**sz, "layers": sz["mla_layers"]}
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
