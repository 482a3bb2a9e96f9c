"""Serving a ``glm5_next_text`` model (``apex_tpu.models.glm_next``: Kimi Delta
Attention in three layers of four, its state and convolution tails a slot,
BESIDE one latent pool whose rows the fourth layer's attention PICKS by an
indexer with a cache of its own; four residual streams under hyper-
connections; a leading dense layer and sparse expert layers) through the
server's normal path, exactly as ``gpt_serve`` serves GPT-2: the same
``PagedDecodeEngine`` (prefix sharing OFF: refused beside recurrent state and
over an indexed pool) under ``ContinuousBatchingScheduler`` with a
``StreamMux`` sink, the same window, clock readings and comparison, which are
IMPORTED from ``runners/gpt_serve.py`` (``warm_up``, ``drive``, ``measures``,
``say_window``, ``check_outputs``), the counters' differences from
``runners/nemotron_serve.py`` (``counted``), and the RESIDENT phase and the
controls' scorer from ``runners/deepseek_serve.py`` (``make_resident``,
``with_resident``, ``control_reference``). What is this file's own: ``build``
(the config object from the configuration file's keys, its ``assumed`` block
held to the program's ``ASSUMED``), ``decisions_agree`` over this model's
prompt path (routes and picks), the sparse layer's counters and the glue of
``run``.

A run has to end well inside the 360 s at which the driver stops it, under an
empty compile cache too (the first hand-in took 520 s there: my chip runs,
PR 47). A compile is the host's work and needs the weights' shapes alone, so
every large program of the run is compiled in ``build``, side by side, while
the program that draws the weights compiles and runs: the server's two, the
judge's and the program-side ``decisions_program`` (one each: the sequences
``judged_ahead`` names are all padded to ONE length). Nothing compiles
between the warm-up and the window's end.

Three controls, all with ``--control 1`` (``--option control=<name>`` reads
one): ``bfloat16_activations``, ``dense_attention`` (the float32 reference
attending every position before the query: a program that did not apply the
selection) and ``single_stream`` (the float32 reference with the residual maps
at their fixed point: a program that did not apply them): ``correct`` has to
refuse each.

``counts["sizes"]`` are the reference's sizes and ``layers``, the number of
sparse layers (what the accepted latent-attention readers count calls by);
``counts["dsa"]`` the window's share of the two device counters, and
``counts["attended_positions"]`` the mapped positions times their ratio.
"""

import time
import types
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import harness, traffic

gpt = harness.load_module("runners", "gpt_serve")
counted = harness.load_module("runners", "nemotron_serve").counted
resident = harness.load_module("runners", "deepseek_serve")

CONTROLS = {
    "bfloat16_activations": "the reference with what the configuration "
    "states as float32 in bfloat16 (one bfloat16 term into every product, "
    "the delta rule's inputs and its state rounded to bfloat16 at every "
    "token, the indexer, the hyper-connections' maps and the attention in "
    "bfloat16)",
    "dense_attention": "the float32 reference attending every position "
    "before the query, as a program that did not apply the indexer's "
    "selection would compute",
    "single_stream": "the float32 reference with Hres the identity, Hpre 1/4 "
    "and Hpost 1, as a program that did not apply the residual maps would "
    "compute"}


def model_config(config, sz):
    from apex_tpu.models.glm_next import ASSUMED, GlmNextConfig

    said = {name: form[0] for name, form in config["assumed"].items()
            if isinstance(form, list)}
    for name in sorted(set(said) | set(ASSUMED)):
        if said.get(name) != ASSUMED.get(name):
            raise harness.BenchmarkError(
                f"assumed {name}: the configuration file says "
                f"{said.get(name)!r}, the program implements "
                f"{ASSUMED.get(name)!r}")
    return GlmNextConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        layer_types=tuple(sz["layer_types"]),
        first_k_dense=sz["dense_layers"], num_heads=sz["heads"],
        head_dim=sz["head_dim"], conv_kernel=sz["conv_kernel"],
        kda_lower_bound=sz["kda_lower_bound"],
        kda_gate_rank=sz["kda_gate_rank"], q_lora_rank=sz["q_rank"],
        kv_lora_rank=sz["kv_rank"], qk_nope_head_dim=sz["nope"],
        v_head_dim=sz["v_dim"], index_n_heads=sz["index_heads"],
        index_head_dim=sz["index_width"], index_topk=sz["index_topk"],
        index_kpool=sz["index_pool"], index_rope_dim=sz["index_rope"],
        index_rope_theta=sz["index_rope_theta"],
        index_norm_eps=sz["index_norm_eps"], hc_mult=sz["streams"],
        hc_sinkhorn_iters=sz["sinkhorn_iters"], hc_eps=sz["hc_eps"],
        ffn_size=sz["dense_ffn"], moe_ffn_size=sz["expert_ffn"],
        shared_experts=int(config["n_shared_experts"]),
        num_experts=sz["router_experts"],
        experts_per_token=sz["experts_per_token"],
        routed_scaling_factor=sz["routed_scale"],
        swiglu_limit=sz["swiglu_limit"], experts_held=sz["experts_held"],
        expert_offset=sz["expert_offset"], rms_norm_eps=sz["eps"],
        max_position_embeddings=int(config["max_position_embeddings"]))


def build(ctx, config, ref, judged=()):
    """(engine, scheduler, deliveries, sizes, program bytes): the server a
    user runs. Every large program of the run is compiled here, side by side,
    from the weights' SHAPES, while the program that draws the weights
    compiles and runs: the server's two timed programs (XLA's account of
    their memory is read off them, and their first calls find them in the
    compile cache), and for sequences of ``judged`` tokens the judge's
    (``ref.scorer_program``) and :func:`decisions_program`."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import (ContinuousBatchingScheduler,
                                  PagedDecodeEngine, StreamMux)

    sz = ref.sizes_of(config)
    cfg = model_config(config, sz)
    if cfg.kv_row_width != sz["row_width"]:
        raise harness.BenchmarkError(
            f"the program's cache row is {cfg.kv_row_width} wide, the "
            f"configuration file says {sz['row_width']}")
    serving = config["serving"]
    slots, page, max_len = (int(serving["slots"]), int(serving["page_size"]),
                            int(serving["max_len"]))
    cache_dtype = {"bfloat16": jnp.bfloat16}[serving["cache_dtype"]]
    lengths = sorted({ref.padded_length(sz, n) for n in judged})
    with ThreadPoolExecutor(3 + 2 * len(lengths)) as pool:
        # the one bfloat16 tree of this seed: the reference's scorer reads
        # the same arrays after the server is freed
        drawn = pool.submit(ref.served_weights, sz, ctx.seed)
        shapes = jax.eval_shape(lambda key: ref.make_weights(sz, key),
                                ref.seed_key(ctx.seed))
        engine = PagedDecodeEngine(
            shapes, cfg, num_slots=slots, max_len=max_len,
            num_pages=PagedDecodeEngine.full_pool_pages(slots, max_len, page),
            page_size=page, cache_dtype=cache_dtype, prefix_sharing=False,
            buckets=[int(b) for b in serving["prefill_buckets"]])
        # both timed programs: the largest prefill bucket holds the most
        programs = engine.trace_programs()
        compiled = [pool.submit(lambda traced: harness.program_bytes(
            traced.lower().compile()), t) for t in programs.values()]
        ahead = [pool.submit(ref.scorer_program, sz, shapes, n)
                 for n in lengths] + [
            pool.submit(decisions_program, cfg, shapes, n,
                        sz["judged_tokens"]) for n in lengths]
        engine.params = drawn.result()
        mem = dict(zip(programs, (c.result() for c in compiled)))
        for program in ahead:
            program.result()
    deliveries = {}            # rid -> [(wall, n tokens), ...]

    def sink(rid, tenant, tokens):
        deliveries.setdefault(rid, []).append(
            (time.perf_counter(), len(tokens)))

    mux = StreamMux(injector=engine.injector, tracer=engine.tracer,
                    stats=engine.stats, sink=sink)
    sched = ContinuousBatchingScheduler(engine, eos_id=-1, streams=mux)
    return engine, sched, deliveries, sz, mem


_DECISIONS = {}     # a padded length -> the compiled program of that length


def decisions_program(cfg, params, length: int, judged: int):
    """The program's prompt path over a sequence padded to ``length``
    positions and from there to whole stretches (one stretch of 12,800
    positions would not fit beside the weights), compiled: ``(params, ids,
    mask, first) -> (the experts its routers chose (expert layers, positions,
    k), the groups its sparse layers picked at the positions that produced
    the ``judged`` tokens after ``first`` (sparse layers, judged,
    groups))``. ``params`` the served weights or their shapes."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import glm_next

    if length not in _DECISIONS:
        whole = -(-length // glm_next._STRETCH) * glm_next._STRETCH

        def program(params, ids, mask, first):
            chosen, picks = glm_next.prefill_layers(
                params, cfg, ids, mask, jnp.bfloat16, routes=True)[-2:]
            return chosen, picks[:, jnp.clip(
                first - 1 + jnp.arange(judged), 0, whole - 1)]

        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        _DECISIONS[length] = jax.jit(program).lower(
            params, i32(whole), i32(whole), i32()).compile()
    return _DECISIONS[length]


def decisions_agree(config, ref, sz, seed, prompt, served):
    """What the program's prompt path decided over ``prompt`` and the judged
    tokens of ``served``, teacher-forced, beside what the reference decided
    when it scored them: ``(the share of (token, expert layer) pairs whose
    chosen experts agree, that by expert layer, the share of the groups the
    reference's sparse layers picked at the judged positions that the program
    picked too)``."""
    import jax.numpy as jnp

    served = list(served)[:sz["judged_tokens"]]
    tokens = list(prompt) + served
    n, params = len(tokens), ref.served_weights(sz, seed)
    program = decisions_program(model_config(config, sz), params,
                                ref.padded_length(sz, n),
                                sz["judged_tokens"])
    ids = np.zeros(program.in_avals[0][1].shape, np.int32)
    ids[:n] = tokens
    mask = (np.arange(ids.shape[0]) < n).astype(np.int32)
    chosen, picks = (np.asarray(t) for t in program(
        params, jnp.asarray(ids), jnp.asarray(mask),
        jnp.int32(len(prompt))))
    want_chosen, want_picks = ref.Scorer(sz, seed).decided(prompt, served)
    same = (np.sort(chosen[:, :n], axis=-1) == want_chosen).all(-1).sum(-1) \
        / n
    groups = min(picks.shape[-1], want_picks.shape[-1])
    mine = picks[:, :len(served), :groups]
    theirs = want_picks[:, :len(served), :groups]
    return (float(same.mean()), [round(float(x), 4) for x in same],
            float((mine & theirs).sum() / max(theirs.sum(), 1)))


def dsa_counted(before, after):
    """The window's share of the sparse layers' two counters (int32 sums
    that may wrap: their difference modulo 2**32)."""
    if not before or "dsa_rows_read" not in (after or {}):
        return None
    diff = {k: int((int(after[k].reshape(-1)[0])
                    - int(before[k].reshape(-1)[0])) % (1 << 32))
            for k in ("dsa_rows_read", "dsa_rows_mapped")}
    return {"rows_read": diff["dsa_rows_read"],
            "rows_mapped": diff["dsa_rows_mapped"]}


def judged_ahead(config, arrivals, seed, sz):
    """How long the sequences are that ``gpt.check_outputs`` will hand the
    reference when no request finishes inside the window (this cell's
    answers outlast it) and every resident has delivered its judged tokens:
    the greedy resident with the longest prompt and a sample of the others,
    drawn as ``check_outputs`` draws it. A guess that turns out wrong costs a
    compile after the window, nothing else."""
    greedy = [i for i, a in enumerate(arrivals) if a.temperature <= 0]
    if not greedy:
        return []
    longest = max(greedy, key=lambda i: len(arrivals[i].prompt))
    rest = [i for i in greedy if i != longest]
    order = traffic.seeded(seed, 4).permutation(len(rest))
    picks = [longest] + [rest[j] for j in order[
        :int(config["correct"]["sample_requests"]) - 1]]
    return [len(arrivals[i].prompt) + min(
        arrivals[i].max_new_tokens, sz["judged_tokens"]) for i in picks]


def run(ctx):
    import jax

    config, mix = harness.views(ctx.cell, ctx.rehearsal)
    ref = ctx.cell.reference()
    sz = ref.sizes_of(config)
    arrivals = traffic.requests(mix, ctx.seed, ctx.seconds, sz["vocab"],
                                int(config["serving"]["max_len"]))
    n_resident = min(int(mix.get("resident", 0)),
                     int(config["serving"]["slots"]), len(arrivals))
    engine, sched, deliveries, sz, mem = build(
        ctx, config, ref,
        judged_ahead(config, arrivals[:n_resident], ctx.seed, sz))
    cache = engine.cache
    ctx.say(stage="built", buckets=list(engine.buckets),
            num_pages=engine.pool.num_pages, slots=engine.num_slots,
            row_bytes=cache.k.shape[-1] * cache.k.dtype.itemsize,
            pool_bytes=cache.k.nbytes, state_bytes=cache.state.nbytes,
            tail_bytes=cache.conv.nbytes,
            index_bytes=cache.index["rows"].nbytes,
            index_tail_bytes=cache.index["tail"].nbytes,
            state_bytes_per_slot=engine.cfg.state_bytes_per_slot())
    del cache
    warm = gpt.warm_up(ctx, engine, sched, mix, sz)
    deliveries.clear()
    ctx.say(stage="warm", **warm, program_bytes=mem,
            compile_events=ctx.counter.n)
    if ctx.options.get("break_tokens"):   # the harness's own test: a
        real = sched.streams.stage        # token altered where it is staged
        sched.streams.stage = lambda rid, tok: real(rid, (tok + 1) % 7 + 2)
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
    after = engine.read_counters()
    moe, dsa = counted(counters, after), dsa_counted(counters, after)

    in_window = {rid: [(t, k) for t, k in got if t >= clock["t0"]]
                 for rid, got in deliveries.items()}
    values, counts, failed, finished = gpt.measures(
        ctx, arrivals, clock, in_window, sched, mix)
    counts["moe"], counts["dsa"] = moe, dsa
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
            sz["experts_held"]],
        dsa_rows=dsa)
    counts["sizes"] = {**sz, "layers": sz["mla_layers"]}
    if dsa and dsa["rows_mapped"]:
        counts["attended_positions"] = int(round(
            counts["mapped_positions"] * dsa["rows_read"]
            / dsa["rows_mapped"]))
    ctx.say(stage="mapped", mapped_positions=counts["mapped_positions"],
            attended_positions=counts.get("attended_positions"),
            attended_per_slot=dsa and moe and moe["steps"] and round(
                dsa["rows_read"] / moe["steps"] / max(n_resident, 1), 1))
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
    agree = by_layer = picks = None
    if info.get("worst_at"):
        i = info["worst_at"][0]
        agree, by_layer, picks = decisions_agree(
            config, ref, sz, ctx.seed, arrivals[i].prompt,
            delivered_tokens[clock["rid_of"][i]])
    ctx.say(stage="correct", numbers=numbers, **info, routes_agree=agree,
            routes_agree_by_layer=by_layer, picks_agree=picks,
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
