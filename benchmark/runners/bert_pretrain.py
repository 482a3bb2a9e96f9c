"""BERT masked-LM pre-training through the trainer's normal path: amp O2
with a dynamic loss scale, ``FusedAdam`` with float32 state, one donated
``jit`` (the README quick-start), and on several chips the same step under
``DistributedDataParallel`` inside ``ps.shard_map``.

Set-up builds ONE compiled step with its state, drives it through the
first steps from the seed (reading what ``correct`` compares) and hands the
same object to the window. After the window the program's state is freed
and the plain reference follows the same first steps.
"""

import collections
import time

import numpy as np


def build(ctx, config, mix, ref, *, opt_level=None, break_step=False):
    """(compiled step, state, feed) for this cell: the step a user runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import apply_bert, mlm_loss
    from apex_tpu.models.bert import BertConfig
    from apex_tpu.optimizers import FusedAdam

    sz = ref.sizes_of(config)
    cfg = BertConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_heads=sz["heads"],
        intermediate_size=sz["ffn"], max_position_embeddings=sz["positions"],
        type_vocab_size=sz["types"], layer_norm_eps=sz["eps"])
    train = config["training"]
    level = opt_level or train["amp_opt_level"]
    h = amp.initialize(opt_level=level,
                       loss_scale=train["loss_scale"], verbosity=0)
    o = train["optimizer"]
    opt = FusedAdam(lr=o["lr"], weight_decay=o["weight_decay"],
                    betas=tuple(o["betas"]), eps=o["eps"])
    chips = ctx.chips
    ddp = None
    if chips > 1:
        from apex_tpu.parallel import DistributedDataParallel
        from apex_tpu.transformer import parallel_state as ps
        ps.destroy_model_parallel()
        mesh = ps.initialize_model_parallel(devices=ctx.devices[:chips])
        ddp = DistributedDataParallel()

    def train_step(master, opt_state, scaler, ids, mask):
        p = h.cast_model(ddp.local_replica(master) if ddp else master)
        loss, grads, found_inf, scaler = h.value_and_grad(
            lambda p: mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"],
                               ids, mask),
            reduce_grads=ddp.allreduce_grads if ddp else None)(p, scaler)
        if ddp:
            loss = jax.lax.pmean(loss, ddp.axis_name)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        return master, opt_state, scaler, loss

    if break_step:      # the broken timed path of the harness's own test
        real_step = train_step

        def train_step(master, opt_state, scaler, ids, mask):
            *_, loss = real_step(master, opt_state, scaler, ids, mask)
            return master, opt_state, scaler, loss

    seed = ctx.seed

    def make_state(key):
        master = ref.make_weights(sz, key)
        if level == "O3":            # the control: no float32 master copy
            master = h.cast_model(master)
        return master, opt.init(master), h.init_state()

    if ddp:
        from apex_tpu.transformer import parallel_state as ps
        rep, data = P(), P(ps.DATA_AXIS)
        step = jax.jit(ps.shard_map(
            train_step, mesh=mesh, in_specs=(rep, rep, rep, data, data),
            out_specs=(rep, rep, rep, rep)), donate_argnums=(0, 1, 2))
        state = jax.jit(make_state, out_shardings=NamedSharding(mesh, rep))(
            ref.seed_key(seed))
        batch_sharding = NamedSharding(mesh, data)
    else:
        step = jax.jit(train_step, donate_argnums=(0, 1, 2))
        state = jax.jit(make_state)(ref.seed_key(seed))
        batch_sharding = None

    def put(batch):
        return tuple(jax.device_put(x, batch_sharding) for x in batch)

    from benchmark.traffic import Batches
    feed = Batches(mix, seed, sz["vocab"], chips)
    first = put(feed.batch(0))
    compiled = step.lower(*state, *first).compile()
    return compiled, list(state), feed, put, sz, opt


def first_steps(compiled, state, feed, put, sz, ref, seed, n_steps, b1):
    """Drive the first ``n_steps`` through the window's own call and feed;
    read each loss, the per-leaf norm of the first gradient as the optimizer
    got it (from Adam's first moment after one step: m = (1 - b1) g) and
    the per-leaf norm of the parameters' change after them."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda tree: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree.leaves(tree)]))
    change = jax.jit(lambda p, key: norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(a.dtype).astype(
            jnp.float32), p, ref.make_weights(sz, key))))
    losses, grad_norms = [], None
    for i in range(n_steps):
        *state, loss = compiled(*state, *put(feed.next()))
        losses.append(loss)
        if i == 0:
            grad_norms = np.asarray(norms(state[1].m)) / (1.0 - b1)
    update_norms = np.asarray(change(state[0], ref.seed_key(seed)))
    return state, [float(x) for x in losses], grad_norms, update_norms


def worst_leaf_gap(got, want, names):
    """Largest gap between the program's norm and the reference's over the
    leaves, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    floor = float(np.median(want))
    rel = np.abs(got - want) / np.maximum(want, floor)
    i = int(np.argmax(rel))
    return float(rel[i]), names[i]


def compare(readings, want, limits):
    losses, grad_norms, update_norms = readings
    g, g_leaf = worst_leaf_gap(grad_norms, want["grad_norms"],
                               want["leaves"])
    u, u_leaf = worst_leaf_gap(update_norms, want["update_norms"],
                               want["leaves"])
    rows = [(f"loss_gap_step{i}", abs(a - b), limits["loss_gap"])
            for i, (a, b) in enumerate(zip(losses, want["losses"]))]
    rows += [("grad_norm_gap_worst_leaf", g, limits["grad_norm_gap"]),
             ("update_norm_gap_worst_leaf", u, limits["update_norm_gap"])]
    return rows, {"grad_worst_leaf": g_leaf, "update_worst_leaf": u_leaf}


def run(ctx):
    import jax

    from benchmark import harness

    config, mix = harness.views(ctx.cell, ctx.rehearsal)
    ref = ctx.cell.reference()
    limits = config["correct"]["limits"]
    n_first = int(config["correct"]["first_steps"])
    compiled, state, feed, put, sz, opt = build(
        ctx, config, mix, ref, opt_level=ctx.options.get("opt_level"),
        break_step=bool(ctx.options.get("break_step")))
    mem = harness.program_bytes(compiled)
    ctx.say(stage="compiled", program_bytes=mem,
            compile_events=ctx.counter.n)
    state, losses, grad_norms, update_norms = first_steps(
        compiled, state, feed, put, sz, ref, ctx.seed, n_first, opt.beta1)
    ctx.say(stage="first_steps", losses=losses)

    # -- the window ---------------------------------------------------------
    prefetch = int(mix["prefetch"])
    max_inflight = int(mix["max_inflight"])
    pending = collections.deque(put(feed.next()) for _ in range(prefetch))
    inflight, all_losses = collections.deque(), []
    jax.block_until_ready(state)
    compiles_before = ctx.counter.n
    tracing = ctx.start_trace()
    t0 = time.perf_counter()
    steps = 0
    while time.perf_counter() - t0 < ctx.seconds:
        if tracing and time.perf_counter() - t0 >= ctx.trace_seconds:
            jax.block_until_ready(state)
            tracing = ctx.stop_trace()
        with ctx.span("feed"):
            batch = pending.popleft()
            pending.append(put(feed.next()))
        with ctx.span("dispatch"):
            *state, loss = compiled(*state, *batch)
        steps += 1
        inflight.append(loss)
        all_losses.append(loss)
        if len(inflight) > max_inflight:
            with ctx.span("wait"):
                inflight.popleft().block_until_ready()
    with ctx.span("drain"):
        jax.block_until_ready(state)
    t1 = time.perf_counter()
    if tracing:
        ctx.stop_trace()
    compiles_in_window = ctx.counter.n - compiles_before

    window = t1 - t0
    tokens = steps * feed.rows * feed.seq
    values = {"train_tokens_per_s_per_chip": tokens / window / ctx.chips,
              "setup_s": t0 - ctx.t_start}
    window_losses = np.asarray([float(x) for x in all_losses])
    scaler = state[2]
    skipped = int(scaler.overflows)
    failed = skipped + int(np.sum(~np.isfinite(window_losses)))
    peak = harness.memory_peak_bytes(
        ctx.devices[:ctx.chips], mem["arguments"] + mem["temp"])
    ctx.say(stage="window", steps=steps, window_s=window,
            step_ms=1e3 * window / max(steps, 1),
            last_loss=float(window_losses[-1]) if steps else None,
            skipped_steps=skipped, loss_scale=float(scaler.loss_scale),
            compiles_in_window=compiles_in_window,
            peak_bytes_in_use=[(d.memory_stats() or {}).get(
                "peak_bytes_in_use") for d in ctx.devices[:ctx.chips]])
    replicas_equal = True
    if ctx.chips > 1:     # every replica holds the same master weights
        leaf = jax.tree.leaves(state[0])[-1]
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        replicas_equal = all(np.array_equal(shards[0], s) for s in shards)

    # -- free the program, then the reference follows the same steps ---------
    del state, pending, inflight, all_losses, compiled, loss, batch
    batches = [feed.batch(i) for i in range(n_first)]
    t_ref = time.perf_counter()
    many = ctx.devices[:ctx.chips] if ctx.chips > 1 else None
    want = ref.train(sz, ctx.seed, batches, config["training"]["optimizer"],
                     int(config["correct"]["reference_row_block"]),
                     devices=many)
    rows, where = compare((losses, grad_norms, update_norms), want, limits)
    rows.append(("compiles_in_window", compiles_in_window, 0))
    rows.append(("nonfinite_losses",
                 int(np.sum(~np.isfinite(window_losses))), 0))
    rows.append(("replicas_differ", 0 if replicas_equal else 1, 0))
    ok, numbers = harness.comparison(rows)
    ctx.say(stage="correct", numbers=numbers, **where,
            reference_s=time.perf_counter() - t_ref,
            reference_losses=want["losses"])
    if ctx.control:
        low = ref.train(sz, ctx.seed, batches,
                        config["training"]["optimizer"],
                        int(config["correct"]["reference_row_block"]),
                        precision="bfloat16", devices=many)
        c_rows, _ = compare((low["losses"], low["grad_norms"],
                             low["update_norms"]), want, limits)
        ctx.say(stage="control", what="the reference in bfloat16 throughout",
                numbers=harness.comparison(c_rows)[1])
    return {"correct": ok, "numbers": numbers, "attempted": steps,
            "failed": failed, "values": values, "memory_peak_bytes": peak,
            "counts": {"steps": steps, "tokens": tokens,
                       "tokens_per_step_per_chip": feed.rows * feed.seq
                       // ctx.chips, "window_s": window,
                       "sizes": sz, "seq": feed.seq,
                       "rows_per_chip": feed.rows // ctx.chips}}
