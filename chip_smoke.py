#!/usr/bin/env python3
"""The quickest proof that apex_tpu still starts on the chip.

    python3 chip_smoke.py                  # on a TPU; fails anywhere else
    python3 chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU backend

One process drives the package's main paths once each, at the full width
of the models the repo supports, through the entry points a user calls:

- server: gpt_medium (bf16 via the amp O2 model cast) behind
  ``PagedDecodeEngine`` + ``ContinuousBatchingScheduler``;
- trainer: BERT-Large amp O2 + ``FusedAdam`` at b64 s128, the README
  quick-start;
- four_chip (when JAX reports four devices or more): gpt_medium on a
  dp2 x tp2 mesh through ``ps.shard_map`` +
  ``forward_backward_no_pipelining`` + ``FusedAdam``.

``peak_bytes_in_use`` is a high-water mark of the whole process, so the
phases run in the order of what they were seen to hold on the chip,
smallest first, and each one's peak is its own. On the v5e runtime that
statistic counts live arrays only, not a running program's temporaries:
``compiled_bytes`` (XLA's own account of a program's arguments, temporaries
and code) is printed beside it. Weights are random, made from a seed;
nothing is read from disk or the network. Every phase checks
what it produced and prints one JSON line; an exception in any phase ends
the run with a traceback and a non-zero exit code. After the phases comes
a summary line, ``{"summary": {<phase>: "passed" | "skipped"}, ...,
"claim": null}``: this script measures nothing and claims nothing, and
the seconds it prints are smoke observations, not benchmark results. The
last line of standard output is the result, with the device as JAX
reports it and no other key::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits non-zero before any phase and prints no
result. ``--cpu-rehearsal`` is the one way to run it off the chip: tiny
presets, Pallas kernels in interpret mode, every line labelled
``"rehearsal": true``, and no result line, because a rehearsal is not
one. There is no automatic switch between the two.
"""

import argparse
import collections
import dataclasses
import json
import math
import sys
import time


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. ``FULL`` is the chip's; ``TINY`` only ever
    runs under ``--cpu-rehearsal``."""
    # trainer
    bert: str
    bert_batch: int
    bert_seq: int
    steps: int
    # server
    gpt: str
    slots: int
    page_size: int
    max_len: int
    long_prompt: int        # >= 512 at full size: the flash-kernel bucket
    shared_prefix: int      # page-aligned, shared by two requests
    short_prompt: tuple     # (lo, hi) lengths of the other prompts
    new_tokens: tuple       # max_new_tokens, cycled over the requests
    forced_steps: int       # teacher-forced decode steps after prefill
    # four_chip
    four_batch: int
    four_micro: int
    four_seq: int


FULL = Sizes(bert="bert_large", bert_batch=64, bert_seq=128, steps=4,
             gpt="gpt_medium", slots=8, page_size=16, max_len=1024,
             long_prompt=600, shared_prefix=64, short_prompt=(24, 120),
             new_tokens=(32, 48, 64), forced_steps=8,
             four_batch=8, four_micro=2, four_seq=1024)
TINY = Sizes(bert="bert_tiny", bert_batch=4, bert_seq=64, steps=4,
             gpt="gpt_tiny", slots=3, page_size=4, max_len=64,
             long_prompt=40, shared_prefix=8, short_prompt=(5, 20),
             new_tokens=(6, 8, 10), forced_steps=4,
             four_batch=8, four_micro=2, four_seq=32)


def describe(devices) -> dict:
    """The device as JAX reports it, in the result line's three keys."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def result_line(device: dict) -> str:
    """The last line of a run on the chip: these keys and no other —
    whoever runs this check reads exactly that."""
    return json.dumps({"ok": True, "device": device})


class Smoke:
    """Shared plumbing of the phases: the device, the labels every line
    carries, the compile-cache counters and the compile-and-inspect
    step."""

    def __init__(self, rehearsal: bool):
        import jax

        self.rehearsal = rehearsal
        self.sizes = TINY if rehearsal else FULL
        self.devices = jax.devices()   # starts the backend
        self.device = describe(self.devices)
        self.summary = {}
        self._events = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self._events.update([name]))

    def cache_counts(self):
        return (self._events["/jax/compilation_cache/cache_hits"],
                self._events["/jax/compilation_cache/cache_misses"])

    def line(self, phase: str, status: str, **fields) -> None:
        """Print one labelled JSON line and note the phase's outcome."""
        self.summary[phase] = status
        print(json.dumps({"phase": phase, "rehearsal": self.rehearsal,
                          "platform": self.device["platform"],
                          "device_kind": self.device["kind"], **fields}),
              flush=True)

    def emit(self, phase: str, since, **fields) -> None:
        """The line of a phase that passed; ``since`` is the
        :meth:`cache_counts` reading taken when it began."""
        hits, misses = self.cache_counts()
        stats = self.devices[0].memory_stats() or {}
        self.line(phase, "passed", **fields,
                  compile_cache={"hits": hits - since[0],
                                 "misses": misses - since[1]},
                  peak_bytes_in_use=stats.get(
                      "peak_bytes_in_use", "not reported by this backend"))

    def compile(self, what: str, traced):
        """Compile and inspect one traced program (``jitted.trace(...)``):
        returns (compiled, ``{"<kernel>": calls}`` over its ``pallas_call``
        equations, seconds the compile took). Every kernel is one of the
        package's list (``KERNEL_NAMES``), which is also where the names
        the phases expect are held to
        (``tests/L0/run_utils/test_kernel_names.py``). On the chip
        no call may be in interpret mode, and the compiled text must hold
        one Mosaic custom call per ``pallas_call`` traced — a kernel that
        silently became something else would not. In the rehearsal every
        call is in interpret mode and lowers to plain HLO."""
        from apex_tpu.utils.pallas import KERNEL_NAMES

        census = collections.Counter()
        for eqn in _pallas_calls(traced.jaxpr):
            name = eqn.params["jaxpr"].debug_info.func_name
            check(name in KERNEL_NAMES,
                  f"{what}: pallas_call {name} is not in KERNEL_NAMES")
            census[name] += 1
            check(bool(eqn.params["interpret"]) == self.rehearsal,
                  f"{what}: pallas_call {name} has "
                  f"interpret={eqn.params['interpret']} on platform "
                  f"{self.device['platform']}")
        t0 = time.perf_counter()
        compiled = traced.lower().compile()
        seconds = time.perf_counter() - t0
        if not self.rehearsal:
            n = compiled.as_text().count(
                'custom_call_target="tpu_custom_call"')
            check(n == sum(census.values()),
                  f"{what}: compiled text holds {n} tpu_custom_call(s), "
                  f"the trace held {sum(census.values())} pallas_call(s): "
                  f"{dict(census)}")
        return compiled, dict(census), seconds


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a (closed) jaxpr, through scans,
    remats, custom derivatives and mapped regions, not into the kernels'
    own bodies."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from _pallas_calls(sub)


def check(ok: bool, message: str) -> None:
    if not ok:
        raise AssertionError(message)


def compiled_bytes(compiled) -> dict:
    """XLA's account of one compiled program's device memory."""
    mem = compiled.memory_analysis()
    return {"arguments": mem.argument_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "code": mem.generated_code_size_in_bytes}


# -- server -------------------------------------------------------------------

def _requests(sz: Sizes, vocab: int):
    """Twelve seeded requests: one long prompt, two that share a
    page-aligned prefix, nine short; greedy and seeded-sampled
    alternate."""
    import numpy as np

    from apex_tpu.serving import Request

    rng = np.random.RandomState(0)

    def toks(n):
        return tuple(int(t) for t in rng.randint(2, vocab, size=n))

    shared = toks(sz.shared_prefix)
    lo, hi = sz.short_prompt
    prompts = [toks(sz.long_prompt), shared + toks(lo), shared + toks(hi // 2)]
    prompts += [toks(int(rng.randint(lo, hi))) for _ in range(9)]
    return [Request(prompt=p,
                    max_new_tokens=sz.new_tokens[i % len(sz.new_tokens)],
                    temperature=0.0 if i % 2 == 0 else 0.8, seed=100 + i)
            for i, p in enumerate(prompts)]


def _drain(engine, requests):
    """Run ``requests`` to completion on a fresh scheduler over
    ``engine``; returns (outcomes in submission order, scheduler steps,
    seconds)."""
    from apex_tpu.serving import ContinuousBatchingScheduler

    # eos_id=-1: no token ends a request early, so every stream must run
    # to its max_new_tokens
    sched = ContinuousBatchingScheduler(engine, eos_id=-1)
    for r in requests:
        sched.submit(r)
    steps, t0 = 0, time.perf_counter()
    while sched.busy:
        sched.step()
        steps += 1
    seconds = time.perf_counter() - t0
    return ([sched.outcomes[rid] for rid in sorted(sched.outcomes)],
            steps, seconds)


def phase_server(smoke: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import gpt as gpt_models
    from apex_tpu.models.gpt import apply_gpt_unsharded, init_gpt
    from apex_tpu.serving import PagedDecodeEngine

    since, sz = smoke.cache_counts(), smoke.sizes
    cfg = getattr(gpt_models, sz.gpt)()
    # bf16 inference params: the O2 model cast (norms stay float32)
    params = amp.initialize("O2", verbosity=0).cast_model(
        init_gpt(jax.random.PRNGKey(0), cfg))
    num_pages = PagedDecodeEngine.full_pool_pages(sz.slots, sz.max_len,
                                                  sz.page_size)
    engine = PagedDecodeEngine(params, cfg, num_slots=sz.slots,
                               max_len=sz.max_len, num_pages=num_pages,
                               page_size=sz.page_size)
    requests = _requests(sz, cfg.vocab_size)

    outcomes, steps, cold_s = _drain(engine, requests)
    for i, (req, out) in enumerate(zip(requests, outcomes)):
        check(out.error is None and out.reason == "length"
              and len(out.tokens) == req.max_new_tokens,
              f"request {i}: {len(out.tokens)} of {req.max_new_tokens} "
              f"tokens, reason {out.reason!r}, error {out.error!r}")
        check(all(0 <= t < cfg.vocab_size for t in out.tokens),
              f"request {i}: token outside the vocabulary")
    check(engine.pool.num_cached > 0,
          "no prefix page was cached: prefix sharing never engaged")
    check(engine.check_invariants(), "page-pool invariants broken")

    # the same traffic again, every program now compiled: the difference
    # of the two walls is compilation, and the repo's serving contract
    # (streams do not depend on page placement or on prefix-cache hits)
    # says the tokens must be the same ones
    again, steps2, warm_s = _drain(engine, requests)
    check([o.tokens for o in again] == [o.tokens for o in outcomes]
          and steps2 == steps,
          "the second pass over the same requests gave other tokens")

    # teacher-forced logits through prefill + the paged cache, against
    # the plain full forward in float32 at the highest matmul precision,
    # on the same (bf16-rounded) weights
    long_prompt = list(requests[0].prompt)
    rng = np.random.RandomState(1)
    forced = [int(t) for t in rng.randint(2, cfg.vocab_size,
                                          size=sz.forced_steps)]
    rows = [engine.prefill(0, long_prompt)[0]]
    for j, tok in enumerate(forced):
        check(engine.prepare_decode({0: len(long_prompt) + j}) == [],
              "teacher-forced slot was preempted")
        tokens = jnp.zeros((sz.slots,), jnp.int32).at[0].set(tok)
        rows.append(engine.decode(tokens, jnp.arange(sz.slots) == 0)[0])
    got = np.asarray(jnp.stack(rows), np.float32)
    engine.free_slot(0)

    ref_params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    seq = jnp.asarray([long_prompt + forced], jnp.int32)
    first = len(long_prompt) - 1

    @jax.jit
    def reference(p, seq):
        hidden = apply_gpt_unsharded(p, cfg, seq)[0, first:]
        return jnp.dot(hidden, p["embedding"]["word"]["embedding"].T)

    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference(ref_params, seq), np.float32)
    check(got.shape == want.shape == (sz.forced_steps + 1, cfg.vocab_size)
          and np.isfinite(got).all(), f"logits {got.shape} not finite")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.std(want))
    # Tolerance: the served model computes in bfloat16 (8 bits of
    # mantissa, a relative rounding error of 2^-9 per value) with float32
    # accumulation; the reference is float32 throughout. What is compared
    # is the largest error over the ~450,000 logits of nine positions,
    # against the logits' own spread. On the CPU backend the same program
    # at full width and depth gave 5.2% of the spread, and 3.6% at a
    # twelfth of the depth: the last rounding, of the logits themselves,
    # is most of it. 10% leaves the chip's different bfloat16 summation
    # order some room and nothing coarser: an int8 weight tier, a lost
    # layer, a wrong position or a page read from the wrong slot land at
    # 20% to 100% of the spread.
    tol = 0.10 * scale
    check(err <= tol, f"teacher-forced logits off by {err:.4f} "
                      f"(tolerance {tol:.4f} = 10% of std {scale:.4f})")
    rms = float(np.sqrt(np.mean((got - want) ** 2)))
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))

    # the engine's own prefill (largest bucket) and decode programs, the
    # executables it just ran, must hold their kernels as Mosaic custom
    # calls
    census, programs = {}, {}
    longest = max(engine.buckets)
    for name, traced in engine.trace_programs().items():
        compiled, census[name], _ = smoke.compile(name, traced)
        programs[name] = compiled_bytes(compiled)
    if longest >= 512:
        check("apex_flash_fwd" in census[f"prefill_{longest}"],
              f"prefill at bucket {longest} holds no flash kernel: "
              f"{census}")
    check(census["decode"].get("apex_paged_decode_fwd") == 1,
          f"decode holds no paged-attention kernel in its layer scan: "
          f"{census}")

    smoke.emit(
        "server", since, model=sz.gpt, slots=sz.slots,
        page_size=sz.page_size, max_len=sz.max_len, num_pages=num_pages,
        requests=len(requests),
        tokens=sum(len(o.tokens) for o in outcomes),
        prompt_lengths=[len(r.prompt) for r in requests],
        buckets=list(engine.buckets), scheduler_steps=steps,
        pages_cached=engine.pool.num_cached,
        first_pass_seconds=round(cold_s, 3),
        second_pass_seconds=round(warm_s, 3),
        compile_seconds_about=round(cold_s - warm_s, 3),
        seconds_per_scheduler_step=round(warm_s / steps, 5),
        logits_max_abs_err=round(err, 5), logits_std=round(scale, 5),
        logits_rms_err=round(rms, 5), logits_tolerance=round(tol, 5),
        argmax_agreement=round(agree, 3),
        pallas_calls=census, compiled_bytes=programs)


# -- trainer ------------------------------------------------------------------

def phase_trainer(smoke: Smoke) -> None:
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu import models
    from apex_tpu.models import apply_bert, init_bert, mlm_loss
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.functional.flash_attention import _takes_fmha

    since, sz = smoke.cache_counts(), smoke.sizes
    cfg = getattr(models, sz.bert)()
    h = amp.initialize(opt_level="O2", loss_scale="dynamic", verbosity=0)
    opt = FusedAdam(lr=1e-4, weight_decay=0.01)
    params = init_bert(jax.random.PRNGKey(0), cfg)
    state = (params, opt.init(params), h.init_state())
    del params
    ids = jax.random.randint(jax.random.PRNGKey(1),
                             (sz.bert_batch, sz.bert_seq), 0, cfg.vocab_size)
    mask = jnp.ones((sz.bert_batch, sz.bert_seq), jnp.int32)

    def train_step(master, opt_state, scaler, ids, mask):   # README
        p = h.cast_model(master)
        loss, grads, found_inf, scaler = h.value_and_grad(
            lambda p: mlm_loss(apply_bert(p, cfg, ids, mask)["mlm_logits"],
                               ids, mask))(p, scaler)
        master, opt_state = opt.step(grads, master, opt_state,
                                     found_inf=found_inf)
        return master, opt_state, scaler, loss

    compiled, census, compile_s = smoke.compile(
        "BERT train step",
        jax.jit(train_step, donate_argnums=(0, 1, 2)).trace(
            *state, ids, mask))
    # every LayerNorm (embeddings, two per layer, the MLM head) and the
    # loss run as kernels, forward and backward; at the full size (s128,
    # heads of 64) so does each layer's attention, one whole-sequence
    # forward and ONE backward kernel; the rehearsal's s64 heads of 32
    # keep to plain XLA
    norms = 2 * cfg.num_layers + 2
    expect = {"apex_ln_fwd": norms, "apex_ln_bwd": norms,
              "apex_xentropy_fwd": 1, "apex_xentropy_bwd": 1}
    fmha = _takes_fmha(sz.bert_seq, cfg.num_heads, cfg.head_dim)
    if fmha:
        expect.update(apex_fmha_fwd=cfg.num_layers,
                      apex_fmha_bwd=cfg.num_layers)
    check(census == expect, f"Pallas calls {census} != expected {expect}")

    *state, loss = compiled(*state, ids, mask)   # warm-up; also step 0
    losses = [float(loss)]
    t0 = time.perf_counter()
    for _ in range(sz.steps):
        *state, loss = compiled(*state, ids, mask)
        losses.append(loss)
    jax.block_until_ready(state)
    step_s = (time.perf_counter() - t0) / sz.steps
    losses = [float(x) for x in losses]
    scaler = state[2]

    uniform = math.log(cfg.vocab_size)
    check(abs(losses[0] - uniform) <= 0.5,
          f"step-0 loss {losses[0]:.4f} is not within 0.5 of "
          f"ln(vocab) = {uniform:.4f}")
    check(all(math.isfinite(x) for x in losses) and losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    check(int(scaler.overflows) == 0
          and int(scaler.unskipped) == sz.steps + 1,
          f"the loss scaler skipped a step: overflows "
          f"{int(scaler.overflows)}, unskipped {int(scaler.unskipped)} of "
          f"{sz.steps + 1}")

    smoke.emit(
        "trainer", since, model=sz.bert, batch=sz.bert_batch,
        seq=sz.bert_seq, compile_seconds=round(compile_s, 3),
        seconds_per_step=round(step_s, 4),
        losses=[round(x, 5) for x in losses], ln_vocab=round(uniform, 4),
        loss_scale=float(scaler.loss_scale), pallas_calls=census,
        attention=("whole-sequence kernel pair (apex_fmha_fwd / "
                   "apex_fmha_bwd) on the packed projection" if fmha else
                   f"XLA path: seq {sz.bert_seq} x head width "
                   f"{cfg.head_dim} is no shape the fmha pair is built for, "
                   "and the tiled flash kernel starts above seq 256"),
        compiled_bytes=compiled_bytes(compiled))


# -- four chips ---------------------------------------------------------------

def phase_four_chip(smoke: Smoke) -> None:
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.models import gpt as gpt_models
    from apex_tpu.models.gpt import (
        GPTModel, accumulate_tied_word_grads, gpt_loss_unsharded,
        gpt_pipeline_model, gpt_pipeline_partition_specs,
        gpt_to_pipeline_params, init_gpt)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_no_pipelining)

    since, sz = smoke.cache_counts(), smoke.sizes
    dp, tp = 2, 2
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=tp,
                                        devices=smoke.devices[:dp * tp])
    cfg = getattr(gpt_models, sz.gpt)()
    model = GPTModel(cfg, tp_size=tp)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1),
                             (sz.four_batch, sz.four_seq), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2),
                                (sz.four_batch, sz.four_seq), 0,
                                cfg.vocab_size)
    # one chip, the plain model, the same batch
    want = float(jax.jit(
        lambda p, i, t: gpt_loss_unsharded(p, cfg, i, t))(params, ids, labels))

    pspecs = gpt_pipeline_partition_specs(cfg)
    opt = FusedAdam(lr=1e-4, weight_decay=0.01)

    def shard(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)

    pipe_params = shard(gpt_to_pipeline_params(params, cfg, 1), pspecs)
    del params
    opt_state = opt.init(pipe_params)
    ospecs = type(opt_state)(step=P(), m=pspecs, v=pspecs)
    opt_state = shard(opt_state, ospecs)
    pipe_model = gpt_pipeline_model(model)
    M = sz.four_batch // dp // sz.four_micro

    def train_step(p, ostate, batch):   # examples/gpt/pretrain_gpt.py
        loss, grads = forward_backward_no_pipelining(
            pipe_model, p, batch, num_microbatches=M)
        loss = lax.pmean(loss, ps.DATA_AXIS)
        grads = accumulate_tied_word_grads(grads)
        grads = jax.tree.map(lambda g: lax.pmean(g, ps.DATA_AXIS), grads)
        p, ostate = opt.step(grads, p, ostate)
        return p, ostate, loss

    bspecs = {"input_ids": P(ps.DATA_AXIS), "labels": P(ps.DATA_AXIS)}
    batch = shard({"input_ids": ids, "labels": labels}, bspecs)
    step = jax.jit(ps.shard_map(
        train_step, mesh=mesh, in_specs=(pspecs, ospecs, bspecs),
        out_specs=(pspecs, ospecs, P())), donate_argnums=(0, 1))
    compiled, census, compile_s = smoke.compile(
        "dp2 x tp2 step", step.trace(pipe_params, opt_state, batch))
    check("all-reduce" in compiled.as_text(),
          "the compiled step holds no all-reduce")

    losses, t0 = [], time.perf_counter()
    for _ in range(sz.steps):
        pipe_params, opt_state, loss = compiled(pipe_params, opt_state, batch)
        losses.append(loss)
    jax.block_until_ready(pipe_params)
    step_s = (time.perf_counter() - t0) / sz.steps
    losses = [float(x) for x in losses]
    check(all(math.isfinite(x) for x in losses) and losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    # Tolerance: both sides run float32 at the TPU's default matmul
    # precision, which rounds each operand to bfloat16 the same way
    # whatever the layout, so they differ only in float32 summation order
    # (the tp split of every contraction, the dp mean) and in the few
    # bfloat16 roundings that order flips; the mean over thousands of
    # tokens moves by ~1e-5 of itself. At random init every model scores
    # about ln(vocab), so a loose bound would pass anything: 1e-4 still
    # fails a dropped all-reduce or a mis-sharded vocabulary, which move
    # the loss by a percent.
    check(abs(losses[0] - want) <= 1e-4 * abs(want),
          f"step-0 loss {losses[0]:.6f} on dp2 x tp2 != one-chip "
          f"{want:.6f} (rtol 1e-4)")

    leaf = pipe_params["stages"]["qkv"]["kernel"]
    holders = {s.device for s in leaf.addressable_shards}
    check(holders == set(smoke.devices[:dp * tp]),
          f"parameter shards live on {len(holders)} of {dp * tp} devices")
    in_use = []
    for d in smoke.devices[:dp * tp]:
        stats = d.memory_stats()
        if stats is None:   # the CPU backend keeps no such statistics
            in_use.append("not reported by this backend")
            continue
        in_use.append({"bytes_in_use": stats["bytes_in_use"],
                       "peak_bytes_in_use": stats["peak_bytes_in_use"]})
        # its share of parameters and both Adam moments, at the least
        floor = 3 * leaf.addressable_shards[0].data.nbytes
        check(stats["bytes_in_use"] > floor,
              f"device {d.id} holds {stats['bytes_in_use']} bytes, under "
              f"the {floor} of one sharded leaf and its moments")
    ps.destroy_model_parallel()

    smoke.emit(
        "four_chip", since, model=sz.gpt, mesh={"data": dp, "model": tp},
        global_batch=sz.four_batch, microbatches=M, seq=sz.four_seq,
        compile_seconds=round(compile_s, 3),
        seconds_per_step=round(step_s, 4), losses=[round(x, 5) for x in losses],
        one_chip_loss=round(want, 5), loss_rtol=1e-4,
        devices_holding_shards=len(holders), memory_per_device=in_use,
        pallas_calls=census, compiled_bytes=compiled_bytes(compiled))


def main() -> int:
    ap = argparse.ArgumentParser(
        description="On-chip smoke of apex_tpu (see the module docstring).")
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run the tiny presets on the CPU backend with Pallas in "
             "interpret mode; every output line says it is a rehearsal")
    args = ap.parse_args()

    import jax
    import jaxlib

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 4)
    # outside a checkout of the repo this import is what fails
    from apex_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    smoke = Smoke(args.cpu_rehearsal)
    dev = smoke.device
    if dev["platform"] != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found platform {dev['platform']!r} "
              f"({dev['count']} x {dev['kind']}), not a TPU. This check "
              "only means something on the chip; --cpu-rehearsal runs the "
              "tiny presets here.", file=sys.stderr)
        return 2

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    smoke.emit("device", smoke.cache_counts(), device_count=dev["count"],
               jax=jax.__version__, jaxlib=jaxlib.__version__,
               libtpu=libtpu_version, compile_cache_dir=cache_dir)

    for name, phase, needs in (("server", phase_server, 1),
                               ("trainer", phase_trainer, 1),
                               ("four_chip", phase_four_chip, 4)):
        if dev["count"] < needs:
            smoke.line(name, "skipped", skipped=f"needs {needs} devices, "
                       f"JAX reports {dev['count']}")
            continue
        phase(smoke)
        # the next phase gets the whole chip: drop this one's programs
        # (its arrays died with its frame)
        jax.clear_caches()

    print(json.dumps({"summary": smoke.summary,
                      "rehearsal": smoke.rehearsal, "claim": None}),
          flush=True)
    if not smoke.rehearsal:
        print(result_line(dev), flush=True)   # on the chip only
    return 0


if __name__ == "__main__":
    sys.exit(main())
