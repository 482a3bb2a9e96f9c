"""Pipeline-parallel schedules over the ``pipe`` mesh axis.

Reference: ``apex/transformer/pipeline_parallel/schedules/__init__.py ::
get_forward_backward_func`` + ``fwd_bwd_no_pipelining.py``,
``fwd_bwd_pipelining_without_interleaving.py`` (1F1B),
``fwd_bwd_pipelining_with_interleaving.py`` (virtual/interleaved 1F1B).

TPU-native redesign — the *collective pipeline*.  The reference drives
each stage from host Python, posting NCCL p2p ops between ranks and
invoking torch autograd per microbatch.  Under XLA's single-controller
SPMD model the whole schedule is instead ONE jitted program:

- stage parameters are **stacked on a leading axis and sharded over the
  ``pipe`` mesh axis** (each device holds its stage's slice);
- the microbatch loop is a ``lax.scan`` over "ticks"; at every tick each
  device runs ONE forward microbatch (activations rotate +1 via
  ``lax.ppermute``) AND one backward microbatch (cotangents rotate -1)
  — true 1F1B steady state in a single uniform tick;
- the backward IS hand-written, with ``jax.vjp`` inside the tick: stage
  inputs are kept in a depth-``2*pp-1`` circular buffer and the
  backward recomputes the stage forward from the saved input (the
  activation-recompute discipline the reference pairs with 1F1B), so
  the scan itself is never differentiated and **live activation memory
  is O(pp × microbatch), independent of the number of microbatches** —
  the ``deallocate_output_tensor`` property, asserted on compiled HLO by
  ``tests/L0/run_transformer/test_pipeline_memory.py``;
- grad/loss accumulators ride the scan carry in fp32.

Bubble accounting: the plain schedule runs ``M + 2(pp-1)`` ticks for
``M`` microbatches — the same fill/steady/drain span as 1F1B (fill
``pp-1``, drain ``pp-1``).  The interleaved schedule uses ``vpp`` lanes
per device (virtual chunks round-robin over stages, chunk ``c`` on
device ``c % pp``) and runs ``M + 2(pp*vpp - 1)`` ticks; each tick
computes all resident lanes, so in steady state utilization matches the
reference (ticks are the same stage-size — see the module docstring of
``p2p_communication`` for why SPMD prefers uniform ticks).  Grads and
losses are bit-for-bit the same math as the reference's schedules.

On fill/drain "garbage" compute: during the bubble every stage runs its
tick body on masked data where the reference's ranks sit idle.  This is
deliberate — each stage is its own chip, so the garbage tick costs ZERO
wall-clock (the pipeline advances at one tick per step either way; the
bubble's cost is the tick COUNT, identical to the reference's 1F1B
bubble), and it keeps the scan body branch-free.  Gating the stage
behind per-device ``lax.cond`` would save only energy, at the price of
divergent control flow around the TP collectives inside ``stage_fn``.

Model contract (the functional analogue of the reference's
``forward_step_func(batch, model)`` protocol):

    model = PipelineModel(embed_fn, stage_fn, loss_fn)
    params = {"embed": ..., "stages": <leaves stacked on a leading
              stage axis>, "head": ...}

- ``embed_fn(embed_params, microbatch) -> hidden`` — first-stage input.
- ``stage_fn(one_stage_params, hidden) -> hidden`` — homogeneous body.
- ``loss_fn(head_params, hidden, microbatch) -> scalar`` — last stage.

For the pipelined schedules, call INSIDE ``parallel_state.shard_map``
with in_specs ``P(PIPE_AXIS)`` on the leading axis of ``stages`` leaves
(shape ``(pp, ...)``; interleaved: ``(vpp, pp, ...)`` with spec
``P(None, PIPE_AXIS)``) and replicated embed/head/batch.  Returned
grads match the (local) structure of ``params``; embed/head grads are
psum'd over ``pipe`` so every stage holds the full value — the analogue
of the reference's embedding-group allreduce.
"""

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.pipeline_parallel import microbatches as mb_calc
from apex_tpu.transformer.pipeline_parallel.p2p_communication import (
    send_backward_recv_backward,
    send_forward_recv_forward,
)

Pytree = Any


@dataclasses.dataclass(frozen=True)
class PipelineModel:
    embed_fn: Callable[[Pytree, Pytree], jax.Array]
    stage_fn: Callable[[Pytree, jax.Array], jax.Array]
    loss_fn: Callable[[Pytree, jax.Array, Pytree], jax.Array]


def split_batch_into_microbatches(batch: Pytree,
                                  num_microbatches: int) -> Pytree:
    """(B, ...) leaves -> (M, B//M, ...) (ref: the schedules' batch
    iterator; here a reshape so the microbatch loop can be a scan)."""
    def split(a):
        b = a.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch dim {b} not divisible by {num_microbatches} "
                "microbatches")
        return a.reshape((num_microbatches, b // num_microbatches)
                         + a.shape[1:])
    return jax.tree.map(split, batch)


def _num_microbatches(num_microbatches: Optional[int]) -> int:
    if num_microbatches is not None:
        return int(num_microbatches)
    return mb_calc.get_num_microbatches()


def _stage_apply(model: PipelineModel, checkpoint_stages: bool):
    fn = model.stage_fn
    return jax.checkpoint(fn) if checkpoint_stages else fn


# ---------------------------------------------------------------------------
# no pipelining
# ---------------------------------------------------------------------------

def forward_backward_no_pipelining(
    model: PipelineModel,
    params: Dict[str, Pytree],
    batch: Pytree,
    *,
    num_microbatches: Optional[int] = None,
    forward_only: bool = False,
    checkpoint_stages: bool = True,
    fp32_grad_accum: bool = True,
) -> Tuple[jax.Array, Optional[Pytree]]:
    """Grad accumulation over microbatches, no pipe collectives
    (ref: ``fwd_bwd_no_pipelining.py``). Usable with or without a mesh.

    ``fp32_grad_accum`` is the ``gradient_accumulation_fusion`` analogue
    (ref: ``fused_weight_gradient_mlp_cuda`` writing wgrads straight into
    fp32 ``main_grad`` buffers): the accumulator tree is fp32 regardless
    of param/compute dtype, so M bf16 microbatch grads don't lose low
    bits as they sum, and the fp32 result feeds the optimizer directly
    (every ``apex_tpu`` optimizer consumes fp32 grads natively — the
    TPU "fusion" is that XLA folds the widening cast into the bwd GEMM's
    epilogue rather than a separate kernel).
    """
    M = _num_microbatches(num_microbatches)
    mbs = split_batch_into_microbatches(batch, M)
    stage = _stage_apply(model, checkpoint_stages)

    def mb_loss(p, mb):
        x = model.embed_fn(p["embed"], mb)
        x, _ = lax.scan(lambda h, sp: (stage(sp, h), None), x, p["stages"])
        return model.loss_fn(p["head"], x, mb)

    zero = jnp.zeros((), jnp.float32)
    if forward_only:
        total, _ = lax.scan(
            lambda acc, mb: (acc + mb_loss(params, mb), None), zero, mbs)
        return total / M, None

    vg = jax.value_and_grad(mb_loss)
    acc_dtype = (lambda a: jnp.promote_types(a.dtype, jnp.float32)) \
        if fp32_grad_accum else (lambda a: a.dtype)

    def step(carry, mb):
        tot, g = carry
        loss, gi = vg(params, mb)
        g = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g, gi)
        return (tot + loss, g), None

    zero_g = jax.tree.map(
        lambda a: jnp.zeros(a.shape, acc_dtype(a)), params)
    (total, grads), _ = lax.scan(step, (zero, zero_g), mbs)
    grads = jax.tree.map(lambda a: a / M, grads)
    return total / M, grads


# ---------------------------------------------------------------------------
# plain (non-interleaved) pipelining — 1F1B equivalent
# ---------------------------------------------------------------------------

def _mb_at(mbs: Pytree, i, M: int) -> Pytree:
    """Dynamic microbatch slice (clipped; callers mask invalid ticks)."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, jnp.clip(i, 0, M - 1), 0,
                                           keepdims=False), mbs)


def _hidden_proto(model: PipelineModel, embed_p, mb0):
    shape = jax.eval_shape(model.embed_fn, embed_p, mb0)
    return jnp.zeros(shape.shape, shape.dtype)


def _masked_axpy(acc: Pytree, upd: Pytree, valid) -> Pytree:
    return jax.tree.map(
        lambda a, b: a + jnp.where(valid, b, 0).astype(a.dtype), acc, upd)


def _zeros_f32_like(tree: Pytree) -> Pytree:
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.promote_types(a.dtype,
                                                       jnp.float32)), tree)


def forward_backward_pipelining_without_interleaving(
    model: PipelineModel,
    params: Dict[str, Pytree],
    batch: Pytree,
    *,
    num_microbatches: Optional[int] = None,
    forward_only: bool = False,
    checkpoint_stages: bool = True,
) -> Tuple[jax.Array, Optional[Pytree]]:
    """Collective 1F1B (ref: ``fwd_bwd_pipelining_without_interleaving``).

    Call inside shard_map; ``params["stages"]`` leaves arrive as the
    local ``(1, ...)`` slice of the ``(pp, ...)`` stack.

    **Memory discipline** (the schedule's reason to exist — ref:
    ``deallocate_output_tensor`` + the warmup/steady/cooldown split).  The
    backward is written INTO the tick by hand with ``jax.vjp`` rather
    than differentiating the microbatch scan: tick ``t`` forwards
    microbatch ``t - d`` AND backwards microbatch ``t - 2(pp-1) + d``
    (1F1B steady state), with the forward's stage inputs kept in a
    circular buffer of depth ``2*pp - 1`` — the live-activation bound is
    therefore **O(pp × microbatch), independent of the global batch**,
    and the scan itself is never differentiated so no per-tick residuals
    accumulate (``tests/L0/run_transformer/test_pipeline_memory.py``
    asserts the compiled peak temp memory is flat in M).  The backward
    recomputes the stage forward from the saved input (the
    activation-recompute discipline the reference pairs with 1F1B);
    ``checkpoint_stages`` is accepted for API compatibility but the
    recompute is inherent here.

    Grad accumulation is fp32 regardless of param dtype (the schedule-
    level ``gradient_accumulation_fusion`` analogue).
    """
    del checkpoint_stages  # recompute-from-saved-input is inherent
    M = _num_microbatches(num_microbatches)
    mbs = split_batch_into_microbatches(batch, M)
    pp = lax.axis_size(ps.PIPE_AXIS)
    d = lax.axis_index(ps.PIPE_AXIS)
    stage = model.stage_fn
    stage_p = jax.tree.map(lambda a: a[0], params["stages"])
    embed_p, head_p = params["embed"], params["head"]
    state0 = _hidden_proto(model, embed_p, _mb_at(mbs, 0, M))

    if forward_only:
        T = M + pp - 1

        def tick_f(carry, t):
            state, acc = carry
            x_in = jnp.where(d == 0,
                             model.embed_fn(embed_p, _mb_at(mbs, t, M)),
                             state)
            y = stage(stage_p, x_in)
            m_l = t - (pp - 1)
            l = model.loss_fn(head_p, y, _mb_at(mbs, m_l, M))
            acc = acc + jnp.where((m_l >= 0) & (d == pp - 1),
                                  l.astype(jnp.float32), 0.0)
            return (send_forward_recv_forward(y), acc), None

        (_, total), _ = lax.scan(tick_f, (state0, jnp.float32(0)),
                                 jnp.arange(T))
        return lax.psum(total / M, ps.PIPE_AXIS), None

    R = 2 * pp - 1          # residual-ring depth: max input residency
    T = M + 2 * (pp - 1)    # fill + steady 1F1B + drain

    def tick(carry, t):
        state, cot, ring, g_stage, g_embed, g_head, loss_acc = carry

        # -- forward half: stage d forwards microbatch t - d ------------
        m_f = t - d
        fwd_valid = (m_f >= 0) & (m_f < M)
        x_in = jnp.where(d == 0,
                         model.embed_fn(embed_p, _mb_at(mbs, t, M)),
                         state)
        y = stage(stage_p, x_in)
        slot_f = jnp.mod(m_f, R)
        old = lax.dynamic_index_in_dim(ring, slot_f, 0, keepdims=False)
        ring = lax.dynamic_update_index_in_dim(
            ring, jnp.where(fwd_valid, x_in, old), slot_f, 0)

        # -- loss half: last stage seeds the backward from this tick's y
        m_l = t - (pp - 1)
        loss_valid = (m_l >= 0) & (m_l < M)
        mb_l = _mb_at(mbs, m_l, M)
        l, loss_vjp = jax.vjp(
            lambda hp, yy: model.loss_fn(hp, yy, mb_l), head_p, y)
        seed = jnp.where(loss_valid & (d == pp - 1), 1.0 / M, 0.0)
        dhead, dy_loss = loss_vjp(seed.astype(l.dtype))
        loss_acc = loss_acc + jnp.where(loss_valid & (d == pp - 1),
                                        l.astype(jnp.float32), 0.0)
        g_head = _masked_axpy(g_head, dhead, True)  # seed already masks

        # -- backward half: stage d backwards microbatch t - 2(pp-1) + d
        m_b = t - 2 * (pp - 1) + d
        bwd_valid = (m_b >= 0) & (m_b < M)
        g_in = jnp.where(d == pp - 1, dy_loss, cot)
        x_saved = lax.dynamic_index_in_dim(ring, jnp.mod(m_b, R), 0,
                                           keepdims=False)
        _, stage_vjp = jax.vjp(stage, stage_p, x_saved)
        dstage, dx = stage_vjp(g_in)
        g_stage = _masked_axpy(g_stage, dstage, bwd_valid)
        mb_b = _mb_at(mbs, m_b, M)
        _, embed_vjp = jax.vjp(lambda ep: model.embed_fn(ep, mb_b),
                               embed_p)
        (dembed,) = embed_vjp(dx)
        g_embed = _masked_axpy(g_embed, dembed, bwd_valid & (d == 0))

        return (send_forward_recv_forward(y),
                send_backward_recv_backward(dx),
                ring, g_stage, g_embed, g_head, loss_acc), None

    carry0 = (state0, jnp.zeros_like(state0),
              jnp.zeros((R,) + state0.shape, state0.dtype),
              _zeros_f32_like(stage_p), _zeros_f32_like(embed_p),
              _zeros_f32_like(head_p), jnp.float32(0))
    (_, _, _, g_stage, g_embed, g_head, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(T))

    loss = lax.psum(loss_acc, ps.PIPE_AXIS) / M
    grads = {
        "stages": jax.tree.map(lambda a: a[None], g_stage),
        # embed grads live on stage 0 (injection), head grads on the last
        # stage (loss seed): replicate both — the analogue of the
        # reference's embedding-group allreduce
        "embed": lax.psum(g_embed, ps.PIPE_AXIS),
        "head": lax.psum(g_head, ps.PIPE_AXIS),
    }
    return loss, grads


# ---------------------------------------------------------------------------
# interleaved (virtual pipeline) — lanes of round-robin chunks
# ---------------------------------------------------------------------------

def forward_backward_pipelining_with_interleaving(
    model: PipelineModel,
    params: Dict[str, Pytree],
    batch: Pytree,
    *,
    num_microbatches: Optional[int] = None,
    forward_only: bool = False,
    checkpoint_stages: bool = True,
    virtual_pipeline_size: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Pytree]]:
    """Interleaved schedule (ref: ``fwd_bwd_pipelining_with_interleaving``).

    Model chunk ``c`` (of ``pp*vpp``) lives on device ``c % pp`` —
    exactly the reference's round-robin assignment.  ``params["stages"]``
    leaves arrive as the local ``(vpp, 1, ...)`` slice of a
    ``(vpp, pp, ...)`` stack (``[l, dev]`` = chunk ``l*pp + dev``).
    Each device keeps ``vpp`` activation lanes; lane ``l`` holds the
    microbatch currently entering chunk ``l*pp + dev``.  One ppermute
    per tick rotates all lanes; the first stage additionally rolls
    lanes by one (a chunk boundary wraps from the last stage back to
    the first).
    """
    vpp = virtual_pipeline_size or \
        ps.get_virtual_pipeline_model_parallel_world_size()
    if vpp is None or vpp < 1:
        raise ValueError("interleaved schedule requires a virtual "
                         "pipeline size (initialize_model_parallel("
                         "virtual_pipeline_model_parallel_size_=...))")
    del checkpoint_stages  # recompute-from-saved-input is inherent
    M = _num_microbatches(num_microbatches)
    mbs = split_batch_into_microbatches(batch, M)
    pp = lax.axis_size(ps.PIPE_AXIS)
    d = lax.axis_index(ps.PIPE_AXIS)
    stage = model.stage_fn
    stage_p = jax.tree.map(lambda a: a[:, 0], params["stages"])  # (vpp,...)
    embed_p, head_p = params["embed"], params["head"]
    n_chunks = pp * vpp
    # chunk ids this device hosts, one per lane: c(l) = l*pp + d
    chunk = jnp.arange(vpp) * pp + d
    state0 = _hidden_proto(model, embed_p, _mb_at(mbs, 0, M))
    lanes0 = jnp.zeros((vpp,) + state0.shape, state0.dtype)

    def fwd_lanes(t, lanes):
        """One tick of the forward wave: inject at chunk 0, apply every
        resident chunk, rotate +1 with the stage-0 lane roll (a chunk
        boundary wraps from the last stage back to the first)."""
        inject = model.embed_fn(embed_p, _mb_at(mbs, t, M))
        lane0 = jnp.where(d == 0, inject, lanes[0])
        x_in = jnp.concatenate([lane0[None], lanes[1:]], axis=0)
        ys = jax.vmap(stage)(stage_p, x_in)
        return x_in, ys

    def rotate_fwd(ys):
        recv = send_forward_recv_forward(ys)
        return jnp.where(d == 0, jnp.roll(recv, 1, axis=0), recv)

    if forward_only:
        T = M + n_chunks - 1

        def tick_f(carry, t):
            lanes, acc = carry
            _, ys = fwd_lanes(t, lanes)
            m_l = t - (n_chunks - 1)
            l = model.loss_fn(head_p, ys[vpp - 1], _mb_at(mbs, m_l, M))
            acc = acc + jnp.where((m_l >= 0) & (d == pp - 1),
                                  l.astype(jnp.float32), 0.0)
            return (rotate_fwd(ys), acc), None

        (_, total), _ = lax.scan(tick_f, (lanes0, jnp.float32(0)),
                                 jnp.arange(T))
        return lax.psum(total / M, ps.PIPE_AXIS), None

    # Backward written into the tick, as in the plain schedule: chunk c
    # forwards microbatch t-c and backwards microbatch t-2(N-1)+c, with
    # per-lane input rings of depth 2N-1 bounding live activations at
    # O(vpp * N * microbatch) — the interleaved schedule's higher
    # in-flight count, independent of M.
    R = 2 * n_chunks - 1
    T = M + 2 * (n_chunks - 1)

    def tick(carry, t):
        lanes, cot, ring, g_stage, g_embed, g_head, loss_acc = carry

        # -- forward half ----------------------------------------------
        m_f = t - chunk                       # (vpp,) microbatch per lane
        x_in, ys = fwd_lanes(t, lanes)
        slot_f = jnp.mod(m_f, R)
        fwd_valid = (m_f >= 0) & (m_f < M)

        def save(ring_l, x_l, slot_l, ok_l):
            old = lax.dynamic_index_in_dim(ring_l, slot_l, 0,
                                           keepdims=False)
            return lax.dynamic_update_index_in_dim(
                ring_l, jnp.where(ok_l, x_l, old), slot_l, 0)

        ring = jax.vmap(save)(ring, x_in, slot_f, fwd_valid)

        # -- loss half: chunk N-1 = lane vpp-1 on the last stage -------
        m_l = t - (n_chunks - 1)
        loss_valid = (m_l >= 0) & (m_l < M)
        mb_l = _mb_at(mbs, m_l, M)
        l, loss_vjp = jax.vjp(
            lambda hp, yy: model.loss_fn(hp, yy, mb_l), head_p,
            ys[vpp - 1])
        seed = jnp.where(loss_valid & (d == pp - 1), 1.0 / M, 0.0)
        dhead, dy_loss = loss_vjp(seed.astype(l.dtype))
        loss_acc = loss_acc + jnp.where(loss_valid & (d == pp - 1),
                                        l.astype(jnp.float32), 0.0)
        g_head = _masked_axpy(g_head, dhead, True)  # seed already masks

        # -- backward half ---------------------------------------------
        m_b = t - 2 * (n_chunks - 1) + chunk  # (vpp,)
        bwd_valid = (m_b >= 0) & (m_b < M)
        # chunk N-1 seeds from this tick's loss; every other chunk uses
        # the cotangent received from chunk c+1 (rotated in last tick)
        last = (jnp.arange(vpp) == vpp - 1) & (d == pp - 1)
        g_in = jnp.where(last.reshape((vpp,) + (1,) * dy_loss.ndim),
                         dy_loss[None], cot)
        x_saved = jax.vmap(
            lambda ring_l, slot_l: lax.dynamic_index_in_dim(
                ring_l, slot_l, 0, keepdims=False))(ring, jnp.mod(m_b, R))

        def lane_bwd(sp_l, x_l, g_l):
            _, vjp_l = jax.vjp(stage, sp_l, x_l)
            return vjp_l(g_l)

        dstage, dx = jax.vmap(lane_bwd)(stage_p, x_saved, g_in)
        g_stage = jax.tree.map(
            lambda a, b: a + jnp.where(
                bwd_valid.reshape((vpp,) + (1,) * (b.ndim - 1)), b, 0
            ).astype(a.dtype), g_stage, dstage)
        # chunk 0 (lane 0, stage 0) feeds the embed backward
        mb_b0 = _mb_at(mbs, m_b[0], M)
        _, embed_vjp = jax.vjp(lambda ep: model.embed_fn(ep, mb_b0),
                               embed_p)
        (dembed,) = embed_vjp(dx[0])
        g_embed = _masked_axpy(g_embed, dembed, bwd_valid[0] & (d == 0))

        # rotate: activations +1 with stage-0 roll; cotangents -1 with
        # the mirrored roll at the LAST stage (chunk (l+1)*pp flows back
        # to chunk l*pp + pp-1)
        cot_recv = send_backward_recv_backward(dx)
        cot_next = jnp.where(d == pp - 1, jnp.roll(cot_recv, -1, axis=0),
                             cot_recv)
        return (rotate_fwd(ys), cot_next, ring, g_stage, g_embed, g_head,
                loss_acc), None

    carry0 = (lanes0, jnp.zeros_like(lanes0),
              jnp.zeros((vpp, R) + state0.shape, state0.dtype),
              _zeros_f32_like(stage_p), _zeros_f32_like(embed_p),
              _zeros_f32_like(head_p), jnp.float32(0))
    (_, _, _, g_stage, g_embed, g_head, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(T))

    loss = lax.psum(loss_acc, ps.PIPE_AXIS) / M
    grads = {
        "stages": jax.tree.map(lambda a: a[:, None], g_stage),
        "embed": lax.psum(g_embed, ps.PIPE_AXIS),
        "head": lax.psum(g_head, ps.PIPE_AXIS),
    }
    return loss, grads


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def get_forward_backward_func() -> Callable[..., Tuple[jax.Array,
                                                       Optional[Pytree]]]:
    """Pick the schedule from the global parallel state (ref:
    ``schedules/__init__.py :: get_forward_backward_func``)."""
    if ps.get_pipeline_model_parallel_world_size() == 1:
        return forward_backward_no_pipelining
    if ps.get_virtual_pipeline_model_parallel_world_size() is not None:
        return forward_backward_pipelining_with_interleaving
    return forward_backward_pipelining_without_interleaving
