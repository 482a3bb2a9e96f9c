"""Stage-to-stage activation/grad exchange over the ``pipe`` mesh axis.

Reference: ``apex/transformer/pipeline_parallel/p2p_communication.py ::
_communicate`` — NCCL ``batch_isend_irecv`` between adjacent pipeline
ranks, with a shape/dtype handshake for ``variable_seq_lengths``.

TPU-native redesign: under single-controller SPMD there are no point-to-
point sockets — the exchange is ONE ``lax.ppermute`` (XLA collective-
permute, which rides a direct ICI hop between mesh-adjacent chips).  A
"send" on stage *i* and the matching "recv" on stage *i+1* are the same
collective, so the reference's eight send/recv entry points collapse into
ring shifts:

- forward direction (activations):   shift **+1** along ``pipe``
- backward direction (gradients):    shift **-1** along ``pipe``

The shape handshake disappears entirely: XLA requires static shapes, so
both sides always agree by construction (``variable_seq_lengths`` is
handled at a higher level by bucketing/padding batches, the standard TPU
approach).

All functions must be called INSIDE ``parallel_state.shard_map`` (or any
mapped region binding the ``pipe`` axis).  They are linear, so JAX's
built-in transpose gives the correct dual (a reversed ppermute) under
``jax.grad`` — no custom_vjp needed.

The wraparound link (last stage -> first stage) is included in the ring;
schedules mask the wrapped value where the reference would simply not
post a recv.  On hardware the extra hop is off the critical path (it
overlaps with the first stage's injection compute).
"""

from typing import Any

import jax
from jax import lax

from apex_tpu.transformer import parallel_state as ps


def _ring(n: int, step: int):
    return [(i, (i + step) % n) for i in range(n)]


def _shift(x: Any, step: int) -> Any:
    """ppermute every leaf of ``x`` by ``step`` stages along ``pipe``."""
    n = lax.axis_size(ps.PIPE_AXIS)
    perm = _ring(n, step)
    return jax.tree.map(lambda a: lax.ppermute(a, ps.PIPE_AXIS, perm), x)


# -- reference-shaped API ----------------------------------------------------
# Each reference send/recv PAIR is one collective here; the lone send_* and
# recv_* names are kept as documented aliases of the combined op so schedule
# code written against the reference API ports mechanically.

def send_forward_recv_forward(output_tensor: Any) -> Any:
    """Send activations to the next stage; return what the previous stage
    sent us (ref: ``send_forward`` + ``recv_forward`` fused)."""
    return _shift(output_tensor, +1)


def send_backward_recv_backward(input_tensor_grad: Any) -> Any:
    """Send grads to the previous stage; return the next stage's grads
    (ref: ``send_backward`` + ``recv_backward`` fused)."""
    return _shift(input_tensor_grad, -1)


def send_forward_recv_backward(output_tensor: Any,
                               input_tensor_grad: Any) -> Any:
    """1F1B steady-state exchange: activations go +1 while grads go -1
    (ref: ``send_forward_recv_backward``). Returns (recv_fwd, recv_bwd)."""
    return _shift(output_tensor, +1), _shift(input_tensor_grad, -1)


def send_backward_recv_forward(input_tensor_grad: Any,
                               output_tensor: Any) -> Any:
    """Mirror of :func:`send_forward_recv_backward`; returns
    (recv_bwd, recv_fwd)."""
    return _shift(input_tensor_grad, -1), _shift(output_tensor, +1)


# Lone send/recv: under SPMD a "send" and its matching "recv" are ONE
# collective, so code ported from the reference that calls send_forward(x)
# and then recv_forward(...) — two ops in the NCCL world — would ppermute
# TWICE here and double-shift activations. Rather than silently alias,
# the lone names fail fast with the correct replacement.

def _one_collective(name: str, repl: str):
    def guard(*_a, **_k):
        raise RuntimeError(
            f"p2p_communication.{name}: under SPMD the send and its "
            f"matching recv are a single collective — call {repl}(x) "
            f"EXACTLY ONCE per exchange (it both sends and returns the "
            f"received value). Calling lone send_*/recv_* pairs as in "
            f"the reference would ppermute twice and double-shift.")
    guard.__name__ = name
    guard.__doc__ = (f"Removed alias; use :func:`{repl}` once per "
                     f"exchange (see module docstring).")
    return guard


send_forward = _one_collective("send_forward", "send_forward_recv_forward")
recv_forward = _one_collective("recv_forward", "send_forward_recv_forward")
send_backward = _one_collective("send_backward",
                                "send_backward_recv_backward")
recv_backward = _one_collective("recv_backward",
                                "send_backward_recv_backward")
