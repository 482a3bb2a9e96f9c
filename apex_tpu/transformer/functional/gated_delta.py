"""Gated DeltaNet (Yang, Kautz, Hatamizadeh, arXiv:2412.06464): the linear
attention layer whose state is one ``(d_k, d_v)`` matrix per head,

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t,

with a scalar decay ``alpha_t`` in (0, 1] and a scalar step ``beta_t`` in
(0, 2) per head and token. Two forms of it, both Pallas kernels, and the
short causal convolution that feeds it:

``gated_delta_chunked`` (prefill): the sequence is cut in chunks of 64. Inside
a chunk the rank-one updates are folded into their WY form (Bischof and Van
Loan), which every chunk does for itself in plain XLA: with ``g`` the running
sum of ``log alpha`` in the chunk, ``M[t, i] = exp(g_t - g_i)`` for ``i <= t``
and ``N`` the strictly lower part of ``M * (beta k) k^T``,

    U = (I + N)^{-1} (beta v) - (I + N)^{-1} (beta exp(g) k) S_0 = W_v - W_k S_0
    O = (exp(g) q) S_0 + (M * q k^T) U
    S_C = exp(g_C) S_0 + (exp(g_C - g) k)^T U.

The kernel ``apex_gdn_chunk_fwd`` walks the chunks in order with ``S`` resident
in VMEM, three products with the state and one inside the chunk per step.
``(I + N)^{-1}`` comes from forward substitution on 16-row blocks merged by
block products: the Neumann product ``(I - N)(I + N^2)...`` is shorter and
loses everything to cancellation when neighbouring keys are alike.

``gated_delta_step`` (decode): one token for every slot. The kernel
``apex_gdn_decode_fwd`` takes the WHOLE stacked state ``[layers, slots, heads,
d_k, d_v]`` and a layer index, reads and writes only that layer's blocks, and
hands the array back through ``input_output_aliases``: under a donated cache
the update is in place, nothing the size of the state is copied per tick.

*Per-channel decay* (Kimi Delta Attention, arXiv:2510.26692): the decay is a
VECTOR over the key channels, ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1}
+ beta_t k_t v_t^T``. Both functions take ``log_decay`` with a trailing
``d_k`` axis for it and run the same two kernel bodies under the names
``apex_kda_chunk_fwd`` / ``apex_kda_decode_fwd`` (one body, two names: the
name says which rule a traced call ran). In a chunk the running sum ``G`` of
``log a`` is a vector too, ``M`` has no common factor, and ``N`` and ``M * q
k^T`` become ``sum_c x_t[c] k_i[c] exp(G_t[c] - G_i[c])``: computed 16 rows at
a time against the block's first row, ``(x * exp(G - G_ref)) (k * exp(G_ref -
G))^T``. Left of the diagonal block both factors are at most 1; inside it the
second is at most ``exp(15 * 5)``, which float32 holds BECAUSE the layer
bounds ``log a`` below by -5 (its ``kda_lower_bound``); the state's decay is
its ROWS scaled, ``Diag(exp(G_C)) S``.

Everything here is float32 with exact products (``Precision.HIGHEST`` on the
MXU, the VPU elsewhere): the state is summed over thousands of tokens, and a
bfloat16 product in it is the error the benchmark's control is refused for.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

CHUNK = 64
# rows inverted by plain forward substitution; larger blocks are merged
_SOLVE_BLOCK = 16
# heads whose chunk the prefill kernel works on in one grid step (their
# products are independent, so the compiler can overlap them)
_CHUNK_HEADS = 5
# heads of one slot the decode kernel holds in VMEM at once
_STEP_HEADS = 10

_HI = lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _column(row):
    """A (1, n) row as an (n, 1) column, on the VPU (inside a kernel)."""
    n = row.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _group(heads: int, most: int) -> int:
    """The largest divisor of ``heads`` that is at most ``most``."""
    return next(h for h in range(min(most, heads), 0, -1) if heads % h == 0)


# ---------------------------------------------------------------------------
# the causal depthwise convolution in front of q, k and v
# ---------------------------------------------------------------------------

def causal_conv(x, weight, length, tail=None):
    """``x`` (s, c), ``weight`` (w, c): ``y_t = sum_j weight[j] x_{t-w+1+j}``
    with zeros before the sequence, or with ``tail`` (w-1, c) there: the
    inputs before ``x`` of a sequence taken a stretch at a time. Returns
    ``(y, tail)``: ``tail`` (w-1, c) holds the inputs at ``length - w + 1 ..
    length - 1`` (zeros, or the ``tail`` given, before the start), what
    :func:`conv_step` or the next stretch needs to go on from ``length``;
    rows of ``x`` at or past ``length`` never reach it."""
    w = weight.shape[0]
    xp = jnp.pad(x, ((w - 1, 0), (0, 0))) if tail is None \
        else jnp.concatenate([tail.astype(x.dtype), x])
    y = sum(weight[j] * lax.dynamic_slice_in_dim(xp, j, x.shape[0], 0)
            for j in range(w))
    return y, lax.dynamic_slice_in_dim(xp, length, w - 1, 0)


def conv_step(x, tail, weight):
    """One position for every slot: ``x`` (b, c) is the new input, ``tail``
    (b, w-1, c) the ones before it. Returns ``(y, tail')``."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], 1)
    y = jnp.einsum("bwc,wc->bc", window.astype(weight.dtype), weight,
                   precision=_HI)
    return y, window[:, 1:]


def ring_of_tail(tail, length):
    """:func:`causal_conv`'s ``tail`` (w-1, c), oldest first, as the ring
    :func:`conv_ring_step` goes on from: the input of position ``t`` in row
    ``t % (w-1)``."""
    w1 = tail.shape[0]
    return jnp.take(tail, (jnp.arange(w1) - length) % w1, axis=0)


def conv_ring_step(x, ring, weight, pos, active):
    """:func:`conv_step` with the tail kept as a ring: ``ring`` (b, w-1, c)
    holds the input of position ``t`` in row ``t % (w-1)``, ``pos`` (b,) is
    the position of ``x``. An active slot's new input takes the oldest row's
    place and no row moves: every element of ``ring'`` is computed from the
    same element of ``ring`` (and ``x``), so the update can be written in
    place over a donated buffer, and run twice, without reading what it
    overwrote. (A tail SHIFTED by a row and written back cannot: at 256 slots
    the compiler rematerialised that update, ran it again from the buffer it
    had already written, and the tail came out shifted twice: my chip run,
    PR 42; ``tests/L0/test_aot_v5e.py`` holds the compiled program to this.)
    Returns ``(y, ring')``."""
    w1 = ring.shape[1]
    at = pos % w1                         # the oldest row: position pos-(w-1)
    # row i holds tap (i - at) % (w-1): the taps rolled by ``at``
    taps = weight[:w1]
    for r in range(1, w1):
        taps = jnp.where((at == r)[:, None, None],
                         jnp.roll(weight[:w1], r, 0), taps)
    y = jnp.sum(taps * ring.astype(weight.dtype), 1) \
        + weight[w1] * x.astype(weight.dtype)
    new = (jnp.arange(w1) == at[:, None]) & active[:, None]
    return y, jnp.where(new[..., None], x[:, None].astype(ring.dtype), ring)


# ---------------------------------------------------------------------------
# prefill: chunks of 64, state carried from chunk to chunk
# ---------------------------------------------------------------------------

def unit_lower_inverse(n):
    """``(I + n)^{-1}`` for ``n`` (..., c, c) strictly lower triangular, ``c``
    a power-of-two multiple of 16 (or at most 16): forward substitution on
    the diagonal blocks of 16 rows, then ``[[A, 0], [C, D]]^{-1} = [[A', 0],
    [-D' C A', D']]`` block by block."""
    c = n.shape[-1]
    b = min(c, _SOLVE_BLOCK)
    if c % b or (c // b) & (c // b - 1):
        raise ValueError(f"chunk {c} is no power-of-two multiple of {b}")
    lead = n.shape[:-2]
    eye = jnp.eye(b, dtype=n.dtype)
    # (..., c/b, b, b): the diagonal blocks
    diag = jnp.stack([n[..., i:i + b, i:i + b] for i in range(0, c, b)], -3)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (b,))]
    for i in range(1, b):
        done = jnp.stack(rows, -2)                       # (..., i, b)
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], done, precision=_HI))
    inv = jnp.stack(rows, -2)                            # (..., c/b, b, b)
    size = b
    while size < c:
        # merge neighbours: blocks 2j (A') and 2j+1 (D') of ``size`` rows
        a, d = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = jnp.stack([n[..., i + size:i + 2 * size, i:i + size]
                         for i in range(0, c, 2 * size)], -3)
        low = -_mm(_mm(d, low), a)
        zero = jnp.zeros_like(a)
        inv = jnp.concatenate([jnp.concatenate([a, zero], -1),
                               jnp.concatenate([low, d], -1)], -2)
        size *= 2
    return inv.reshape(lead + (c, c))


def _chunk_kernel(wv_ref, wk_ref, qg_ref, kt_ref, attn_ref, gl_ref, *refs,
                  per_channel=False):
    """``per_channel``: ``gl_ref`` holds the chunk's decay as a ``(1, d_k)``
    row, one factor a ROW of the state; else as a ``(1, d_v)`` row of one
    number. ``refs``: the outputs ``o_ref, s_ref``, behind the state the walk
    starts from where the call gives one (else it starts from zeros)."""
    *start, o_ref, s_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = start[0][...] if start else jnp.zeros_like(s_ref)

    def dot(a, b):
        return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)

    for h in range(s_ref.shape[0]):
        s = s_ref[h]                                      # (d_k, d_v)
        u = wv_ref[h, 0] - dot(wk_ref[h, 0], s)           # (chunk, d_v)
        o_ref[h, 0] = dot(qg_ref[h, 0], s) + dot(attn_ref[h, 0], u)
        decay = _column(gl_ref[h, 0]) if per_channel else gl_ref[h, 0]
        s_ref[h] = decay * s + dot(kt_ref[h, 0], u)


def _scalar_decay_operands(q, k, v, g, beta):
    """The chunk walk's operands where ``g`` (heads, n, chunk) is the running
    sum of ONE ``log alpha`` a head and token: ``(W_v, W_k, Q exp(g), the
    transposed decayed keys, M * q k^T, exp(g_C) as a (1, d_v) row)``."""
    chunk, dv = q.shape[-2], v.shape[-1]
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    # exp(g_t - g_i) for i <= t: never above 1, and no overflow above the
    # diagonal, where the difference is masked before the exponential
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, g[..., :, None] - g[..., None, :], 0.0)), 0.0)
    kb = k * beta
    strict = at[:, None] > at[None, :]
    inv = unit_lower_inverse(jnp.where(
        strict, decay * _mm(kb, jnp.swapaxes(k, -1, -2)), 0.0))
    eg = jnp.exp(g)[..., None]
    w_v = _mm(inv, v * beta)
    w_k = _mm(inv, kb * eg)
    attn = decay * _mm(q, jnp.swapaxes(k, -1, -2))
    g_last = g[..., -1:]
    k_tail = jnp.swapaxes(k * jnp.exp(g_last - g)[..., None], -1, -2)
    e_last = jnp.broadcast_to(jnp.exp(g_last)[..., None],
                              g.shape[:2] + (1, dv))
    return w_v, w_k, q * eg, k_tail, attn, e_last


def _channel_decay_operands(q, k, v, g, beta):
    """:func:`_scalar_decay_operands` where ``g`` (heads, n, chunk, d_k) is
    the running sum of a ``log a`` per key CHANNEL: ``N`` and ``M * q k^T``
    are ``sum_c x_t[c] k_i[c] exp(g_t[c] - g_i[c])``, made ``_SOLVE_BLOCK``
    rows at a time against the block's first row ``r``, ``(x * exp(g - g_r))
    (k * exp(g_r - g))^T`` over the columns up to the block's last: the first
    factor is at most 1, the second at most 1 left of the diagonal block and
    ``exp(-(_SOLVE_BLOCK - 1) * min log a)`` inside it (``exp(75)`` at the
    layer's bound of -5). Last: ``exp(g_C)`` as a ``(1, d_k)`` row."""
    chunk = q.shape[-2]
    b = min(chunk, _SOLVE_BLOCK)
    kb = k * beta
    n_strips, p_strips = [], []
    for r in range(0, chunk, b):
        ref = g[..., r:r + 1, :]
        rows = jnp.exp(g[..., r:r + b, :] - ref)
        cols = k[..., :r + b, :] * jnp.exp(ref - g[..., :r + b, :])
        both = _mm(jnp.concatenate([kb[..., r:r + b, :] * rows,
                                    q[..., r:r + b, :] * rows], -2),
                   jnp.swapaxes(cols, -1, -2))
        pad = ((0, 0),) * (both.ndim - 1) + ((0, chunk - r - b),)
        n_strips.append(jnp.pad(both[..., :b, :], pad))
        p_strips.append(jnp.pad(both[..., b:, :], pad))
    at = jnp.arange(chunk)
    inv = unit_lower_inverse(jnp.where(
        at[:, None] > at[None, :], jnp.concatenate(n_strips, -2), 0.0))
    attn = jnp.where(at[:, None] >= at[None, :],
                     jnp.concatenate(p_strips, -2), 0.0)
    eg = jnp.exp(g)
    w_v = _mm(inv, v * beta)
    w_k = _mm(inv, kb * eg)
    g_last = g[..., -1:, :]
    k_tail = jnp.swapaxes(k * jnp.exp(g_last - g), -1, -2)
    return w_v, w_k, q * eg, k_tail, attn, jnp.exp(g_last)


def gated_delta_chunked(q, k, v, log_decay, beta, *, chunk=CHUNK,
                        interpret=None, initial_state=None):
    """The recurrence over a whole sequence from a zero state, or from
    ``initial_state`` (heads, d_k, d_v) float32: a sequence taken a stretch
    at a time.

    ``q``, ``k`` (heads, s, d_k) and ``v`` (heads, s, d_v) float32, ``q``
    already scaled and both already normalised; ``log_decay`` (heads, s) is
    ``log alpha_t <= 0`` and ``beta`` (heads, s) the step. ``log_decay``
    (heads, s, d_k) is a decay per key channel (the module's docstring),
    nowhere below ``-88 / (_SOLVE_BLOCK - 1)``, and the call is
    ``apex_kda_chunk_fwd``. ``s`` is a multiple of ``chunk``. A position with
    ``log_decay = 0`` and ``beta = 0`` leaves the state as it found it: that
    is how the caller pads. Returns ``(o (heads, s, d_v), S_s (heads, d_k,
    d_v))``, float32.
    """
    heads, s, dk = q.shape
    dv = v.shape[-1]
    if s % chunk:
        raise ValueError(f"sequence {s} is no multiple of the chunk {chunk}")
    per_channel = log_decay.ndim == 3
    if per_channel and log_decay.shape != (heads, s, dk):
        raise ValueError(f"a decay per key channel is (heads, s, d_k), got "
                         f"{log_decay.shape} beside q {q.shape}")
    n = s // chunk
    hb = _group(heads, _CHUNK_HEADS)
    f32 = jnp.float32

    def cut(x):
        return x.astype(f32).reshape(heads, n, chunk, *x.shape[2:])

    q, k, v, beta = cut(q), cut(k), cut(v), cut(beta)[..., None]
    # the running sum of log alpha in each chunk: (heads, n, chunk[, d_k])
    if per_channel:
        # as a product with a triangle of ones at full precision: inside a
        # larger program the TPU compiler runs ``jnp.cumsum`` (a
        # ``reduce_window``) on the matrix unit at reduced precision
        # (``functional.ssd``; PERF.md, section 6, PR 33), and these sums
        # reach -320 and go into exponentials
        at = jnp.arange(chunk)
        g = jnp.einsum("ti,hnic->hntc",
                       (at[:, None] >= at[None, :]).astype(f32),
                       cut(log_decay), precision=_HI)
    else:
        g = jnp.cumsum(cut(log_decay), 2)
    operands = (_channel_decay_operands if per_channel
                else _scalar_decay_operands)(q, k, v, g, beta)

    def rows(width):
        return pl.BlockSpec((hb, 1, chunk, width),
                            lambda i, c: (i, c, 0, 0),
                            memory_space=pltpu.VMEM)

    call = dict(
        grid=(heads // hb, n),
        in_specs=[rows(dv), rows(dk), rows(dk),
                  pl.BlockSpec((hb, 1, dk, chunk), lambda i, c: (i, c, 0, 0),
                               memory_space=pltpu.VMEM),
                  rows(chunk),
                  pl.BlockSpec((hb, 1, 1, dk if per_channel else dv),
                               lambda i, c: (i, c, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[rows(dv),
                   pl.BlockSpec((hb, dk, dv), lambda i, c: (i, 0, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((heads, n, chunk, dv), f32),
                   jax.ShapeDtypeStruct((heads, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(interpret))
    if initial_state is not None:
        call["in_specs"].append(call["out_specs"][1])
        operands += (initial_state.astype(f32),)
    # one body, two names: the name says which rule a traced call ran
    if per_channel:
        with jax.named_scope("apex_kda_chunk_fwd"):
            o, state = pl.pallas_call(
                functools.partial(_chunk_kernel, per_channel=True),
                name="apex_kda_chunk_fwd", **call)(*operands)
    else:
        with jax.named_scope("apex_gdn_chunk_fwd"):
            o, state = pl.pallas_call(_chunk_kernel,
                                      name="apex_gdn_chunk_fwd",
                                      **call)(*operands)
    return o.reshape(heads, s, dv), state


# ---------------------------------------------------------------------------
# decode: one token for every slot, the stacked state updated in place
# ---------------------------------------------------------------------------

def _step_kernel(layer_ref, active_ref, qk_ref, va_ref, s_in, o_ref, s_out, *,
                 per_channel=False):
    """``per_channel``: the decay is a fourth row of ``qk_ref``, one factor a
    key channel (a ROW of the state); else the second row of ``va_ref``, one
    number broadcast over ``d_v``."""
    del layer_ref                       # read by the index maps
    heads, dk = qk_ref.shape[2], qk_ref.shape[4]
    eye = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    def column(row):
        """A (1, d_k) row as a (d_k, 1) column, on the VPU."""
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        def head(h, _):
            qk = qk_ref[0, 0, h]                    # rows q, k, beta * k
            va = va_ref[0, 0, h]                    # rows beta * v, alpha
            if per_channel:                         # Diag(a) S
                s = column(qk[3:4]) * s_in[0, 0, h]
            else:
                s = va[1:2] * s_in[0, 0, h]         # alpha S
            r = va[0:1] - jnp.sum(column(qk[2:3]) * s, axis=0, keepdims=True)
            s = s + column(qk[1:2]) * r
            s_out[0, 0, h] = s
            o_ref[0, 0, pl.ds(h, 1)] = jnp.sum(column(qk[0:1]) * s, axis=0,
                                               keepdims=True)
            return 0

        lax.fori_loop(0, heads, head, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_in[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def gated_delta_step(q, k, v, log_decay, beta, state, layer, active, *,
                     interpret=None):
    """One step of the recurrence for every slot, on layer ``layer`` of the
    stacked state.

    ``q``, ``k`` (b, heads, d_k) and ``v`` (b, heads, d_v) as for
    :func:`gated_delta_chunked`; ``log_decay``, ``beta`` (b, heads), or
    ``log_decay`` (b, heads, d_k) for a decay per key channel (the call is
    then ``apex_kda_decode_fwd``);
    ``state`` (layers, b, heads, d_k, d_v) float32, the whole array;
    ``layer`` a scalar int32 (traced under the layer scan); ``active`` (b,)
    bool: a slot that is not active keeps its state and gives zeros. Returns
    ``(o (b, heads, d_v) float32, state')`` where ``state'`` aliases
    ``state``: only layer ``layer`` of the active slots differs.
    """
    b, heads, dk = q.shape
    dv = v.shape[-1]
    if state.shape[1:] != (b, heads, dk, dv) or state.dtype != jnp.float32:
        raise ValueError(f"state {state.shape} {state.dtype} does not hold "
                         f"float32 [layers, {b}, {heads}, {dk}, {dv}]")
    hb = _group(heads, _STEP_HEADS)
    groups = heads // hb
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    beta = beta.astype(f32)[..., None]
    per_channel = log_decay.ndim == 3
    if per_channel and log_decay.shape != (b, heads, dk):
        raise ValueError(f"a decay per key channel is (b, heads, d_k), got "
                         f"{log_decay.shape} beside q {q.shape}")

    def grouped(rows, width):
        return jnp.stack(rows, 2).reshape(b, groups, hb, len(rows), width)

    if per_channel:
        qk = grouped([q, k, beta * k, jnp.exp(log_decay.astype(f32))], dk)
        va = grouped([beta * v], dv)
    else:
        alpha = jnp.broadcast_to(jnp.exp(log_decay.astype(f32))[..., None],
                                 (b, heads, dv))
        qk = grouped([q, k, beta * k], dk)
        va = grouped([beta * v, alpha], dv)

    def rows(n, width):
        return pl.BlockSpec((1, 1, hb, n, width),
                            lambda i, j, *_: (i, j, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    block = pl.BlockSpec((1, 1, hb, dk, dv),
                         lambda i, j, layer, *_: (layer[0], i, j, 0, 0),
                         memory_space=pltpu.VMEM)
    call = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, groups),
            in_specs=[rows(qk.shape[3], dk), rows(va.shape[3], dv), block],
            out_specs=[pl.BlockSpec((1, 1, hb, dv),
                                    lambda i, j, *_: (i, j, 0, 0),
                                    memory_space=pltpu.VMEM), block]),
        out_shape=[jax.ShapeDtypeStruct((b, groups, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the two prefetched scalars: the state is the
        # fifth, and comes back as the second result
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(interpret))
    operands = (jnp.reshape(layer, (1,)).astype(jnp.int32),
                active.astype(jnp.int32), qk, va, state)
    # one body, two names: the name says which rule a traced call ran
    if per_channel:
        with jax.named_scope("apex_kda_decode_fwd"):
            o, state = pl.pallas_call(
                functools.partial(_step_kernel, per_channel=True),
                name="apex_kda_decode_fwd", **call)(*operands)
    else:
        with jax.named_scope("apex_gdn_decode_fwd"):
            o, state = pl.pallas_call(_step_kernel,
                                      name="apex_gdn_decode_fwd",
                                      **call)(*operands)
    return o.reshape(b, heads, dv), state
