"""The Mamba-2 state-space recurrence (Dao and Gu, arXiv:2405.21060): ``H``
heads of ``P`` channels, each with a ``(P, N)`` state, a scalar decay per head
and token, and ``B``, ``C`` rows of ``N`` shared by the heads of a group,

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T,        y_t = S_t C_t,

with ``A < 0`` one scalar a head and ``delta_t > 0`` one scalar a head and
token. (The skip ``D x_t`` is the caller's: it needs no state.) Two forms:

``ssd_chunked`` (prefill), plain XLA: the sequence is cut in chunks of 128.
With ``g`` the running sum of ``delta A`` inside a chunk (a product with a
triangle of ones, at full precision), a chunk's output is
the part its own tokens give, ``(C B^T * exp(g_t - g_i) delta_i, i <= t) x``,
plus what the state it found gives, ``exp(g_t) C_t S_0``; the chunk leaves
``exp(g_last) S_0 + sum_i exp(g_last - g_i) delta_i x_i B_i^T``. The chunks'
states are chained by a scan over the (few) chunks. A position with ``delta =
0`` decays nothing and writes nothing: that is how the caller pads.

``ssd_step`` (decode): one token for every slot. The kernel
``apex_ssd_decode_fwd`` takes the WHOLE stacked state ``[layers, slots, H, P,
N]`` and a layer index, reads and writes only that layer's blocks, and hands
the array back through ``input_output_aliases``: under a donated cache the
update is in place, nothing the size of the state is copied per tick. ``N`` is
the lane axis (128 at the published widths: whole tiles, no padding); the
per-head scalars and the ``x`` rows come in with the heads on the lanes, so
that a head's ``(P, 1)`` column is one lane of a tile.

Everything here is float32 with exact products: the state is summed over
thousands of tokens, and a bfloat16 product in it is the error the
benchmark's control is refused for.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

CHUNK = 128
# heads of one slot whose state the decode kernel holds in VMEM at once
# (64 x (64, 128) float32 = 2 MiB a block, in and out double-buffered)
_STEP_HEADS = 64

_HI = lax.Precision.HIGHEST


def _group(heads: int, most: int, multiple_of: int) -> int:
    """The largest divisor of ``heads`` that is at most ``most`` and holds
    whole groups of ``multiple_of`` heads."""
    return next(h for h in range(min(most, heads), 0, -1)
                if heads % h == 0 and h % multiple_of == 0)


# ---------------------------------------------------------------------------
# prefill: chunks of 128, the chunks' states chained by a scan
# ---------------------------------------------------------------------------

def ssd_chunked(x, delta, a, b, c, *, chunk=CHUNK):
    """The recurrence over a whole sequence from a zero state.

    ``x`` (s, H, P), ``delta`` (s, H) (after its softplus; 0 at a padded
    position), ``a`` (H,) negative, ``b`` and ``c`` (s, G, N) with ``H`` a
    multiple of ``G``: head ``h`` reads group ``h // (H / G)``. ``s`` is a
    multiple of ``chunk``. Returns ``(y (s, H, P), S_s (H, P, N))``, float32.
    """
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    if s % chunk or heads % groups:
        raise ValueError(f"sequence {s} is no multiple of the chunk {chunk}, "
                         f"or {heads} heads no multiple of {groups} groups")
    nc, per = s // chunk, heads // groups
    f32 = jnp.float32
    x = x.astype(f32).reshape(nc, chunk, groups, per, p)
    delta = delta.astype(f32).reshape(nc, chunk, groups, per)
    b = b.astype(f32).reshape(nc, chunk, groups, n)
    c = c.astype(f32).reshape(nc, chunk, groups, n)
    at = jnp.arange(chunk)
    upto = at[:, None] >= at[None, :]
    # the running sum as a product with a triangle of ones at full precision.
    # ``jnp.cumsum`` is a ``reduce_window``, which the TPU compiler fused here
    # into a matrix-unit operation (``kind=kOutput``) at reduced precision,
    # and ``g`` goes into an exponential: on the chip the prompt path's
    # routers agreed with the float32 reference's at 57-61% of the (token,
    # layer) pairs with it and at 97-99.9% without (PERF.md, section 6, PR 33;
    # alone in a program the same ``cumsum`` is exact, and so it is on the CPU)
    g = jnp.einsum("ti,kigh->ktgh", upto.astype(f32),
                   delta * a.astype(f32).reshape(groups, per), precision=_HI)
    lower = upto[None, :, :, None, None]
    # exp(g_t - g_i) for i <= t: never above 1, and no overflow above the
    # diagonal, where the difference is masked before the exponential
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, g[:, :, None] - g[:, None, :], 0.0)), 0.0)  # (nc, t, i, G, per)
    cb = jnp.einsum("ktgn,kign->ktig", c, b, precision=_HI)
    xd = x * delta[..., None]
    y = jnp.einsum("ktigh,kighp->ktghp", cb[..., None] * decay, xd,
                   precision=_HI)
    # what each chunk adds to the state, decayed to the chunk's end
    g_last = g[:, -1]                                      # (nc, G, per)
    tail = jnp.exp(g_last[:, None] - g)                    # (nc, chunk, G, per)
    own = jnp.einsum("kighp,kign->kghpn", xd * tail[..., None], b,
                     precision=_HI)

    def chain(state, chunk_of):
        e_last, own = chunk_of
        return e_last[..., None, None] * state + own, state

    state, found = lax.scan(chain, jnp.zeros(own.shape[1:], f32),
                            (jnp.exp(g_last), own))
    y = y + jnp.einsum("ktgn,kghpn->ktghp", c, found,
                       precision=_HI) * jnp.exp(g)[..., None]
    return y.reshape(s, heads, p), state.reshape(heads, p, n)


# ---------------------------------------------------------------------------
# decode: one token for every slot, the stacked state updated in place
# ---------------------------------------------------------------------------

def _step_kernel(layer_ref, active_ref, xd_ref, decay_ref, b_ref, c_ref, s_in,
                 o_ref, s_out):
    del layer_ref                       # read by the index maps
    hb = s_in.shape[2]
    per = hb // b_ref.shape[2]          # heads that share one B / C row
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        xd = xd_ref[0, 0]               # (P, hb): head h is lane h
        decay = decay_ref[0, 0]         # (P, hb): one value down a lane
        lane = lax.broadcasted_iota(jnp.int32, xd.shape, 1)
        out = jnp.zeros(xd.shape, jnp.float32)
        for h in range(hb):
            b_row = b_ref[0, 0, h // per:h // per + 1]      # (1, N)
            c_row = c_ref[0, 0, h // per:h // per + 1]
            s = decay[:, h:h + 1] * s_in[0, 0, h] + xd[:, h:h + 1] * b_row
            s_out[0, 0, h] = s
            out = jnp.where(lane == h,
                            jnp.sum(s * c_row, axis=1, keepdims=True), out)
        o_ref[0, 0] = out

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_in[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def ssd_step(x, delta, a, b, c, state, layer, active, *, interpret=None):
    """One step of the recurrence for every slot, on layer ``layer`` of the
    stacked state.

    ``x`` (slots, H, P), ``delta`` (slots, H), ``a`` (H,), ``b`` and ``c``
    (slots, G, N) as for :func:`ssd_chunked`; ``state`` (layers, slots, H, P,
    N) float32, the whole array; ``layer`` a scalar int32 (traced under the
    layer scan); ``active`` (slots,) bool: a slot that is not active keeps its
    state and gives zeros. Returns ``(y (slots, H, P) float32, state')`` where
    ``state'`` aliases ``state``: only layer ``layer`` of the active slots
    differs.
    """
    slots, heads, p = x.shape
    groups, n = b.shape[1:]
    if state.shape[1:] != (slots, heads, p, n) or state.dtype != jnp.float32 \
            or heads % groups:
        raise ValueError(f"state {state.shape} {state.dtype} does not hold "
                         f"float32 [layers, {slots}, {heads}, {p}, {n}] for "
                         f"{groups} groups")
    hb = _group(heads, _STEP_HEADS, heads // groups)
    blocks = heads // hb
    f32 = jnp.float32
    delta = delta.astype(f32)

    def lanes(t):
        """(slots, H, P) -> (slots, blocks, P, hb): a block's heads on the
        lanes."""
        return t.reshape(slots, blocks, hb, p).transpose(0, 1, 3, 2)

    xd = lanes(x.astype(f32) * delta[..., None])
    decay = lanes(jnp.broadcast_to(
        jnp.exp(delta * a.astype(f32))[..., None], (slots, heads, p)))

    def rows(t):
        return t.astype(f32).reshape(slots, blocks, groups // blocks, n)

    def at_slot(*shape):
        return pl.BlockSpec((1, 1) + shape, lambda i, j, *_: (i, j, 0, 0),
                            memory_space=pltpu.VMEM)

    block = pl.BlockSpec((1, 1, hb, p, n),
                         lambda i, j, layer, *_: (layer[0], i, j, 0, 0),
                         memory_space=pltpu.VMEM)
    with jax.named_scope("apex_ssd_decode_fwd"):
        y, state = pl.pallas_call(
            _step_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(slots, blocks),
                in_specs=[at_slot(p, hb), at_slot(p, hb),
                          at_slot(groups // blocks, n),
                          at_slot(groups // blocks, n), block],
                out_specs=[at_slot(p, hb), block]),
            out_shape=[jax.ShapeDtypeStruct((slots, blocks, p, hb), f32),
                       jax.ShapeDtypeStruct(state.shape, f32)],
            # operands count the two prefetched scalars: the state is the
            # seventh, and comes back as the second result
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=pallas_interpret(interpret),
            name="apex_ssd_decode_fwd",
        )(jnp.reshape(layer, (1,)).astype(jnp.int32),
          active.astype(jnp.int32), xd, decay, rows(b), rows(c), state)
    return y.transpose(0, 1, 3, 2).reshape(slots, heads, p), state
