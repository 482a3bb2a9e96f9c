"""A sparse expert layer's four steps, for a chip that HOLDS some of the
experts (expert parallelism seen from one chip, without its exchange): the
router scores every expert of the model, the chip computes what its own
experts give for the tokens routed to them, and what the absent experts would
add is simply not there.

``route``: sigmoid scores in float32, the ``k`` experts with the largest
``score + bias`` (the bias moves the choice and not the weight; ties go to the
lower index, ``lax.top_k``'s rule), optionally among the best groups of
experts only, weights ``scale * s / (sum of the k chosen + 1e-20)``.

``dispatch``: the ``rows * k`` assignments sorted by expert, stably, those
that fall on an expert not held here (or on a row that is padding) last. Every
size is static; no token is dropped and there is no capacity: a group is as
long as the router made it.

``grouped_matmul``: ``rows sorted by group @ that group's matrix``, the Pallas
kernel ``apex_moe_gmm_fwd``. The grid walks (row tile, group) pairs in order;
the pairs and the groups' offsets are scalar-prefetched, so a group with no
row is never visited and its matrix never leaves HBM, and a group's matrix is
fetched once per column tile however many row tiles it spans (consecutive
visits of one group name the same block). Rows past the last group are never
written: ``combine`` masks them.

``combine``: each row's weighted sum over its assignments that were computed
here.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.platform import pallas_interpret

# rows of one visit (two float32 sublane tiles). Groups are a handful of rows
# long when every slot decodes one token (5.5 at the published sizes), and
# the product is bound by reading the matrices, not by the MXU's fill
_ROW_TILE = 16
# a matrix's column tile is at most this many bytes in VMEM (two resident)
_RHS_TILE_BYTES = 3 << 20


def route(logits, bias, top_k: int, scale: float, n_group: int = 1,
          topk_group: int = 1):
    """``logits`` (rows, experts) float32, ``bias`` (experts,). Returns
    ``(experts (rows, k) int32, weights (rows, k) float32)``.

    With ``n_group`` > 1 the choice is limited to groups (the experts of one
    node, in the deployment the rule was made for): the experts stand in
    ``n_group`` groups of equal size, side by side; a group's score is the
    sum of its two largest ``score + bias``; only the ``topk_group`` best
    groups (ties to the lower index) stay eligible, and the ``k`` largest
    ``score + bias`` are taken inside them. One group is no limit and adds
    no operation."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        rows, experts = choice.shape
        if experts % n_group or not 0 < topk_group <= n_group \
                or topk_group * (experts // n_group) < top_k:
            raise ValueError(
                f"{experts} experts in {n_group} groups of which "
                f"{topk_group} stay cannot give {top_k} a token")
        grouped = choice.reshape(rows, n_group, experts // n_group)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], -1)
        _, kept = lax.top_k(group_score, topk_group)
        eligible = jnp.zeros((rows, n_group), bool).at[
            jnp.arange(rows)[:, None], kept].set(True)
        choice = jnp.where(eligible[:, :, None], grouped,
                           -jnp.inf).reshape(rows, experts)
    _, chosen = lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen.astype(jnp.int32), scale * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)


class Dispatch(NamedTuple):
    token: jax.Array        # (rows * k,) the row each sorted assignment reads
    weight: jax.Array       # (rows * k,) its weight, 0 where not computed
    here: jax.Array         # (rows * k,) bool: falls on a held expert
    sizes: jax.Array        # (held,) int32 assignments per held expert


def dispatch(experts, weights, expert_offset: int, experts_held: int,
             real=None) -> Dispatch:
    """Sort the assignments by held expert. ``experts``, ``weights`` (rows,
    k); the chip holds experts ``expert_offset ..  expert_offset +
    experts_held - 1``; ``real`` (rows,) bool marks the rows that are tokens
    (padding is routed nowhere)."""
    rows, k = experts.shape
    local = experts.reshape(-1) - expert_offset
    here = (local >= 0) & (local < experts_held)
    if real is not None:
        here &= jnp.repeat(real.astype(bool), k)
    key = jnp.where(here, local, experts_held)
    order = jnp.argsort(key, stable=True)
    here = here[order]
    sizes = jnp.zeros((experts_held + 1,), jnp.int32).at[key].add(1)
    return Dispatch(token=(order // k).astype(jnp.int32),
                    weight=jnp.where(here, weights.reshape(-1)[order], 0.0),
                    here=here, sizes=sizes[:experts_held])


def combine(out, d: Dispatch, rows: int):
    """``out`` (rows * k, width), the sorted assignments' results ->
    (rows, width) float32: each row's weighted sum."""
    out = jnp.where(d.here[:, None],
                    out.astype(jnp.float32) * d.weight[:, None], 0.0)
    return jnp.zeros((rows, out.shape[1]), jnp.float32).at[d.token].add(out)


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

def _visits(sizes, m: int, tm: int):
    """The (group, row tile) pairs to visit, in order, as arrays of the
    static length ``tiles + groups - 1`` (no more can exist), and how many
    of them are real. The entries past the real ones repeat the last real
    pair, so that their grid steps fetch nothing."""
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(tiles)
    n = visit_end[-1]
    most = -(-m // tm) + groups - 1
    at = jnp.minimum(jnp.arange(most, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_end, at, side="right"),
                        groups - 1).astype(jnp.int32)
    tile = first[group] + at - (visit_end - tiles)[group]
    tile = jnp.where(n > 0, tile, 0)
    offsets = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return group, tile.astype(jnp.int32), offsets, n.astype(jnp.int32)[None]


def _gmm_kernel(group_ref, tile_ref, offsets_ref, n_ref, *refs, activation):
    # after the four scalars every call has: the matrices' own indices where
    # ``rhs`` holds more than this call's groups, then rows, matrices, result
    lhs_ref, rhs_ref, out_ref = refs[-3:]
    v = pl.program_id(1)

    @pl.when(v < n_ref[0])
    def _():
        tm = lhs_ref.shape[0]
        g, t = group_ref[v], tile_ref[v]
        row = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        lhs, rhs = lhs_ref[...], rhs_ref[0]
        if lhs.dtype == rhs.dtype:
            acc = jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)
        else:       # float32 rows into a bfloat16 matrix: hi + lo, two passes
            # (inside a kernel nothing folds the round trip away, and Mosaic
            # has no reduce_precision: models.nemotron_h._two_terms)
            hi = lhs.astype(rhs.dtype)
            lo = (lhs - hi.astype(lhs.dtype)).astype(rhs.dtype)
            acc = jnp.dot(hi, rhs, preferred_element_type=jnp.float32) \
                + jnp.dot(lo, rhs, preferred_element_type=jnp.float32)
        if activation == "relu2":
            acc = jnp.square(jnp.maximum(acc, 0.0))
        # rows of this tile that an earlier visit (another group) wrote
        # stand in the block still; the tile's first visit clears the rest
        before = jnp.logical_and(v > 0, tile_ref[jnp.maximum(v - 1, 0)] == t)
        kept = jnp.where(before, out_ref[...].astype(jnp.float32), 0.0)
        out_ref[...] = jnp.where(mine, acc, kept).astype(out_ref.dtype)


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest divisor of ``n`` in whole 128-lane tiles whose ``(k, tile)``
    block stays under ``_RHS_TILE_BYTES``, or under two lane tiles' worth
    where ``k`` is so long that this is more (hidden 7168 in bfloat16: 3.7
    MB, so that a tile is 256 columns and a matrix's grid steps half as
    many); ``n`` itself when it is small or no multiple of 128."""
    most = max(_RHS_TILE_BYTES, k * 256 * itemsize)
    if n % 128 or k * n * itemsize <= most:
        return n
    fits = [t for t in range(128, n, 128)
            if n % t == 0 and k * t * itemsize <= most]
    return max(fits) if fits else 128


def grouped_matmul(lhs, rhs, sizes, *, activation=None, out_dtype=None,
                   first_group=None, interpret=None):
    """``lhs`` (m, k), its rows sorted by group: the first ``sizes[0]`` rows
    belong to group 0, the next ``sizes[1]`` to group 1, ...; ``rhs``
    (groups, k, n); ``sizes`` (groups,) int32 with ``sum(sizes) <= m``.
    Returns (m, n): row ``i`` of group ``g`` is ``lhs[i] @ rhs[g]`` (after
    ``activation``: ``"relu2"`` squares the positive part), summed in float32;
    rows past the last group hold nothing defined. Float32 rows into a
    bfloat16 ``rhs`` go in as two bfloat16 terms, ``hi + lo`` (two MXU passes
    over a matrix fetched once: the product is bound by reading the matrices,
    and the router downstream is why: ``models.nemotron_h._dense``); rows
    already in ``rhs``'s dtype go in as they are.

    ``first_group`` (a scalar int32, traced or not): ``rhs`` holds MORE
    matrices than this call's groups, ``(all groups, k, n)``, and group ``g``
    multiplies by ``rhs[first_group + g]``. That is how a layer under
    ``lax.scan`` reads its experts out of the stacked weights of all layers
    in place: a layer's slice of them, handed to a kernel, is a copy of every
    expert's matrix, hit or not, each step."""
    m, k = lhs.shape
    all_groups, k2, n = rhs.shape
    groups = all_groups if first_group is None else sizes.shape[0]
    if k != k2 or sizes.shape != (groups,) or groups > all_groups:
        raise ValueError(f"lhs {lhs.shape}, rhs {rhs.shape}, sizes "
                         f"{sizes.shape} do not fit")
    if activation not in (None, "relu2"):
        raise ValueError(f"activation {activation!r}")
    out_dtype = out_dtype or jnp.float32
    tm = _ROW_TILE
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tn = _column_tile(k, n, rhs.dtype.itemsize)
    group, tile, offsets, real = _visits(sizes.astype(jnp.int32), m + pad, tm)
    scalars = (group, tile, offsets, real)
    if first_group is not None:
        scalars += (group + jnp.asarray(first_group, jnp.int32),)
    matrix = len(scalars) - 1 if first_group is not None else 0
    with jax.named_scope("apex_moe_gmm_fwd"):
        out = pl.pallas_call(
            functools.partial(_gmm_kernel, activation=activation),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(n // tn, group.shape[0]),
                in_specs=[
                    pl.BlockSpec((tm, k), lambda j, v, g, t, *_: (t[v], 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, k, tn),
                                 lambda j, v, *s: (s[matrix][v], 0, j),
                                 memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, v, g, t, *_: (t[v], j),
                    memory_space=pltpu.VMEM)),
            out_shape=jax.ShapeDtypeStruct((m + pad, n), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=pallas_interpret(interpret),
            name="apex_moe_gmm_fwd",
        )(*scalars, lhs, rhs)
    return out[:m] if pad else out
