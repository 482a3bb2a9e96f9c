"""TP/SP region mappings — the collective autograd pairs.

Reference: ``apex/transformer/tensor_parallel/mappings.py`` — each mapping
is an ``autograd.Function`` whose forward/backward are a collective and its
dual. Here each is a ``jax.custom_vjp`` built on XLA collectives, to be
called INSIDE ``parallel_state.shard_map`` over the ``model`` axis:

=============================================  ==============  =============
mapping                                         forward         backward
=============================================  ==============  =============
``copy_to_tensor_model_parallel_region``        identity        psum
``reduce_from_tensor_model_parallel_region``    psum            identity
``scatter_to_tensor_model_parallel_region``     split last dim  all-gather
``gather_from_tensor_model_parallel_region``    all-gather      split
``scatter_to_sequence_parallel_region``         split seq dim   all-gather
``gather_from_sequence_parallel_region``        all-gather seq  reduce-scatter
``reduce_scatter_to_sequence_parallel_region``  reduce-scatter  all-gather
=============================================  ==============  =============

The sequence dim is axis 0 (Megatron's (s, b, h) layout is preserved so SP
semantics match the reference line for line).
"""

import functools

import jax
from jax import lax

from apex_tpu.transformer import parallel_state as ps

_AXIS = ps.TENSOR_AXIS


def _tp_size():
    return lax.axis_size(_AXIS)


def _split_along(x, dim):
    """Local chunk of dim for this TP rank (ref: ``_split_along_last_dim``)."""
    size = x.shape[dim] // _tp_size()
    idx = lax.axis_index(_AXIS)
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis=dim)


def _gather_along(x, dim):
    return lax.all_gather(x, _AXIS, axis=dim, tiled=True)


def _reduce_scatter_along(x, dim):
    return lax.psum_scatter(x, _AXIS, scatter_dimension=dim, tiled=True)


# -- copy / reduce (last-dim free) ------------------------------------------

@jax.custom_vjp
def copy_to_tensor_model_parallel_region(x):
    return x

def _copy_fwd(x):
    return x, None

def _copy_bwd(_, g):
    return (lax.psum(g, _AXIS),)

copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


@jax.custom_vjp
def reduce_from_tensor_model_parallel_region(x):
    return lax.psum(x, _AXIS)

def _reduce_fwd(x):
    return lax.psum(x, _AXIS), None

def _reduce_bwd(_, g):
    return (g,)

reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


# -- scatter / gather over the LAST dim (tensor-parallel regions) -----------

@jax.custom_vjp
def scatter_to_tensor_model_parallel_region(x):
    return _split_along(x, -1)

def _scatter_fwd(x):
    return _split_along(x, -1), None

def _scatter_bwd(_, g):
    return (_gather_along(g, -1),)

scatter_to_tensor_model_parallel_region.defvjp(_scatter_fwd, _scatter_bwd)


@jax.custom_vjp
def gather_from_tensor_model_parallel_region(x):
    return _gather_along(x, -1)

def _gather_fwd(x):
    return _gather_along(x, -1), None

def _gather_bwd(_, g):
    return (_split_along(g, -1),)

gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# -- sequence-parallel region mappings ---------------------------------------
# seq dim defaults to axis 0 (Megatron (s, b, h)); models in (b, s, h)
# layout pass seq_dim=1 — the collectives are dim-agnostic.

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def scatter_to_sequence_parallel_region(x, seq_dim: int = 0):
    return _split_along(x, seq_dim)

def _sp_scatter_fwd(x, seq_dim):
    return _split_along(x, seq_dim), None

def _sp_scatter_bwd(seq_dim, _, g):
    return (_gather_along(g, seq_dim),)

scatter_to_sequence_parallel_region.defvjp(_sp_scatter_fwd, _sp_scatter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_sequence_parallel_region(x, to_model_parallel: bool = True,
                                         seq_dim: int = 0):
    return _gather_along(x, seq_dim)

def _sp_gather_fwd(x, to_model_parallel, seq_dim):
    return _gather_along(x, seq_dim), None

def _sp_gather_bwd(to_model_parallel, seq_dim, _, g):
    # entering a TP region: the dual is reduce-scatter (grads from all TP
    # ranks must be summed); leaving to a pure SP consumer: plain split
    if to_model_parallel:
        return (_reduce_scatter_along(g, seq_dim),)
    return (_split_along(g, seq_dim),)

gather_from_sequence_parallel_region.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_scatter_to_sequence_parallel_region(x, seq_dim: int = 0):
    return _reduce_scatter_along(x, seq_dim)

def _sp_rs_fwd(x, seq_dim):
    return _reduce_scatter_along(x, seq_dim), None

def _sp_rs_bwd(seq_dim, _, g):
    return (_gather_along(g, seq_dim),)

reduce_scatter_to_sequence_parallel_region.defvjp(_sp_rs_fwd, _sp_rs_bwd)
