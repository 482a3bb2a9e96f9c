"""Model- and data-parallel state over a ``jax.sharding.Mesh``.

TPU-native equivalent of the reference's global process-group registry
(ref: ``apex/transformer/parallel_state.py :: initialize_model_parallel``).
Where the reference builds NCCL process groups (DP / TP / PP / embedding)
with ``torch.distributed.new_group``, we build ONE device mesh with named
axes and treat each axis as the "group":

- ``data``    — data parallelism (gradient psum rides this axis)
- ``pipe``    — pipeline stages (ppermute of activations rides this axis)
- ``context`` — context/sequence-block parallelism for ring attention
  (not present in the reference — see SURVEY.md §2c — but first-class here)
- ``model``   — tensor parallelism (Megatron column/row sharding). The
  Megatron-style *sequence parallel* region also lives on this axis, exactly
  as in the reference (``sequence_parallel_enabled`` shards activations over
  the TP group).

Axis order is chosen so that ``model`` is innermost: adjacent device ids sit
on the same ICI link on a real pod slice, so the per-layer TP collectives
(the hottest comm in the stack, ref ``apex/transformer/tensor_parallel/
mappings.py``) ride ICI, while ``data``/``pipe`` traffic may cross DCN on
multi-slice topologies.

Rank accessors work both on the host (returning the static value for a
single-controller program: 0) and inside ``shard_map``/``jit`` where they
return the traced ``lax.axis_index``. "Groups" are just axis names; every
collective in this package takes the axis name from here.
"""

from typing import Optional, Sequence

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh

# Canonical axis names. Other modules must use these constants rather than
# string literals so a future re-ordering stays local to this file.
DATA_AXIS = "data"
PIPE_AXIS = "pipe"
CONTEXT_AXIS = "context"
TENSOR_AXIS = "model"

MESH_AXIS_NAMES = (DATA_AXIS, PIPE_AXIS, CONTEXT_AXIS, TENSOR_AXIS)

_MESH: Optional[Mesh] = None
# Virtual pipeline (interleaved 1F1B) bookkeeping, mirroring the reference's
# module-level globals.
_VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE: Optional[int] = None
_VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK: Optional[int] = None
_PIPELINE_MODEL_PARALLEL_SPLIT_RANK: Optional[int] = None


class ParallelStateError(RuntimeError):
    pass


def initialize_model_parallel(
    tensor_model_parallel_size_: int = 1,
    pipeline_model_parallel_size_: int = 1,
    virtual_pipeline_model_parallel_size_: Optional[int] = None,
    pipeline_model_parallel_split_rank_: Optional[int] = None,
    *,
    context_parallel_size_: int = 1,
    data_parallel_size_: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build and register the global mesh.

    Signature mirrors the reference (``parallel_state.py ::
    initialize_model_parallel``); data-parallel size is inferred as
    ``world // (tp * pp * cp)``. ``data_parallel_size_`` is a validation
    hook (used by ``partition.make_mesh``): when given, the inferred dp
    must equal it. Returns the mesh (also installed globally).
    """
    global _MESH
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    global _PIPELINE_MODEL_PARALLEL_SPLIT_RANK

    if devices is None:
        devices = jax.devices()
    world = len(devices)
    tp = int(tensor_model_parallel_size_)
    pp = int(pipeline_model_parallel_size_)
    cp = int(context_parallel_size_)
    denom = tp * pp * cp
    if denom <= 0 or world % denom != 0:
        raise ParallelStateError(
            f"world size {world} not divisible by tp*pp*cp = {tp}*{pp}*{cp}"
        )
    dp = world // denom
    if data_parallel_size_ is not None and dp != int(data_parallel_size_):
        raise ParallelStateError(
            f"requested data_parallel_size {data_parallel_size_} but world "
            f"{world} with tp*pp*cp = {tp}*{pp}*{cp} gives dp = {dp}"
        )
    if virtual_pipeline_model_parallel_size_ is not None and pp < 2:
        raise ParallelStateError(
            "virtual pipeline parallelism requires pipeline_model_parallel_size >= 2"
        )

    mesh_devices = np.asarray(devices, dtype=object).reshape(dp, pp, cp, tp)
    _MESH = Mesh(mesh_devices, MESH_AXIS_NAMES)
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = (
        virtual_pipeline_model_parallel_size_
    )
    # Reset (not leak) the virtual rank across re-initializations, matching
    # the reference which sets it to 0 whenever a virtual size is given.
    set_virtual_pipeline_model_parallel_rank(
        0 if virtual_pipeline_model_parallel_size_ is not None else None
    )
    _PIPELINE_MODEL_PARALLEL_SPLIT_RANK = pipeline_model_parallel_split_rank_
    return _MESH


def model_parallel_is_initialized() -> bool:
    return _MESH is not None


def destroy_model_parallel() -> None:
    """Forget the global mesh (ref: ``destroy_model_parallel``)."""
    global _MESH, _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    global _PIPELINE_MODEL_PARALLEL_SPLIT_RANK
    _MESH = None
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE = None
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = None
    _PIPELINE_MODEL_PARALLEL_SPLIT_RANK = None


def get_mesh() -> Mesh:
    if _MESH is None:
        # Lazy default: a pure data-parallel mesh over all devices, so
        # single-chip flows work without an explicit initialize call.
        initialize_model_parallel()
    return _MESH


def shard_map(f, *, mesh: Optional[Mesh] = None, in_specs, out_specs,
              **kwargs):
    """``jax.shard_map`` over the global mesh with ``check_vma=False``.

    Two reasons this wrapper exists (use it for every mapped region in
    this package):

    - Pallas kernels in interpreter mode (the CPU test rig) reject mixed
      varying/unvarying operands under ``check_vma=True`` (JAX's own error
      suggests disabling it).
    - ``check_vma=False`` restores the classic semantics where ``jax.grad``
      inside the body yields LOCAL gradients (no implicit cross-axis psum
      for replicated params) — the torch model the reference's DDP and TP
      layers are written against; collectives stay explicit.
    """
    kwargs.setdefault("check_vma", False)
    return jax.shard_map(f, mesh=mesh or get_mesh(), in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


# ---------------------------------------------------------------------------
# "Groups" — axis names.
# ---------------------------------------------------------------------------

def get_tensor_model_parallel_group() -> str:
    return TENSOR_AXIS


def get_pipeline_model_parallel_group() -> str:
    return PIPE_AXIS


def get_data_parallel_group() -> str:
    return DATA_AXIS


def get_context_parallel_group() -> str:
    return CONTEXT_AXIS


def get_embedding_group() -> str:
    # The reference builds a dedicated group of {first, last} pipeline stage
    # for embedding-weight allreduce. On a mesh that collective is a psum
    # over the pipe axis masked to those stages; callers use PIPE_AXIS.
    return PIPE_AXIS


# ---------------------------------------------------------------------------
# World sizes (static, from the mesh shape).
# ---------------------------------------------------------------------------

def _axis_size(name: str) -> int:
    return get_mesh().shape[name]


def get_tensor_model_parallel_world_size() -> int:
    return _axis_size(TENSOR_AXIS)


def get_pipeline_model_parallel_world_size() -> int:
    return _axis_size(PIPE_AXIS)


def get_data_parallel_world_size() -> int:
    return _axis_size(DATA_AXIS)


def get_context_parallel_world_size() -> int:
    return _axis_size(CONTEXT_AXIS)


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE


def get_pipeline_model_parallel_split_rank() -> Optional[int]:
    return _PIPELINE_MODEL_PARALLEL_SPLIT_RANK


# ---------------------------------------------------------------------------
# Ranks. Inside shard_map/jit over the mesh these are traced axis indices;
# on the host of a single-controller program they are 0 (every collective
# that cares about rank runs inside shard_map anyway).
# ---------------------------------------------------------------------------

def _axis_rank(name: str):
    try:
        return lax.axis_index(name)
    except NameError:
        return 0


def get_tensor_model_parallel_rank():
    return _axis_rank(TENSOR_AXIS)


def get_pipeline_model_parallel_rank():
    return _axis_rank(PIPE_AXIS)


def get_data_parallel_rank():
    return _axis_rank(DATA_AXIS)


def get_context_parallel_rank():
    return _axis_rank(CONTEXT_AXIS)


def get_virtual_pipeline_model_parallel_rank() -> Optional[int]:
    return _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK


def set_virtual_pipeline_model_parallel_rank(rank: Optional[int]) -> None:
    global _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK = rank


def get_tensor_model_parallel_src_rank() -> int:
    """Index-0 position along the TP axis (broadcast source)."""
    return 0


def get_data_parallel_src_rank() -> int:
    return 0


def get_pipeline_model_parallel_first_rank() -> int:
    return 0


def get_pipeline_model_parallel_last_rank() -> int:
    return get_pipeline_model_parallel_world_size() - 1


def get_pipeline_model_parallel_next_rank():
    pp = get_pipeline_model_parallel_world_size()
    return (get_pipeline_model_parallel_rank() + 1) % pp


def get_pipeline_model_parallel_prev_rank():
    pp = get_pipeline_model_parallel_world_size()
    return (get_pipeline_model_parallel_rank() - 1) % pp


def is_pipeline_first_stage(ignore_virtual: bool = False):
    """True on the first pipeline stage (traced inside shard_map).

    Mirrors the reference's virtual-pipeline handling: with interleaving,
    only virtual rank 0 on pipe rank 0 is "first".
    """
    if not ignore_virtual:
        vpp = _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
        if vpp is not None and (_VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK or 0) != 0:
            return False
    return get_pipeline_model_parallel_rank() == 0


def is_pipeline_last_stage(ignore_virtual: bool = False):
    if not ignore_virtual:
        vpp = _VIRTUAL_PIPELINE_MODEL_PARALLEL_WORLD_SIZE
        if vpp is not None and (
            (_VIRTUAL_PIPELINE_MODEL_PARALLEL_RANK or 0) != vpp - 1
        ):
            return False
    return (
        get_pipeline_model_parallel_rank()
        == get_pipeline_model_parallel_world_size() - 1
    )


def get_model_parallel_world_size() -> int:
    """Deprecated-style accessor (reference keeps it for Megatron compat):
    tensor-parallel world size, valid when pp == 1."""
    return get_tensor_model_parallel_world_size()


def get_model_parallel_rank():
    return get_tensor_model_parallel_rank()
