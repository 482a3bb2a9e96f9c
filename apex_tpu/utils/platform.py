"""Platform detection helpers.

Pallas kernels compile only on TPU backends; on CPU (the unit-test rig runs
on an 8-virtual-device CPU mesh) they run in interpreter mode. Every Pallas
entry point in this package accepts ``interpret=None`` meaning "pick
automatically via :func:`pallas_interpret`".

The choice follows the platform JAX reports, never a failure: a backend
that cannot initialise (for instance a chip another process holds) raises
out of these helpers instead of quietly selecting the interpreter.
"""

import functools

import jax


@functools.cache
def _platform() -> str:
    return jax.devices()[0].platform


def pallas_interpret(interpret=None) -> bool:
    """Resolve a user-supplied ``interpret`` flag (None → interpret on the
    CPU backend, compile everywhere else)."""
    if interpret is None:
        return _platform() == "cpu"
    return bool(interpret)
