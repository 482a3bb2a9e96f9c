"""Where JAX's persistent compilation cache lives.

Every entry point (``benchmark/run.py``, ``chip_smoke.py``,
``__graft_entry__.py``, the examples, ``tests/conftest.py``) calls :func:`enable_compile_cache`
before its first compile. The directory is part of the cache key, so it is
a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(jax reads that variable itself; no directory is set in code), otherwise
``.jax_cache`` at the root of this checkout.
"""

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program that took a second to compile, however small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
