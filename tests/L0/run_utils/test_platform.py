"""``apex_tpu.utils.platform`` follows the platform JAX reports and never
turns a backend failure into a choice of interpret mode, and
``apex_tpu.utils.compile_cache`` leaves the cache where the environment
put it."""

import os
import subprocess
import sys

import jax
import pytest

from apex_tpu.utils import platform

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


@pytest.fixture
def fresh_platform():
    platform._platform.cache_clear()
    yield
    platform._platform.cache_clear()


def test_cpu_backend_selects_interpret(fresh_platform):
    assert platform.pallas_interpret() is True
    assert platform.pallas_interpret(False) is False


def test_backend_error_propagates(fresh_platform, monkeypatch):
    """A chip that fails to initialise (for instance because another
    process holds it) must not read as "no TPU, interpret instead"."""
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        platform.pallas_interpret()


def _cache_dir_of_child(env):
    code = ("from apex_tpu.utils.compile_cache import enable_compile_cache\n"
            "import jax\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_compile_cache_follows_the_environment(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    default = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_of_child(env) == [default, default]
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    assert _cache_dir_of_child(env) == [str(tmp_path), str(tmp_path)]
