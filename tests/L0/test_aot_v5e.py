"""Chipless pre-flight: every Pallas kernel family compiles for a TPU v5e.

libtpu can describe a v5e topology with no chip attached, and
``jax.jit(f).lower(...).compile()`` against a device of that topology runs
the real Mosaic compiler. This is a COMPILE, not a run: it says nothing
about numerics, run-time memory or speed — ``chip_smoke.py`` and the
chip say those. It catches the kernel the compiler refuses before any chip
time is spent on it. Seconds per kernel; ``slow``-marked so the unmarked
tier stays compile-light.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu.utils import platform

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or one without v5e
        pytest.skip(f"libtpu cannot describe a v5e topology: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices[0]


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """Kernels choose interpret mode from the platform; the process runs
    on the CPU, the compile targets the TPU."""
    monkeypatch.setattr(platform, "_platform", lambda: "tpu")


def compile_on(device, fn, *shapes):
    """Compile ``fn`` for ``device`` at ``shapes`` ((shape, dtype) pairs);
    returns the number of Mosaic custom calls in the compiled program."""
    sharding = SingleDeviceSharding(device)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


@pytest.mark.parametrize("h", [1024, 4096])
@pytest.mark.parametrize("norm", ["layer", "rms"])
def test_norm_fwd_bwd(v5e, norm, h):
    from apex_tpu.normalization import (fused_layer_norm_affine,
                                        fused_rms_norm_affine)

    def f(x, w, b):
        if norm == "layer":
            return _sum32(fused_layer_norm_affine(x, w, b, h, 1e-5))
        return _sum32(fused_rms_norm_affine(x, w, h, 1e-5))

    assert compile_on(v5e, jax.grad(f, argnums=(0, 1, 2)),
                      ((8192, h), jnp.bfloat16), ((h,), jnp.float32),
                      ((h,), jnp.float32)) >= 2


@pytest.mark.parametrize("b,h,s,d,causal,dropout", [
    (64, 16, 128, 64, False, False), (64, 16, 128, 64, False, True),
    (8, 16, 1024, 64, True, False), (8, 16, 1024, 64, True, True),
    (4, 16, 2048, 64, True, False), (2, 16, 4096, 64, True, False),
    (4, 8, 2048, 128, True, False),
])
def test_flash_attention_fwd_bwd(v5e, b, h, s, d, causal, dropout):
    from apex_tpu.transformer.functional import flash_attention

    def f(q, k, v, mask):
        return _sum32(flash_attention(
            q, k, v, None if causal else mask, causal=causal,
            dropout_rate=0.1,
            dropout_rng=jax.random.PRNGKey(0) if dropout else None,
            use_kernel=True))

    qkv = ((b, h, s, d), jnp.bfloat16)
    assert compile_on(v5e, jax.grad(f, argnums=(0, 1, 2)), qkv, qkv, qkv,
                      ((b, s), jnp.int32)) >= 2


def test_fused_softmax(v5e):
    from apex_tpu.transformer.functional import (
        scaled_masked_softmax, scaled_upper_triang_masked_softmax)

    x = ((8, 16, 1024, 1024), jnp.bfloat16)
    assert compile_on(v5e, lambda x, m: scaled_masked_softmax(x, m, 0.125),
                      x, ((8, 1, 1024, 1024), jnp.int32)) >= 1
    assert compile_on(
        v5e, lambda x: scaled_upper_triang_masked_softmax(x, 0.125), x) >= 1
    assert compile_on(
        v5e, jax.grad(lambda x: _sum32(
            scaled_upper_triang_masked_softmax(x, 0.125))), x) >= 2


@pytest.mark.parametrize("vocab,dtype", [(30522, jnp.float32),
                                         (50304, jnp.bfloat16)])
def test_xentropy_fwd_bwd(v5e, vocab, dtype):
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    assert compile_on(
        v5e, jax.grad(lambda x, y: jnp.sum(softmax_cross_entropy_loss(x, y))),
        ((8192, vocab), dtype), ((8192,), jnp.int32)) == 2


@pytest.mark.parametrize("m,k,n", [
    (8, 1024, 1024), (64, 1024, 3072), (512, 1024, 4096), (8, 4096, 1024)])
def test_w8_matmul(v5e, m, k, n):
    from apex_tpu.quant.kernels import w8_matmul

    assert compile_on(v5e, w8_matmul, ((m, k), jnp.bfloat16),
                      ((k, n), jnp.int8), ((n,), jnp.float32),
                      ((n,), jnp.bfloat16)) == 1


@pytest.mark.parametrize("m", [8, 40])
def test_w8_matmul_nk(v5e, m):
    from apex_tpu.quant.kernels import w8_matmul_nk

    assert compile_on(v5e, w8_matmul_nk, ((m, 1024), jnp.bfloat16),
                      ((50304, 1024), jnp.int8),
                      ((50304,), jnp.float32)) == 1


def test_flat_adam(v5e):
    from apex_tpu.optimizers import FusedAdam

    opt = FusedAdam(lr=1e-4, weight_decay=0.01, use_flat_kernel=True)

    def make():
        params = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((1024,))}
        return params, opt.init(params)

    sharding = SingleDeviceSharding(v5e)
    p, s = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(make))
    text = jax.jit(lambda g, p, s: opt.step(g, p, s)).lower(
        p, p, s).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 1
