"""Tests for the multi-tensor engine: flatten round-trip, list ops, and the
flat Pallas kernels vs jnp references."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.multi_tensor_apply import (
    MultiTensorApply,
    flatten_pytree,
    flatten_tensors,
    kernels,
    make_spec,
    multi_tensor_axpby,
    multi_tensor_l2norm,
    multi_tensor_scale,
    unflatten_pytree,
    unflatten_tensors,
)


def _tensors():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return [
        jax.random.normal(ks[0], (33, 7), jnp.float32),
        jax.random.normal(ks[1], (129,), jnp.float32),
        jax.random.normal(ks[2], (4, 4, 4), jnp.bfloat16),
        jax.random.normal(ks[3], (2048,), jnp.float32),
    ]


def test_flatten_roundtrip():
    ts = _tensors()
    buf, spec = flatten_tensors(ts)
    assert buf.shape[1] == 128 and buf.dtype == jnp.float32
    back = unflatten_tensors(buf, spec)
    for t, b in zip(ts, back):
        assert t.dtype == b.dtype and t.shape == b.shape
        np.testing.assert_allclose(np.asarray(t, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=1e-2)


def test_flatten_pytree_roundtrip():
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 5), jnp.float32)}}
    buf, spec, treedef = flatten_pytree(tree)
    back = unflatten_pytree(buf, spec, treedef)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), tree, back)


def test_tile_tensor_ids():
    ts = _tensors()
    spec = make_spec(ts)
    ids = spec.tile_tensor_ids(8)
    assert ids.shape[0] == spec.total_rows // 8
    assert ids[0] == 0 and ids[-1] == len(ts) - 1


def test_multi_tensor_scale_and_overflow():
    ts = _tensors()
    out, found_inf = multi_tensor_scale(ts, 0.5)
    assert not bool(found_inf)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(ts[0]) * 0.5, rtol=1e-6)
    bad = ts[:2] + [ts[2].astype(jnp.float32).at[0, 0, 0].set(jnp.inf)]
    _, found_inf = multi_tensor_scale(bad, 0.5)
    assert bool(found_inf)


def test_multi_tensor_l2norm():
    ts = [t.astype(jnp.float32) for t in _tensors()]
    total, per = multi_tensor_l2norm(ts, per_tensor=True)
    want = np.sqrt(sum(float(jnp.sum(t * t)) for t in ts))
    np.testing.assert_allclose(float(total), want, rtol=1e-5)
    np.testing.assert_allclose(
        float(per[1]), float(jnp.linalg.norm(ts[1])), rtol=1e-5)


def test_multi_tensor_axpby():
    xs = [jnp.ones((5,)), jnp.full((3, 3), 2.0)]
    ys = [jnp.full((5,), 3.0), jnp.ones((3, 3))]
    out, flag = multi_tensor_axpby(2.0, xs, -1.0, ys)
    np.testing.assert_allclose(np.asarray(out[0]), np.full(5, -1.0))
    np.testing.assert_allclose(np.asarray(out[1]), np.full((3, 3), 3.0))
    assert not bool(flag)


def test_applier_shim_apex_convention():
    applier = MultiTensorApply(2048)
    # apex: multi_tensor_applier(scale_op, noop_buf, [src, dst], scale) —
    # dst supplies the out dtypes, results are returned
    src = [jnp.ones((4,), jnp.bfloat16)]
    dst = [jnp.zeros((4,), jnp.float32)]
    out, flag = applier("scale", None, [src, dst], 2.0)
    assert out[0].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out[0]), np.full(4, 2.0))
    assert not bool(flag)

    # apex: applier(axpby_op, noop, [xs, ys, outs], a, b, ...)
    xs, ys = [jnp.ones((4,))], [jnp.full((4,), 2.0)]
    out, _ = applier("axpby", None, [xs, ys, ys], 3.0, 1.0)
    np.testing.assert_allclose(np.asarray(out[0]), np.full(4, 5.0))

    # single-list form still works for l2norm
    total = applier("l2norm", None, [[jnp.full((4,), 2.0)]])
    np.testing.assert_allclose(float(total), 4.0)


# -- flat Pallas kernels ----------------------------------------------------

def test_flat_scale_kernel():
    ts = [t.astype(jnp.float32) for t in _tensors()]
    buf, spec = flatten_tensors(ts)
    out, found_inf = kernels.flat_scale(buf, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(buf) * 0.25,
                               rtol=1e-6)
    assert not bool(found_inf)
    bad = buf.at[0, 0].set(jnp.nan)
    _, found_inf = kernels.flat_scale(bad, 0.25)
    assert bool(found_inf)


def test_flat_axpby_kernel():
    buf, _ = flatten_tensors([t.astype(jnp.float32) for t in _tensors()])
    out, _ = kernels.flat_axpby(2.0, buf, 0.5, buf * 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(buf) * 4,
                               rtol=1e-6)


def test_flat_l2norm_kernel_global_and_per_tensor():
    ts = [t.astype(jnp.float32) for t in _tensors()]
    buf, spec = flatten_tensors(ts)
    norm = kernels.flat_l2norm(buf)
    want = np.sqrt(sum(float(jnp.sum(t * t)) for t in ts))
    np.testing.assert_allclose(float(norm), want, rtol=1e-5)

    parts = kernels.flat_l2norm_partials(buf)
    ids = spec.tile_tensor_ids(8)
    # pad ids to match block-padded partials (pad partials are zero)
    ids = np.pad(ids, (0, parts.shape[0] - ids.shape[0]),
                 constant_values=len(ts) - 1)
    seg = jax.ops.segment_sum(parts, jnp.asarray(ids), num_segments=len(ts))
    for i, t in enumerate(ts):
        np.testing.assert_allclose(
            float(jnp.sqrt(seg[i])), float(jnp.linalg.norm(t)), rtol=1e-5)


def test_flat_adam_kernel_matches_manual():
    n = 5000
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    p = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
    (gbuf, spec) = flatten_tensors([g])
    (pbuf, _) = flatten_tensors([p], spec)
    m = jnp.zeros_like(pbuf)
    v = jnp.zeros_like(pbuf)
    p1, m1, v1 = kernels.flat_adam(
        gbuf, pbuf, m, v, lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8,
        step=1, weight_decay=0.01, adam_w_mode=True)
    # manual
    mm = 0.1 * g
    vv = 0.001 * g * g
    mhat = mm / (1 - 0.9)
    vhat = vv / (1 - 0.999)
    want = p - 1e-2 * (mhat / (jnp.sqrt(vhat) + 1e-8) + 0.01 * p)
    got = unflatten_tensors(p1, spec)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_applier_callable_reference_arity():
    # apex convention: applier(op, noop_flag, tensor_lists, *args) invokes
    # op(chunk_size, noop_flag, tensor_lists, *args) — the first two must
    # be forwarded, not dropped
    applier = MultiTensorApply(4096)
    seen = {}

    def op(chunk_size, noop_flag, tensor_lists, alpha):
        seen.update(chunk_size=chunk_size, noop_flag=noop_flag,
                    n_lists=len(tensor_lists), alpha=alpha)
        return [t * alpha for t in tensor_lists[0]]

    out = applier(op, "noop", [[jnp.ones(3)]], 2.0)
    assert seen == {"chunk_size": 4096, "noop_flag": "noop",
                    "n_lists": 1, "alpha": 2.0}
    np.testing.assert_allclose(np.asarray(out[0]), np.full(3, 2.0))


def test_flat_adam_kernel_bf16_moment_and_castout():
    """Kernel-level reduced-precision contract: bf16 m in/out with fp32
    accumulate, rounded ONCE to bf16 on the store, and the optional 4th
    output == the updated params cast to the emit dtype, bit for bit.

    The stored m is the rounding of the kernel's own fp32 accumulator,
    which need not be the fp32 path's m bit for bit: the two programs
    fuse differently, and a contracted multiply-add moves
    ``b1 * m + (1 - b1) * g`` by one fp32 ulp. That shows only where the
    fp32 value sits on a bf16 rounding tie (element 1325 of this draw:
    0x3ec68000 against 0x3ec68001), and there either neighbour is a
    correct single rounding. So: one bf16 ulp at most, only at such
    ties, and in at most 1e-4 of the elements."""
    n = 5000
    g = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
    p = jax.random.normal(jax.random.PRNGKey(4), (n,), jnp.float32)
    (gbuf, spec) = flatten_tensors([g])
    (pbuf, _) = flatten_tensors([p], spec)
    kw = dict(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8, step=3,
              weight_decay=0.01, adam_w_mode=True)

    m32 = jnp.full_like(pbuf, 0.25)
    v32 = jnp.full_like(pbuf, 0.5)
    p_ref, m_ref, v_ref = kernels.flat_adam(gbuf, pbuf, m32, v32, **kw)

    outs = kernels.flat_adam(gbuf, pbuf, m32.astype(jnp.bfloat16), v32,
                             emit_compute_dtype=jnp.bfloat16, **kw)
    assert len(outs) == 4
    p_bf, m_bf, v_bf, pc = outs
    assert m_bf.dtype == jnp.bfloat16 and v_bf.dtype == jnp.float32
    # m32 is bf16-exact, so the bf16 path's accumulator is the fp32
    # path's m up to the order of operations; v must match bit for bit
    got = np.asarray(m_bf).view(np.uint16).astype(np.int32)
    want = np.asarray(m_ref.astype(jnp.bfloat16)).view(
        np.uint16).astype(np.int32)
    off = got != want
    assert np.abs(got - want).max() <= 1
    low = np.asarray(m_ref).view(np.uint32)[off] & 0xFFFF
    assert np.isin(low, (0x7FFF, 0x8000, 0x8001)).all(), low
    assert off.mean() <= 1e-4
    np.testing.assert_array_equal(np.asarray(v_bf), np.asarray(v_ref))
    np.testing.assert_allclose(np.asarray(p_bf), np.asarray(p_ref),
                               rtol=1e-5, atol=1e-6)
    # fused cast-out == cast of the kernel's own updated params
    assert pc.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(pc, np.float32),
        np.asarray(p_bf.astype(jnp.bfloat16), np.float32))
