"""Trace-time VMEM budget check (APX102).

A ``pallas_call`` whose resident blocks outgrow VMEM (~16 MiB per
TensorCore) fails at Mosaic compile time on hardware — but the CPU
test rig runs every kernel in interpret mode, where any block shape
"works", so an oversized retune only explodes on the TPU. This check
closes that gap without a TPU: ``pl.pallas_call`` is monkeypatched to
record (grid, block specs, scratch, out shapes) and return
correctly-shaped zeros, then each *registered configuration* — the
representative shapes of the kernels in ``multi_tensor_apply/
kernels.py``, ``flash_attention.py`` and ``fused_layer_norm.py``,
forward and backward — is traced under ``jax.eval_shape`` (abstract
only: no compile, no execution, CPU-safe, milliseconds per config).

The budget model per recorded call:

    2 x (sum of VMEM input blocks + sum of VMEM output blocks)
      + SMEM blocks + scratch bytes   <=  16 MiB

The 2x is Pallas' double buffering of streamed blocks; scratch and
SMEM are single-resident. Block dims of ``None`` take the operand's
full dimension. This deliberately overcounts revisited blocks — a
conservative estimator that passes is a real guarantee, one that
undercounts is noise.

A config that fails to trace at all is reported as APX100: an
unverifiable kernel is a lint failure, not a skip.
"""

import contextlib
import functools
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

from apex_tpu.lint import Finding

BUDGET_BYTES = 16 * 1024 * 1024


@dataclass
class CallRecord:
    kernel: str
    grid: Tuple
    in_bytes: int = 0
    out_bytes: int = 0
    smem_bytes: int = 0
    scratch_bytes: int = 0

    @property
    def total(self) -> int:
        return (2 * (self.in_bytes + self.out_bytes)
                + self.smem_bytes + self.scratch_bytes)

    def describe(self) -> str:
        mib = 1024 * 1024
        return (f"2x({self.in_bytes / mib:.2f}+{self.out_bytes / mib:.2f})"
                f" + smem {self.smem_bytes / mib:.3f}"
                f" + scratch {self.scratch_bytes / mib:.2f}"
                f" = {self.total / mib:.2f} MiB (grid {self.grid})")


@dataclass
class Config:
    """One registered kernel configuration: ``build()`` returns
    ``(fn, args)`` to run under ``jax.eval_shape``."""
    name: str
    module: str  # dotted module whose kernels this config exercises
    build: Callable[[], Tuple[Callable, tuple]]
    budget: int = BUDGET_BYTES


def _kernel_name(kernel) -> str:
    if isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", repr(kernel))


def _space(spec) -> str:
    return str(getattr(spec, "memory_space", "")).lower()


def _is_smem(spec) -> bool:
    return "smem" in _space(spec)


def _off_chip(spec) -> bool:
    """An operand left in HBM (``ANY`` / ``HBM``: the kernel brings what
    it wants by DMA into scratch, which is priced) or a semaphore."""
    return _space(spec) in ("any", "hbm") or "semaphore" in _space(spec)


def _block_bytes(spec, operand) -> int:
    import numpy as np

    shape = getattr(operand, "shape", ())
    dtype = getattr(operand, "dtype", None)
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
    block = getattr(spec, "block_shape", None) if spec is not None else None
    if block is None:
        dims = shape
    else:
        dims = [s if b is None else b for b, s in zip(block, shape)]
    n = 1
    for d in dims:
        n *= int(d)
    return n * itemsize


@contextlib.contextmanager
def capture_calls(records: List[CallRecord]):
    """Swap ``pl.pallas_call`` for a recorder returning shaped zeros."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def fake(kernel, *, out_shape, grid=None, in_specs=None,
             out_specs=None, scratch_shapes=None, grid_spec=None, **_kw):
        prefetch = 0
        if grid_spec is not None:
            grid, in_specs, out_specs, scratch_shapes = (
                grid_spec.grid, grid_spec.in_specs, grid_spec.out_specs,
                grid_spec.scratch_shapes)
            prefetch = getattr(grid_spec, "num_scalar_prefetch", 0)

        def runner(*operands):
            import jax.numpy as jnp

            rec = CallRecord(_kernel_name(kernel),
                             grid if isinstance(grid, tuple) else (grid,))
            # scalar-prefetch operands live whole in SMEM
            for op in operands[:prefetch]:
                rec.smem_bytes += _block_bytes(None, op)
            operands = operands[prefetch:]
            specs = in_specs if in_specs is not None else [None] * len(
                operands)
            for spec, op in zip(specs, operands):
                if _off_chip(spec):
                    continue
                b = _block_bytes(spec, op)
                if _is_smem(spec):
                    rec.smem_bytes += b
                else:
                    rec.in_bytes += b
            out_leaves = (list(out_shape)
                          if isinstance(out_shape, (list, tuple))
                          else [out_shape])
            ospecs = (list(out_specs)
                      if isinstance(out_specs, (list, tuple))
                      else [out_specs] * len(out_leaves))
            for spec, leaf in zip(ospecs, out_leaves):
                rec.out_bytes += _block_bytes(spec, leaf)
            for s in scratch_shapes or []:
                if not _off_chip(s):
                    rec.scratch_bytes += _block_bytes(None, s)
            records.append(rec)
            outs = [jnp.zeros(l.shape, l.dtype) for l in out_leaves]
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(outs)
            return outs[0]

        return runner

    pl.pallas_call = fake
    try:
        yield
    finally:
        pl.pallas_call = real


def run_configs(configs: List[Config]) -> List[Finding]:
    import jax

    findings: List[Finding] = []
    for cfg in configs:
        records: List[CallRecord] = []
        path = _module_path(cfg.module)
        try:
            with capture_calls(records):
                fn, args = cfg.build()
                jax.eval_shape(fn, *args)
        except Exception as e:  # noqa: BLE001 - surfaced as a finding
            findings.append(Finding(
                "APX100", path, 1,
                f"config '{cfg.name}' failed to trace: "
                f"{type(e).__name__}: {e}"))
            continue
        for rec in records:
            if rec.total > cfg.budget:
                findings.append(Finding(
                    "APX102", path, 1,
                    f"config '{cfg.name}' kernel '{rec.kernel}': "
                    f"estimated VMEM residency {rec.describe()} exceeds "
                    f"the {cfg.budget // (1024 * 1024)} MiB budget"))
    return findings


def _module_path(dotted: str) -> str:
    import importlib

    try:
        return importlib.import_module(dotted).__file__ or dotted
    except Exception:  # noqa: BLE001
        return dotted


# -- registered repo configurations -----------------------------------------

def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _flash_cfg(d, dtype, seq):
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu.transformer.functional.flash_attention import (
            flash_attention,
        )

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, use_kernel=True)
            return jnp.sum(out.astype(jnp.float32))

        grads = lambda q, k, v: jax.value_and_grad(loss, (0, 1, 2))(q, k, v)
        shape = (1, 2, seq, d)
        return grads, (_sds(shape, dtype),) * 3

    return build


def _fmha_cfg(b, s, h, d, dtype):
    """The whole-sequence pair (``apex_fmha_fwd`` / ``apex_fmha_bwd``) on a
    packed projection at a real shape, forward and backward."""
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu.transformer.functional.flash_attention import (
            flash_attention_packed,
        )

        def loss(qkv):
            return jnp.sum(flash_attention_packed(qkv).astype(jnp.float32))

        return jax.value_and_grad(loss), (_sds((b, s, 3, h, d), dtype),)

    return build


def _ln_cfg(h, rms=False):
    def build():
        import importlib

        import jax
        import jax.numpy as jnp

        # the package __init__ re-exports a function of the same name,
        # so the submodule must be imported by dotted path
        fln = importlib.import_module(
            "apex_tpu.normalization.fused_layer_norm")

        if rms:
            def loss(x, w):
                y = fln.fused_rms_norm_affine(x, w, (h,))
                return jnp.sum(y.astype(jnp.float32))
            argnums = (0, 1)
            args = (_sds((4096, h), "float32"), _sds((h,), "float32"))
        else:
            def loss(x, w, b):
                y = fln.fused_layer_norm_affine(x, w, b, (h,))
                return jnp.sum(y.astype(jnp.float32))
            argnums = (0, 1, 2)
            args = (_sds((4096, h), "float32"), _sds((h,), "float32"),
                    _sds((h,), "float32"))
        return (lambda *a: jax.value_and_grad(loss, argnums)(*a)), args

    return build


def _flat_cfg(which):
    rows = 8192  # 8192x128 fp32 = 4 MiB flat buffer, 32 grid tiles

    def build():
        import functools as ft

        from apex_tpu.multi_tensor_apply import kernels as K

        buf = _sds((rows, 128), "float32")
        m16 = _sds((rows, 128), "bfloat16")
        ids = _sds((rows // 8,), "int32")
        if which == "adam":
            fn = ft.partial(K.flat_adam, lr=1e-3, beta1=0.9, beta2=0.99,
                            eps=1e-8, step=1, weight_decay=0.01,
                            emit_compute_dtype="bfloat16", interpret=True)
            return fn, (buf, buf, m16, buf)
        if which == "sgd":
            fn = ft.partial(K.flat_sgd, lr=1e-3, momentum=0.9,
                            dampening=0.0, weight_decay=0.0,
                            nesterov=False, wd_after_momentum=False,
                            first_run=True, interpret=True)
            return fn, (buf, buf, m16)
        if which == "lamb":
            fn = ft.partial(K.flat_lamb, lr=1e-3, beta1=0.9, beta2=0.99,
                            eps=1e-8, step=1, weight_decay=0.01,
                            num_tensors=4, interpret=True)
            return fn, (buf, buf, m16, buf, ids)
        if which == "adagrad":
            fn = ft.partial(K.flat_adagrad, lr=1e-3, eps=1e-8,
                            weight_decay=0.0, interpret=True)
            return fn, (buf, buf, buf)
        if which == "novograd":
            fn = ft.partial(K.flat_novograd, lr=1e-3, beta1=0.9,
                            beta2=0.99, eps=1e-8, step=1,
                            weight_decay=0.0, num_tensors=4,
                            interpret=True)
            return fn, (buf, buf, m16, _sds((4,), "float32"), ids)
        if which == "scale":
            fn = ft.partial(K.flat_scale, scale=0.5, interpret=True)
            return fn, (buf,)
        if which == "axpby":
            fn = (lambda x, y: K.flat_axpby(1.0, x, 2.0, y,
                                            interpret=True))
            return fn, (buf, buf)
        fn = ft.partial(K.flat_l2norm_partials, interpret=True)
        return fn, (buf,)

    return build


def _xentropy_cfg():
    def build():
        import jax

        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

        def loss(logits, labels):
            return softmax_cross_entropy_loss(logits, labels).mean()

        fn = lambda lg, lb: jax.value_and_grad(loss)(lg, lb)
        return fn, (_sds((1024, 512), "float32"), _sds((1024,), "int32"))

    return build


def _fused_softmax_cfg():
    """Both fused-softmax families (masked 4D + causal 3D) fwd+bwd at
    full 128-row tiles."""
    def build():
        import jax
        import jax.numpy as jnp

        from apex_tpu.transformer.functional import fused_softmax as fs

        def loss(x, mask, x3):
            y = fs.scaled_masked_softmax(x, mask, scale=0.5)
            z = fs.scaled_upper_triang_masked_softmax(x3, scale=0.5)
            return (jnp.sum(y.astype(jnp.float32))
                    + jnp.sum(z.astype(jnp.float32)))

        fn = lambda *a: jax.value_and_grad(loss, (0, 2))(*a)
        return fn, (_sds((2, 2, 128, 128), "bfloat16"),
                    _sds((2, 1, 128, 128), "int32"),
                    _sds((4, 128, 128), "bfloat16"))

    return build


def _bottleneck_cfg():
    """Halo'd 3x3-conv spatial bottleneck, H sharded over ``context``.

    The block's compute is XLA convs today, so the capture records no
    pallas calls — registering it pins the *trace* (a halo-exchange or
    conv regression surfaces as APX100) and budget-checks any Pallas
    kernel that later lands in the halo path. Uses an explicit local
    2-device mesh so the global parallel state is untouched; on a
    single-device rig it degrades to the unsharded reference block
    (same convs, no exchange).
    """
    def build():
        import jax

        from apex_tpu.contrib.bottleneck import (
            spatial_bottleneck, spatial_parallel_bottleneck,
        )

        params = {"w1": _sds((1, 1, 8, 4), "float32"),
                  "w2": _sds((3, 3, 4, 4), "float32"),
                  "w3": _sds((1, 1, 4, 8), "float32")}
        x = _sds((2, 16, 5, 8), "float32")
        if len(jax.devices()) < 2:
            return spatial_bottleneck, (params, x)

        import numpy as np
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer import parallel_state as ps

        mesh = Mesh(np.array(jax.devices()[:2]), (ps.CONTEXT_AXIS,))
        fn = ps.shard_map(spatial_parallel_bottleneck, mesh=mesh,
                          in_specs=(P(), P(None, ps.CONTEXT_AXIS)),
                          out_specs=P(None, ps.CONTEXT_AXIS))
        return fn, (params, x)

    return build


def _w8_matmul_cfg():
    """The dequant-fused int8 matmul family at serving-like shapes: a
    column/row-style ``w8_matmul`` (K x N weight, per-N scale, bias)
    chained into the output-channel-major logits head ``w8_matmul_nk``
    (V x h table, per-V scale). The N grid streams one (K, block_n)
    int8 tile + its fp32 dequant in registers — the resident blocks are
    what the budget prices."""
    def build():
        from apex_tpu.quant.kernels import w8_matmul, w8_matmul_nk

        def fn(x, wq, scale, bias, tq, tscale):
            h = w8_matmul(x, wq, scale, bias, out_dtype=x.dtype)
            return w8_matmul_nk(h, tq, tscale)

        return fn, (_sds((32, 1024), "bfloat16"),
                    _sds((1024, 4096), "int8"), _sds((4096,), "float32"),
                    _sds((4096,), "float32"),
                    _sds((50304, 4096), "int8"), _sds((50304,), "float32"))

    return build


def _paged_serving_cfg(which):
    """Paged serving steps under the recorder: prefill runs flash
    attention over the prompt bucket and decode runs the paged-attention
    kernel ``apex_paged_decode_fwd`` (their pallas blocks and the
    kernel's K/V page buffers are what the budget prices); the verify,
    tree-verify and chunk steps keep the XLA gather path, registered to
    pin their trace."""
    def build():
        import dataclasses
        import functools as ft

        import jax

        from apex_tpu.models.gpt import gpt_tiny, init_gpt
        from apex_tpu.serving.cache import init_paged_cache
        from apex_tpu.serving.decode import (
            make_paged_decode_fn, make_paged_prefill_fn,
        )

        cfg = dataclasses.replace(gpt_tiny(), use_rope=True)
        params = jax.eval_shape(
            lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
        cache = jax.eval_shape(ft.partial(
            init_paged_cache, cfg, 2, 32, 6, 16))
        if which == "prefill":
            fn = make_paged_prefill_fn(cfg)
            return fn, (params, cache, _sds((1, 16), "int32"),
                        _sds((16,), "int32"), _sds((), "int32"),
                        _sds((1,), "int32"), _sds((2,), "int32"))
        if which == "chunk_prefill":
            from apex_tpu.serving.decode import make_paged_chunk_prefill_fn

            fn = make_paged_chunk_prefill_fn(cfg)
            return fn, (params, cache, _sds((1, 16), "int32"),
                        _sds((16,), "int32"), _sds((), "int32"),
                        _sds((), "int32"), _sds((1,), "int32"),
                        _sds((2,), "int32"), _sds((2,), "int32"))
        if which == "verify":
            from apex_tpu.serving.decode import make_paged_verify_fn

            fn = make_paged_verify_fn(cfg)
            return fn, (params, cache, _sds((2, 4), "int32"))
        if which == "tree_verify":
            from apex_tpu.serving.decode import make_paged_tree_verify_fn

            fn = make_paged_tree_verify_fn(cfg)
            return fn, (params, cache, _sds((2, 4), "int32"),
                        _sds((2, 4), "int32"), _sds((2, 4, 4), "bool"))
        fn = make_paged_decode_fn(cfg)
        return fn, (params, cache, _sds((2,), "int32"),
                    _sds((2,), "bool"))

    return build


def _paged_attention_cfg():
    """The paged decode-attention kernel at the served width: 56 slots,
    16 heads of 64, pages of 16, a bfloat16 pool left in HBM. What is
    resident is the query / new-row / output blocks, the two K and two V
    buffers the kernel fetches pages into (1 MiB each: 512 positions of
    this row) and the SMEM word that says which buffer a slot starts in."""
    def build():
        import functools as ft

        from apex_tpu.transformer.functional.paged_attention import (
            paged_decode_attention,
        )

        row = _sds((56, 1, 1024), "bfloat16")
        pool = _sds((24, 3586, 16, 1024), "bfloat16")
        return ft.partial(paged_decode_attention, heads=16), (
            row, row, row, pool, pool, _sds((56, 64), "int32"),
            _sds((56,), "int32"), _sds((), "int32"))

    return build


def _gated_delta_cfg(which):
    """The two Gated DeltaNet kernels at the published widths (30 heads,
    d_k 96, d_v 192). ``chunk``: the largest bucket; resident are five
    heads' blocks of one chunk (W_v, W_k, Q, K^T, A, the decay row, the
    output) and their state. ``step``: the stacked state of 12 layers x 16
    slots stays where it is; resident are ten heads of one slot, in and
    out."""
    def build():
        from apex_tpu.transformer.functional import gated_delta as gd

        if which == "chunk":
            return gd.gated_delta_chunked, (
                _sds((30, 4096, 96), "float32"),
                _sds((30, 4096, 96), "float32"),
                _sds((30, 4096, 192), "float32"),
                _sds((30, 4096), "float32"), _sds((30, 4096), "float32"))
        return gd.gated_delta_step, (
            _sds((16, 30, 96), "float32"), _sds((16, 30, 96), "float32"),
            _sds((16, 30, 192), "float32"), _sds((16, 30), "float32"),
            _sds((16, 30), "float32"),
            _sds((12, 16, 30, 96, 192), "float32"), _sds((), "int32"),
            _sds((16,), "bool"))

    return build


def _kda_cfg(which):
    """The two delta-rule kernels with a decay per key channel (Kimi Delta
    Attention) at the ``bailing_hybrid`` family's published widths (32 heads
    of 128 x 128). ``chunk``: the largest bucket; resident are four heads'
    blocks of one chunk (W_v, W_k, Q, K^T, A, the decay's row of 128, the
    output) and their state. ``step``: the stacked state of 6 layers x 256
    slots stays where it is; resident are eight heads of one slot, in and
    out, and their rows (q, k, beta k and the decay; beta v)."""
    def build():
        from apex_tpu.transformer.functional import gated_delta as gd

        if which == "chunk":
            return gd.gated_delta_chunked, (
                _sds((32, 4096, 128), "float32"),
                _sds((32, 4096, 128), "float32"),
                _sds((32, 4096, 128), "float32"),
                _sds((32, 4096, 128), "float32"),
                _sds((32, 4096), "float32"))
        return gd.gated_delta_step, (
            _sds((256, 32, 128), "float32"), _sds((256, 32, 128), "float32"),
            _sds((256, 32, 128), "float32"), _sds((256, 32, 128), "float32"),
            _sds((256, 32), "float32"),
            _sds((6, 256, 32, 128, 128), "float32"), _sds((), "int32"),
            _sds((256,), "bool"))

    return build


def _dsa_index_cfg():
    """The sparse attention's index kernel at the ``glm_next`` family's
    published widths: 32 index heads of 128 a slot, 64 slots, against the one
    sparse layer's cache of 65538 pages of 4 pooled keys, which stays where
    it is; resident are two buffers of 64 pages' keys (64 KB each), the
    queries and a slot's row of 4096 scores."""
    def build():
        from apex_tpu.transformer.functional.sparse_index import index_scores

        return index_scores, (
            _sds((64, 32, 128), "float32"), _sds((64, 32), "float32"),
            _sds((1, 65538, 4, 128), "bfloat16"), _sds((64, 1024), "int32"),
            _sds((64,), "int32"), _sds((), "int32"))

    return build


def _nemotron_kernel_cfg(which):
    """The two kernels of the ``nemotron_h`` family at the published widths.
    ``ssd_step``: the stacked Mamba-2 state of 5 layers x 128 slots (128
    heads of 64 x 128) stays where it is; resident are 64 heads of one slot,
    in and out. ``gmm``: the grouped expert product over 128 held experts of
    1024 x 2688, one decode tick's 128 x 22 assignments; resident are a row
    tile, a column tile of one expert's matrix and the output tile."""
    def build():
        import functools as ft

        if which == "ssd_step":
            from apex_tpu.transformer.functional.ssd import ssd_step
            return ssd_step, (
                _sds((128, 128, 64), "float32"), _sds((128, 128), "float32"),
                _sds((128,), "float32"), _sds((128, 8, 128), "float32"),
                _sds((128, 8, 128), "float32"),
                _sds((5, 128, 128, 64, 128), "float32"), _sds((), "int32"),
                _sds((128,), "bool"))
        from apex_tpu.transformer.functional.moe import grouped_matmul
        return ft.partial(grouped_matmul, activation="relu2"), (
            _sds((2816, 1024), "bfloat16"),
            _sds((128, 1024, 2688), "bfloat16"), _sds((128,), "int32"))

    return build


def _deepseek_kernel_cfg(which):
    """The kernels of the ``deepseek_v3`` family at the published widths.
    ``mla``: 128 absorbed queries of 640 a slot over the ONE latent pool of
    5 layers x 25602 pages of 16 rows x 640, which stays where it is;
    resident are a ring of eight blocks of 256 rows (16 pages, 320 KB each:
    2.5 MiB), the queries and the latent context; beside them the compiler
    keeps the stacked two-term query (256 x 640 bfloat16) and one block's
    scores (256 x 256 float32, and the next block's beside them), 0.8 MiB
    the estimator does not see.
    ``gate_up`` / ``down``: the grouped product over the SwiGLU expert's two
    matrices at hidden 7168 (16 held experts of 4 layers, read in place out
    of the stack), one decode tick's 64 x 8 assignments; resident are a row
    tile, a column tile of one expert's matrix (256 wide at k = 7168) and the
    output tile."""
    def build():
        import functools as ft

        if which == "mla":
            from apex_tpu.transformer.functional.mla_attention import (
                mla_decode_attention,
            )
            return ft.partial(mla_decode_attention, value_width=512), (
                _sds((64, 128, 640), "float32"), _sds((64, 640), "float32"),
                _sds((5, 25602, 16, 640), "bfloat16"),
                _sds((64, 400), "int32"), _sds((64,), "int32"),
                _sds((), "int32"))
        from apex_tpu.transformer.functional.moe import grouped_matmul
        k, n = (7168, 4096) if which == "gate_up" else (2048, 7168)

        def product(lhs, rhs, sizes, first):
            return grouped_matmul(lhs, rhs, sizes, first_group=first)

        return product, (
            _sds((512, k), "float32"), _sds((64, k, n), "bfloat16"),
            _sds((16,), "int32"), _sds((), "int32"))

    return build


def _exaone_kernel_cfg():
    """The paged decode kernel's BOUNDED call at the ``exaone_moe`` family's
    published widths: 64 query heads over 8 K/V heads of 128 a slot, 64
    slots, over the four sliding layers' pool of 578 pages of 16 rows x 1024
    through the (64, 9) view of the cyclic table, which stays where it is;
    resident are two buffers of 9 pages each of K and of V (a slot's whole
    window is one partly filled chunk), the stacked queries and the
    context."""
    def build():
        from apex_tpu.transformer.functional.paged_attention import (
            paged_decode_attention,
        )

        def bounded(q, k, v, k_pool, v_pool, table, pos, layer, start):
            return paged_decode_attention(q, k, v, k_pool, v_pool, table,
                                          pos, layer, heads=64, kv_heads=8,
                                          start=start)

        row = _sds((64, 1, 1024), "bfloat16")
        pool = _sds((4, 578, 16, 1024), "bfloat16")
        return bounded, (
            _sds((64, 1, 8192), "float32"), row, row, pool, pool,
            _sds((64, 9), "int32"), _sds((64,), "int32"), _sds((), "int32"),
            _sds((64,), "int32"))

    return build


def _draft_forward_cfg():
    """The model drafter's per-token forward (``draft_gpt_tiny`` over
    its lockstep cache, the drafter's own pool under its identity
    table): the paged decode step at the drafter's geometry, so its
    attention is ``apex_paged_decode_fwd`` and is budget-checked here."""
    def build():
        import functools as ft

        import jax

        from apex_tpu.models.gpt import draft_gpt_tiny, init_gpt
        from apex_tpu.serving.decode import make_paged_decode_fn
        from apex_tpu.serving.draft_model import init_draft_cache

        cfg = draft_gpt_tiny()
        params = jax.eval_shape(
            lambda k: init_gpt(k, cfg), jax.random.PRNGKey(0))
        # 32 + 5: the engine max_len plus DraftModel's catch-up chunk
        cache = jax.eval_shape(ft.partial(init_draft_cache, cfg, 2, 37))
        fn = make_paged_decode_fn(cfg)
        return fn, (params, cache, _sds((2,), "int32"),
                    _sds((2,), "bool"))

    return build


def repo_configs() -> List[Config]:
    flat = "apex_tpu.multi_tensor_apply.kernels"
    flash = "apex_tpu.transformer.functional.flash_attention"
    ln = "apex_tpu.normalization.fused_layer_norm"
    cfgs = [
        Config("flash_d64_bf16_s2048", flash,
               _flash_cfg(64, "bfloat16", 2048)),
        Config("flash_d128_f32_s2048", flash,
               _flash_cfg(128, "float32", 2048)),
        Config("fmha_bert_large_s128", flash,
               _fmha_cfg(64, 128, 16, 64, "bfloat16")),
        Config("fmha_d128_f32_s256", flash,
               _fmha_cfg(8, 256, 8, 128, "float32")),
        Config("ln_h1024_fwd_bwd", ln, _ln_cfg(1024)),
        Config("ln_h4096_fwd_bwd_colsplit", ln, _ln_cfg(4096)),
        Config("rms_h4096_fwd_bwd", ln, _ln_cfg(4096, rms=True)),
    ]
    for which in ("adam", "sgd", "lamb", "adagrad", "novograd", "scale",
                  "axpby", "l2norm"):
        cfgs.append(Config(f"flat_{which}", flat, _flat_cfg(which)))
    cfgs.append(Config("xentropy_fwd_bwd", "apex_tpu.contrib.xentropy",
                       _xentropy_cfg()))
    cfgs.append(Config("fused_softmax_fwd_bwd",
                       "apex_tpu.transformer.functional.fused_softmax",
                       _fused_softmax_cfg()))
    cfgs.append(Config("bottleneck_spatial_cp2",
                       "apex_tpu.contrib.bottleneck.bottleneck",
                       _bottleneck_cfg()))
    cfgs.append(Config("w8_matmul_suite", "apex_tpu.quant.kernels",
                       _w8_matmul_cfg()))
    cfgs.append(Config("gpt_paged_prefill_step", "apex_tpu.serving.decode",
                       _paged_serving_cfg("prefill")))
    cfgs.append(Config("gpt_paged_chunk_prefill_step",
                       "apex_tpu.serving.decode",
                       _paged_serving_cfg("chunk_prefill")))
    cfgs.append(Config("gpt_paged_decode_step", "apex_tpu.serving.decode",
                       _paged_serving_cfg("decode")))
    cfgs.append(Config(
        "paged_decode_attention_medium",
        "apex_tpu.transformer.functional.paged_attention",
        _paged_attention_cfg()))
    for which in ("chunk", "step"):
        cfgs.append(Config(
            f"gated_delta_{which}_7b",
            "apex_tpu.transformer.functional.gated_delta",
            _gated_delta_cfg(which)))
    cfgs.append(Config("ssd_step_120b",
                       "apex_tpu.transformer.functional.ssd",
                       _nemotron_kernel_cfg("ssd_step")))
    cfgs.append(Config("moe_gmm_120b",
                       "apex_tpu.transformer.functional.moe",
                       _nemotron_kernel_cfg("gmm")))
    cfgs.append(Config("mla_decode_671b",
                       "apex_tpu.transformer.functional.mla_attention",
                       _deepseek_kernel_cfg("mla")))
    for which in ("gate_up", "down"):
        cfgs.append(Config(f"moe_gmm_{which}_671b",
                           "apex_tpu.transformer.functional.moe",
                           _deepseek_kernel_cfg(which)))
    cfgs.append(Config("paged_window_decode_236b",
                       "apex_tpu.transformer.functional.paged_attention",
                       _exaone_kernel_cfg()))
    for which in ("chunk", "step"):
        cfgs.append(Config(f"kda_{which}_125b",
                           "apex_tpu.transformer.functional.gated_delta",
                           _kda_cfg(which)))
    cfgs.append(Config("dsa_index_320b",
                       "apex_tpu.transformer.functional.sparse_index",
                       _dsa_index_cfg()))
    cfgs.append(Config("gpt_spec_verify_step", "apex_tpu.serving.decode",
                       _paged_serving_cfg("verify")))
    cfgs.append(Config("gpt_tree_verify_step", "apex_tpu.serving.decode",
                       _paged_serving_cfg("tree_verify")))
    cfgs.append(Config("gpt_draft_forward_step",
                       "apex_tpu.serving.draft_model",
                       _draft_forward_cfg()))
    return cfgs


def check_repo() -> List[Finding]:
    return run_configs(repo_configs())
