"""Dequant-fused int8 weight-only matmul (Pallas).

The APX6xx cost tier proves decode is pure bandwidth: at the r10 ragged
medium shape, ~0.71 GB of the 1.68 GB step is the bf16 parameter read.
Per-output-channel symmetric int8 weights halve that term; this module
is the compute side of the trade — the int8 tiles are dequantized IN
REGISTERS (``wq.astype(f32) * scale``) straight into an fp32-accumulated
MXU dot, so HBM only ever sees the int8 copy plus a tiny fp32 scale
vector. The apex O2 discipline transplanted to inference: high-precision
master (fp32 scales, >= fp32 accumulators), low-precision streaming copy.

Quantization contracts (pinned by the APX106 AST check and the APX5xx
trace tier):

- scale tensors are fp32 — never the compute dtype;
- the dequant accumulator is fp32 (``preferred_element_type``), whatever
  dtype the activations arrive in;
- int8 stores round to nearest via an explicit ``jnp.round`` — a bare
  ``astype(int8)`` truncates toward zero and doubles the mean error.

Two weight layouts, one contract:

- ``w8_matmul``: activations ``(..., K)`` against ``wq (K, N)`` with
  ``scale (N,)`` — the Column/RowParallel kernel layout;
- ``w8_matmul_nk``: ``wq (N, K)`` row-major over output channels — the
  tied-embedding logits head ``hidden @ table.T`` without ever
  materializing a transposed int8 table.

The grid runs over N tiles only (whole-M, whole-K blocks): decode M is
the slot count and K the hidden size, both comfortably VMEM-resident,
while N (ffn width, vocab) is what scales.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.utils.platform import pallas_interpret

# N-tile candidates, largest first. 384 = 3 x 128 keeps the lane dim a
# multiple of the int8 min tile (32, 128) and divides the GPT-2 padded
# vocab (50304 = 131 x 384); a non-dividing N falls back to one whole
# tile (tiny configs — their widths are VMEM-trivial).
_BLOCK_N = (512, 384, 256, 128)


def _block_n(n: int) -> int:
    for cand in _BLOCK_N:
        if n % cand == 0:
            return cand
    return n


def _w8_matmul_kernel(x_ref, wq_ref, scale_ref, bias_ref, out_ref):
    # dequant in registers: int8 tile * fp32 per-output-channel scale,
    # accumulated fp32 regardless of the activation dtype
    w = wq_ref[...].astype(jnp.float32) * scale_ref[...].astype(
        jnp.float32)
    acc = jnp.dot(x_ref[...].astype(jnp.float32), w,
                  preferred_element_type=jnp.float32)
    acc = acc + bias_ref[...].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def _w8_matmul_nobias_kernel(x_ref, wq_ref, scale_ref, out_ref):
    w = wq_ref[...].astype(jnp.float32) * scale_ref[...].astype(
        jnp.float32)
    out_ref[...] = jnp.dot(
        x_ref[...].astype(jnp.float32), w,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _w8_matmul_nk_kernel(x_ref, wq_ref, scale_ref, out_ref):
    # wq block is (bn, K) output-channel-major: dequant rows, contract
    # both operands on their last dim — the logits head never transposes
    # the int8 table
    w = wq_ref[...].astype(jnp.float32) * scale_ref[...].astype(
        jnp.float32).T
    out_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _check_operands(x, wq, scale, k, n):
    if wq.dtype != jnp.int8:
        raise ValueError(f"wq must be int8, got {wq.dtype}")
    if scale.dtype != jnp.float32:
        raise ValueError(f"scale must be fp32, got {scale.dtype}")
    if scale.shape != (n,):
        raise ValueError(f"scale {scale.shape} != per-output-channel "
                         f"({n},)")
    if x.shape[-1] != k:
        raise ValueError(f"x last dim {x.shape[-1]} != contraction {k}")


def w8_matmul(x, wq, scale, bias=None, out_dtype=None, interpret=None):
    """``x (..., K) @ dequant(wq (K, N), scale (N,)) [+ bias (N,)]``.

    fp32 accumulation, output in ``out_dtype`` (default: ``x.dtype``).
    """
    k, n = wq.shape
    _check_operands(x, wq, scale, k, n)
    out_dtype = jnp.dtype(x.dtype if out_dtype is None else out_dtype)
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    bn = _block_n(n)
    scale2 = scale.reshape(1, n)
    if bias is None:
        with jax.named_scope("apex_w8_matmul"):
            out = pl.pallas_call(
                _w8_matmul_nobias_kernel,
                grid=(n // bn,),
                in_specs=[
                    pl.BlockSpec((m, k), lambda i: (0, 0)),
                    pl.BlockSpec((k, bn), lambda i: (0, i)),
                    pl.BlockSpec((1, bn), lambda i: (0, i)),
                ],
                out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
                out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
                interpret=pallas_interpret(interpret),
                name="apex_w8_matmul",
            )(x2, wq, scale2)
    else:
        bias2 = bias.reshape(1, n)
        with jax.named_scope("apex_w8_matmul_bias"):
            out = pl.pallas_call(
                _w8_matmul_kernel,
                grid=(n // bn,),
                in_specs=[
                    pl.BlockSpec((m, k), lambda i: (0, 0)),
                    pl.BlockSpec((k, bn), lambda i: (0, i)),
                    pl.BlockSpec((1, bn), lambda i: (0, i)),
                    pl.BlockSpec((1, bn), lambda i: (0, i)),
                ],
                out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
                out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
                interpret=pallas_interpret(interpret),
                name="apex_w8_matmul_bias",
            )(x2, wq, scale2, bias2)
    return out.reshape(lead + (n,))


def w8_matmul_nk(x, wq, scale, out_dtype=jnp.float32, interpret=None):
    """``x (..., K) @ dequant(wq (N, K), scale (N,)).T`` — the logits
    head against the output-channel-major int8 word table. fp32 out by
    default (the logits contract)."""
    n, k = wq.shape
    _check_operands(x, wq, scale, k, n)
    out_dtype = jnp.dtype(out_dtype)
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    bn = _block_n(n)
    with jax.named_scope("apex_w8_matmul_nk"):
        out = pl.pallas_call(
            _w8_matmul_nk_kernel,
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((m, k), lambda i: (0, 0)),
                pl.BlockSpec((bn, k), lambda i: (i, 0)),
                pl.BlockSpec((1, bn), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            interpret=pallas_interpret(interpret),
            name="apex_w8_matmul_nk",
        )(x2, wq, scale.reshape(1, n))
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# int8 KV page quantization (plain jnp: the attention gather stays an
# XLA einsum — the byte win is the int8 pool invar, priced at the jit
# boundary by the cost tier, not a fused kernel)
# ---------------------------------------------------------------------------

def kv_quantize(t):
    """Quantize KV page tiles per page per head: ``t (..., nh, page,
    hd)`` -> ``(int8 tiles, fp32 scales (..., nh))``. Symmetric amax
    over each head's page; all-zero pages keep scale 0 and quantize to
    exact zeros (the dequant of a 0-scale page is exactly zero, so the
    NULL page stays pristine under any gather)."""
    ft = t.astype(jnp.float32)
    amax = jnp.max(jnp.abs(ft), axis=(-2, -1))
    scale = (amax / 127.0).astype(jnp.float32)
    safe = jnp.where(scale > 0, scale, 1.0)[..., None, None]
    q = jnp.clip(jnp.round(ft / safe), -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q, scale):
    """``q (..., nh, page, hd)`` int8 * ``scale (..., nh)`` fp32 ->
    fp32 tiles."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None,
                                                             None]
