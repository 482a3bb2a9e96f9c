"""Minimal functional NN layers for the in-tree model zoo.

The reference ships no layer library (its models come from torchvision /
Megatron); these exist so the examples, benchmarks and tests are
self-contained. Conventions: params are nested dicts of arrays; layers are
``init_*(key, ...) -> params`` + ``apply`` functions; compute follows the
AMP policy of the caller (params cast outside, stats in fp32).
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.amp.autocast import cast_args


def lecun_normal(key, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * math.sqrt(1.0 / fan_in)


def kaiming_normal(key, shape, fan_in, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * math.sqrt(2.0 / fan_in)


def trunc_normal(key, shape, stddev=0.02, dtype=jnp.float32):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) * stddev


# -- dense ------------------------------------------------------------------

def init_dense(key, in_features: int, out_features: int, *, bias: bool = True,
               init=trunc_normal, dtype=jnp.float32) -> dict:
    p = {"kernel": init(key, (in_features, out_features), dtype=dtype)
         if init is trunc_normal
         else init(key, (in_features, out_features), in_features, dtype)}
    if bias:
        p["bias"] = jnp.zeros((out_features,), dtype)
    return p


def dense(params: dict, x: jax.Array) -> jax.Array:
    # No explicit preferred_element_type: widening the output would make the
    # transpose (backward) call dot/conv with an f32 cotangent against a
    # bf16 kernel (dtype-mismatch); the MXU accumulates bf16 matmuls in f32
    # internally regardless.
    # O1: under amp.autocast the op-policy casts both operands to the
    # compute dtype (dense is on FP16_FUNCS); outside the context this is
    # the identity (ref: apex/amp/wrap.py cached_cast over torch.nn.linear)
    x, kernel = cast_args("dense", x, params["kernel"])
    y = jnp.dot(x, kernel.astype(x.dtype))
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


# -- conv (NHWC) ------------------------------------------------------------

def init_conv(key, in_ch: int, out_ch: int, kernel: Tuple[int, int],
              dtype=jnp.float32) -> dict:
    fan_in = in_ch * kernel[0] * kernel[1]
    return {"kernel": kaiming_normal(
        key, kernel + (in_ch, out_ch), fan_in, dtype)}


def conv(params: dict, x: jax.Array, stride: int = 1,
         padding="SAME") -> jax.Array:
    x, kernel = cast_args("conv2d", x, params["kernel"])
    return lax.conv_general_dilated(
        x, kernel.astype(x.dtype),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


# -- batch norm -------------------------------------------------------------

def init_batchnorm(ch: int) -> Tuple[dict, dict]:
    """Returns (params, running_state). Params fp32 (AMP keep_batchnorm_fp32
    default), running stats fp32."""
    params = {"scale": jnp.ones((ch,), jnp.float32),
              "bias": jnp.zeros((ch,), jnp.float32)}
    state = {"mean": jnp.zeros((ch,), jnp.float32),
             "var": jnp.ones((ch,), jnp.float32)}
    return params, state


def batchnorm(params: Optional[dict], state: Optional[dict],
              x: jax.Array, *, train: bool,
              momentum: float = 0.9, eps: float = 1e-5,
              axis_name: Optional[str] = None,
              axis_index_groups=None
              ) -> Tuple[jax.Array, Optional[dict]]:
    """BatchNorm over all but the channel (last) axis.

    ``axis_name``: when set and running inside shard_map/pmap, batch
    statistics are averaged across that mesh axis — this is the SyncBN hook
    used by ``apex_tpu.parallel.SyncBatchNorm`` (ref:
    ``apex/parallel/sync_batchnorm.py``). ``axis_index_groups`` limits the
    sync to rank subgroups (the groupbn ``bn_group`` hook).

    ``momentum`` is the KEEP fraction (new = momentum·old +
    (1-momentum)·batch); the module wrappers expose torch's update
    fraction and pass ``1 - momentum`` here.

    ``params=None`` skips the affine transform (``affine=False``);
    ``state=None`` means no running stats are tracked — batch statistics
    are used even when ``train=False`` (torch's
    ``track_running_stats=False`` semantics).
    """
    x32 = x.astype(jnp.float32)
    use_batch_stats = train or state is None
    if use_batch_stats:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x32, axis=axes)
        mean_sq = jnp.mean(jnp.square(x32), axis=axes)
        if axis_name is not None:
            mean = lax.pmean(mean, axis_name,
                             axis_index_groups=axis_index_groups)
            mean_sq = lax.pmean(mean_sq, axis_name,
                                axis_index_groups=axis_index_groups)
        var = mean_sq - jnp.square(mean)
        if train and state is not None:
            n = x32.size // x32.shape[-1]
            if axis_name is not None:
                n = n * lax.psum(1, axis_name,
                                 axis_index_groups=axis_index_groups)
            unbiased = var * (n / max(n - 1, 1))
            new_state = {
                "mean": momentum * state["mean"] + (1 - momentum) * mean,
                "var": momentum * state["var"] + (1 - momentum) * unbiased,
            }
        else:
            new_state = state
    else:
        mean, var = state["mean"], state["var"]
        new_state = state
    y = (x32 - mean) * lax.rsqrt(var + eps)
    if params is not None:
        y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype), new_state


# -- embedding --------------------------------------------------------------

def init_embedding(key, vocab: int, features: int,
                   dtype=jnp.float32) -> dict:
    return {"embedding": trunc_normal(key, (vocab, features), dtype=dtype)}


def embedding(params: dict, ids: jax.Array, dtype=None) -> jax.Array:
    table = params["embedding"]
    if dtype is not None:
        table = table.astype(dtype)
    return jnp.take(table, ids, axis=0)


# -- hyper-connections ------------------------------------------------------

def sinkhorn(m, iters: int):
    """``m`` (..., n, n) positive -> its rows, then its columns, scaled to
    sum 1, ``iters`` times over (unrolled): doubly stochastic in the limit."""
    for _ in range(iters):
        m = m / jnp.sum(m, -1, keepdims=True)
        m = m / jnp.sum(m, -2, keepdims=True)
    return m


def init_hyper_connection(key, streams: int, width: int, spread: float = 0.0):
    """One sub-layer's maps: ``proj`` (streams * width, 2 n + n * n) float32,
    the scalars ``a_*`` 0.01, and biases that make ``Hpre = 1/n``, ``Hpost =
    1`` and ``Hres`` near the identity; with a ``spread`` the biases are
    drawn ``N(., spread)`` around those, so that no stream idles."""
    n = streams
    k_proj, k_pre, k_post, k_res = jax.random.split(key, 4)
    fan_in = n * width
    return {
        "proj": lecun_normal(k_proj, (fan_in, 2 * n + n * n), fan_in),
        "a_pre": jnp.float32(0.01), "a_post": jnp.float32(0.01),
        "a_res": jnp.float32(0.01),
        "b_pre": -jnp.log(n - 1.0) + spread * jax.random.normal(k_pre, (n,)),
        "b_post": spread * jax.random.normal(k_post, (n,)),
        "b_res": 4.0 * jnp.eye(n) + spread * jax.random.normal(k_res, (n, n)),
    }


def hyper_open(streams, hp, *, iters: int = 20, eps: float = 1e-6):
    """The three maps of one sub-layer from ``streams`` (rows, n, width), and
    the stream it reads: ``(Hpre streams (rows, width), Hpost (rows, n), Hres
    (rows, n, n))``; :func:`hyper_connect` has the equations."""
    rows, n, _ = streams.shape
    flat = streams.reshape(rows, -1)
    flat = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    maps = jnp.dot(flat, hp["proj"], precision=lax.Precision.HIGHEST)
    pre = jax.nn.sigmoid(hp["a_pre"] * maps[:, :n] + hp["b_pre"])
    post = 2.0 * jax.nn.sigmoid(hp["a_post"] * maps[:, n:2 * n] + hp["b_post"])
    res = sinkhorn(jnp.exp(hp["a_res"] * maps[:, 2 * n:].reshape(rows, n, n)
                           + hp["b_res"]), iters)
    # sums over the n streams written out: nothing of (rows, n, n, width)
    return sum(pre[:, j, None] * streams[:, j] for j in range(n)), post, res


def hyper_close(streams, opened, y):
    """``Hres streams + Hpost^T y``: what :func:`hyper_open` ``opened`` and
    what the sub-layer made of the stream it read, back into the streams."""
    _, post, res = opened
    return post[..., None] * y[:, None] + sum(
        res[:, :, j, None] * streams[:, j, None]
        for j in range(streams.shape[1]))


def hyper_connect(streams, hp, f, *, iters: int = 20, eps: float = 1e-6):
    """One sub-layer ``f`` on ``n`` residual streams (manifold-constrained
    hyper-connections, arXiv 2512.24880): ``streams`` (rows, n, width)
    float32; with ``x~`` the rows' ``n * width`` values under an RMS norm
    without gain,

        Hpre  = sigmoid(a_pre (x~ P_pre) + b_pre)              (rows, n)
        Hpost = 2 sigmoid(a_post (x~ P_post) + b_post)         (rows, n)
        Hres  = sinkhorn(exp(a_res mat(x~ P_res) + b_res))     (rows, n, n)
        streams' = Hres streams + Hpost^T f(Hpre streams)

    ``f`` takes the ONE mixed stream (rows, width) and returns what the
    sub-layer adds (its norm in front is its own), or a tuple whose first
    entry that is; the rest is handed back after ``streams'``. The maps are
    float32 products at full precision; plain ``jax.numpy``
    (:func:`hyper_open`, ``f``, :func:`hyper_close`)."""
    opened = hyper_open(streams, hp, iters=iters, eps=eps)
    out = f(opened[0])
    if not isinstance(out, tuple):
        return hyper_close(streams, opened, out)
    return (hyper_close(streams, opened, out[0]),) + out[1:]
