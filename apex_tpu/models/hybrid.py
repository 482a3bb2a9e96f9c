"""A decoder of two kinds of layer: Gated DeltaNet (linear attention with a
recurrent state, :mod:`apex_tpu.transformer.functional.gated_delta`) in a
fixed number of layers, then one layer of full causal softmax attention, the
period repeated (the ``olmo_hybrid`` family: three linear layers to one full).

Shared by both kinds: no biases, a SwiGLU MLP, RMSNorm on the sub-layer's
OUTPUT before the residual add (``h = x + norm(mixer(x))``, ``h = h +
norm(mlp(h))``: the Olmo 2 / 3 placement), an untied output head, no
positional embedding of any kind on the full layers (the recurrent layers
carry the order).

*full_attention*: q, k, v projections of ``heads * head_dim``; q and k pass
an RMSNorm over their whole projected width; causal softmax attention.

*linear_attention* (arXiv:2412.06464), ``H`` heads of ``d_k`` / ``d_v``:
``q~, k~, v~`` are projections of ``H d_k``, ``H d_k``, ``H d_v`` channels;
each channel passes a causal depthwise convolution of width 4 and SiLU; per
head ``q = l2norm(q~) / sqrt(d_k)``, ``k = l2norm(k~)``; ``beta = 2
sigmoid(w_b x)`` and ``alpha = exp(-exp(A_log) softplus(w_a x + dt_bias))``
are scalars per head; the recurrence gives ``o``; the layer's output is ``W_o
[RMSNorm_{d_v}(o) * SiLU(W_g x)]``.

Parameters are stacked by place in the period and the model is scanned BY
PERIOD, so a compiled program holds one period: ``periods.linear`` is a list
of ``linear_per_period`` trees and ``periods.full`` one tree, every leaf
leading with ``(periods,)``. (One ``(periods, linear_per_period, ...)`` leaf
per parameter reads shorter and costs a copy: the scan slices a period out
and the static index into that slice is a second slice, which the compiler
materialises, 1.3 GB a period at 7 B widths.)

This file holds the blocks, once for a whole (bucket-padded) prompt and once
for one token per slot against the serving cache, and the two halves the
serving engine builds its programs from (:meth:`HybridConfig.prefill_core`,
:meth:`HybridConfig.decode_core`: the seam ``models.nemotron_h`` stands on
too); :func:`apply_hybrid` is the whole forward with no cache (the tests'
middle term between the two).
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.normalization import fused_rms_norm_affine
from apex_tpu.transformer.functional import flash_attention
from apex_tpu.transformer.functional.gated_delta import (
    CHUNK, causal_conv, conv_step, gated_delta_chunked, gated_delta_step,
)
from apex_tpu.utils.profiler import region

LINEAR, FULL = "linear_attention", "full_attention"
_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    num_periods: int = 8
    linear_per_period: int = 3       # then one full-attention layer
    num_heads: int = 30              # of the full-attention layers
    ffn_hidden_size: int = 11008
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536

    #: the serving engine keeps per-slot state beside the page pool for it
    recurrent = True

    @classmethod
    def from_layer_types(cls, layer_types, **sizes) -> "HybridConfig":
        """From a published ``layer_types`` list, which has to be whole
        periods of ``[linear_attention x n, full_attention]``."""
        types = list(layer_types)
        if FULL not in types:
            raise ValueError("layer_types holds no full_attention layer")
        n = types.index(FULL)
        period = [LINEAR] * n + [FULL]
        if n < 1 or len(types) % len(period) \
                or types != period * (len(types) // len(period)):
            raise ValueError(
                f"layer_types {types} is not whole periods of {period}")
        return cls(num_periods=len(types) // len(period),
                   linear_per_period=n, **sizes)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_linear_layers(self) -> int:
        return self.num_periods * self.linear_per_period

    @property
    def num_full_layers(self) -> int:
        return self.num_periods

    @property
    def num_layers(self) -> int:
        return self.num_linear_layers + self.num_full_layers

    @property
    def conv_channels(self) -> int:
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)

    # -- what the serving engine asks (the seam, with models.nemotron_h) ----

    @property
    def kv_layers(self) -> int:
        """Layers of the page pool: the full-attention layers."""
        return self.num_full_layers

    @property
    def kv_row_width(self) -> int:
        """Width of one cached K (or V) row."""
        return self.num_heads * self.head_dim

    def prefill_core(self, params, ids, mask, kv_dtype):
        return prefill_layers(params, self, embed(params, ids), mask,
                              kv_dtype)

    def decode_core(self, params, cache, tokens, active):
        return decode_layers(params, self, cache, tokens, active)

    def logits_of(self, params, x):
        return logits_of(params, self, x)

    def state_shapes(self, num_slots: int) -> Tuple[Tuple[int, ...], ...]:
        """(recurrent state, convolution tail) of ``num_slots`` slots."""
        n = self.num_linear_layers
        return ((n, num_slots, self.linear_heads, self.linear_key_dim,
                 self.linear_value_dim),
                (n, num_slots, self.conv_kernel - 1, self.conv_channels))

    def state_bytes_per_slot(self) -> int:
        """Bytes one prefill writes for its slot besides the pages
        (float32 state and tails)."""
        state, conv = self.state_shapes(1)
        return 4 * (math.prod(state) + math.prod(conv))


def olmo_hybrid_7b() -> HybridConfig:
    return HybridConfig()


def hybrid_tiny() -> HybridConfig:
    return HybridConfig(vocab_size=512, hidden_size=64, num_periods=1,
                        num_heads=2, ffn_hidden_size=128, linear_heads=2,
                        linear_key_dim=16, linear_value_dim=32,
                        max_position_embeddings=256)


# ---------------------------------------------------------------------------
# init: full params, stacked per kind with the period axis leading
# ---------------------------------------------------------------------------

def init_hybrid(key: jax.Array, cfg: HybridConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Random parameters: matrices ``N(0, 1/fan_in)``, the embedding 0.02,
    norms 1; the decay parameters as the layer's paper sets them (``A``
    uniform in 0..16, ``dt`` log-uniform in 0.001..0.1, its inverse softplus
    as ``dt_bias``) with small ``w_a``, ``w_b`` rows, so that ``A_log`` and
    ``dt_bias`` set the heads' time constants."""
    h, f = cfg.hidden_size, cfg.ffn_hidden_size
    nh, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim

    def dense(k, fan_in, *shape, gain=1.0):
        return {"kernel": (gain * math.sqrt(1.0 / fan_in)
                           * jax.random.normal(k, shape)).astype(dtype)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def mlp(k):
        k1, k2 = jax.random.split(k)
        return {"norm2": norm(h), "gate_up": dense(k1, h, h, 2 * f),
                "down": dense(k2, f, f, h)}

    def linear_layer(k):
        ks = jax.random.split(k, 7)
        dt = jnp.exp(jax.random.uniform(
            ks[4], (nh,), minval=math.log(1e-3), maxval=math.log(0.1)))
        return {"in_proj": dense(ks[0], h, h, cfg.conv_channels + nh * dv),
                "ab_proj": dense(ks[1], h, h, 2 * nh, gain=0.1),
                "conv": {"weight": (0.5 * jax.random.normal(
                    ks[2], (cfg.conv_kernel, cfg.conv_channels))
                ).astype(dtype)},
                "a_log": jnp.log(jax.random.uniform(
                    ks[3], (nh,), minval=0.0, maxval=16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": norm(dv),
                "out": dense(ks[5], nh * dv, nh * dv, h),
                "norm1": norm(h), **mlp(ks[6])}

    def full_layer(k):
        ks = jax.random.split(k, 3)
        return {"qkv": dense(ks[0], h, h, 3 * h), "q_norm": norm(h),
                "k_norm": norm(h), "out": dense(ks[1], h, h, h),
                "norm1": norm(h), **mlp(ks[2])}

    k_emb, k_head, k_lin, k_full = jax.random.split(key, 4)
    p, n = cfg.num_periods, cfg.linear_per_period
    k_lin = jax.random.split(k_lin, p * n).reshape(n, p, -1)
    return {
        "embedding": {"word": {"embedding": (0.02 * jax.random.normal(
            k_emb, (cfg.vocab_size, h))).astype(dtype)}},
        "periods": {
            "linear": [jax.vmap(linear_layer)(k_lin[j]) for j in range(n)],
            "full": jax.vmap(full_layer)(jax.random.split(k_full, p))},
        "final_norm": norm(h),
        "head": dense(k_head, h, h, cfg.vocab_size),
    }


# ---------------------------------------------------------------------------
# what both kinds of layer share
# ---------------------------------------------------------------------------

def _dense(p, x):
    """``x @ kernel``: the product's inputs in the kernel's dtype (bfloat16
    as served: one MXU pass), summed and handed on in float32. The residual
    stream and everything between two products stay float32: with bfloat16
    between them too, the program's distance from the float32 reference is
    no smaller than that of a recurrent state kept in bfloat16, which the
    benchmark's comparison has to tell apart (PERF.md, PR 27)."""
    kernel = p["kernel"]
    return jnp.dot(x.astype(kernel.dtype), kernel,
                   preferred_element_type=jnp.float32)


def _rms(p, x, eps):
    return fused_rms_norm_affine(x, p["weight"], x.shape[-1], eps)


def _mlp(lp, x, cfg):
    """``x + norm2(SwiGLU(x))``: the MLP half of either kind of layer."""
    with region("mlp"):
        gate, up = jnp.split(_dense(lp["gate_up"], x), 2, axis=-1)
        return x + _rms(lp["norm2"],
                        _dense(lp["down"], jax.nn.silu(gate) * up),
                        cfg.rms_norm_eps)


def embed(params, ids):
    with region("embed"):
        return jnp.take(params["embedding"]["word"]["embedding"], ids,
                        axis=0).astype(jnp.float32)


def logits_of(params, cfg, x):
    """Final norm and the untied head: (rows, hidden) -> float32 logits."""
    with region("head"):
        return _dense(params["head"],
                      _rms(params["final_norm"], x, cfg.rms_norm_eps))


# ---------------------------------------------------------------------------
# the linear-attention layer
# ---------------------------------------------------------------------------

def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def _gates(lp, x, cfg):
    """(log alpha, beta), float32 ``(rows, heads)`` each."""
    a, b = jnp.split(_dense(lp["ab_proj"], x), 2, axis=-1)
    log_alpha = -jnp.exp(lp["a_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    return log_alpha, 2.0 * jax.nn.sigmoid(b)


def _heads(conv_out, cfg):
    """Convolved channels (rows, C) -> q, k (rows, H, d_k), v (rows, H,
    d_v), float32, after SiLU, the norms and q's scale."""
    nh, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    y = jax.nn.silu(conv_out)
    rows = y.shape[0]
    q = _l2norm(y[:, :nh * dk].reshape(rows, nh, dk)) / math.sqrt(dk)
    k = _l2norm(y[:, nh * dk:2 * nh * dk].reshape(rows, nh, dk))
    return q, k, y[:, 2 * nh * dk:].reshape(rows, nh, dv)


def _gated_out(lp, o, gate, cfg):
    """``W_o [RMSNorm_{d_v}(o) * SiLU(gate)]``: ``o`` (rows, H, d_v),
    ``gate`` (rows, H * d_v)."""
    rows = o.shape[0]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
    o = (o * lp["o_norm"]["weight"]).reshape(rows, -1)
    return _dense(lp["out"], o * jax.nn.silu(gate))


def linear_block_prefill(lp, x, cfg, mask):
    """One linear-attention layer over a prompt: ``x`` (s, hidden), ``mask``
    (s,) with 1 = real token and the padding at the end. Returns ``(x',
    state (H, d_k, d_v) float32, tail (w-1, C))``, the state and the
    convolution tail as the prompt's last real token leaves them: padded
    positions decay nothing (``log alpha = 0``) and write nothing (``beta =
    0``)."""
    with region("mixer"):
        s = x.shape[0]
        proj = _dense(lp["in_proj"], x)
        chan = cfg.conv_channels
        real = mask.astype(bool)
        conv_out, tail = causal_conv(
            proj[:, :chan], lp["conv"]["weight"].astype(jnp.float32),
            jnp.sum(mask))
        q, k, v = _heads(conv_out, cfg)
        log_alpha, beta = _gates(lp, x, cfg)
        log_alpha = jnp.where(real[:, None], log_alpha, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
        pad = -s % CHUNK

        def lead(t):    # (s, H, ...) -> (H, s padded to whole chunks, ...)
            t = jnp.moveaxis(t, 1, 0)
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

        o, state = gated_delta_chunked(lead(q), lead(k), lead(v),
                                       lead(log_alpha), lead(beta))
        o = jnp.moveaxis(o[:, :s], 0, 1)
        y = _gated_out(lp, o, proj[:, chan:], cfg)
        x = x + _rms(lp["norm1"], y, cfg.rms_norm_eps)
    return _mlp(lp, x, cfg), state, tail


def linear_block_decode(lp, x, cfg, state, conv, layer, active):
    """One token for every slot: ``x`` (b, hidden); ``state`` and ``conv``
    the WHOLE stacked arrays (``HybridConfig.state_shapes``), of which layer
    ``layer`` (a traced scalar) is read and written. Returns ``(x', state',
    conv')``."""
    with region("mixer"):
        proj = _dense(lp["in_proj"], x)
        chan = cfg.conv_channels
        tail = lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
        conv_out, new_tail = conv_step(
            proj[:, :chan], tail, lp["conv"]["weight"].astype(jnp.float32))
        new_tail = jnp.where(active[:, None, None], new_tail, tail)
        conv = lax.dynamic_update_index_in_dim(conv, new_tail, layer, 0)
        q, k, v = _heads(conv_out, cfg)
        log_alpha, beta = _gates(lp, x, cfg)
        o, state = gated_delta_step(q, k, v, log_alpha, beta, state, layer,
                                    active)
        y = _gated_out(lp, o, proj[:, chan:], cfg)
        x = x + _rms(lp["norm1"], y, cfg.rms_norm_eps)
    return _mlp(lp, x, cfg), state, conv


# ---------------------------------------------------------------------------
# the full-attention layer
# ---------------------------------------------------------------------------

def _qkv(lp, x, cfg):
    """(rows, hidden) -> q, k, v (rows, heads * head_dim), q and k normed
    over their whole width: heads side by side, a page's row layout."""
    q, k, v = jnp.split(_dense(lp["qkv"], x), 3, axis=-1)
    return (_rms(lp["q_norm"], q, cfg.rms_norm_eps),
            _rms(lp["k_norm"], k, cfg.rms_norm_eps), v)


def full_block_prefill(lp, x, cfg, mask, kv_dtype):
    """One full-attention layer over a prompt. Returns ``(x', k, v)``, the
    (s, heads * head_dim) rows the cache keeps, in ``kv_dtype``, the cache's:
    the prompt attends to the rows decode will read."""
    with region("attention"):
        s = x.shape[0]
        q, k, v = (t.astype(kv_dtype) for t in _qkv(lp, x, cfg))

        def heads(t):
            return t.reshape(1, s, cfg.num_heads, cfg.head_dim).transpose(
                0, 2, 1, 3)

        ctx = flash_attention(heads(q), heads(k), heads(v), mask[None, :],
                              causal=True,
                              softmax_scale=1.0 / math.sqrt(cfg.head_dim))
        y = _dense(lp["out"], ctx.transpose(0, 2, 1, 3).reshape(s, -1))
        x = x + _rms(lp["norm1"], y, cfg.rms_norm_eps)
    return _mlp(lp, x, cfg), k, v


def full_block_decode(lp, x, cfg, k_pool, v_pool, layer, block_tables, pos):
    """One token for every slot against the paged pool, read in place by
    ``apex_paged_decode_fwd``; ``layer`` indexes the pool's leading axis
    (the full layers only). Returns ``(x', k_row, v_row)`` for the caller
    to write at ``pos``."""
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )

    with region("attention"):
        q, k, v = _qkv(lp, x, cfg)
        k, v = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
        ctx = paged_decode_attention(
            q[:, None], k[:, None], v[:, None], k_pool, v_pool, block_tables,
            pos, layer, heads=cfg.num_heads)[:, 0]
        y = _dense(lp["out"], ctx)
        x = x + _rms(lp["norm1"], y, cfg.rms_norm_eps)
    return _mlp(lp, x, cfg), k, v


# ---------------------------------------------------------------------------
# the whole forward, no cache
# ---------------------------------------------------------------------------

def prefill_layers(params, cfg: HybridConfig, x, mask,
                   kv_dtype=jnp.float32):
    """Every layer over one prompt, scanned by period: ``x`` (s, hidden).
    Returns ``(x', states (linear layers, H, d_k, d_v), tails (linear
    layers, w-1, C), k, v (full layers, s, heads * head_dim))``."""

    def period(x, pp):
        states, tails = [], []
        for lp in pp["linear"]:
            x, state, tail = linear_block_prefill(lp, x, cfg, mask)
            states.append(state)
            tails.append(tail)
        x, k, v = full_block_prefill(pp["full"], x, cfg, mask, kv_dtype)
        return x, (jnp.stack(states), jnp.stack(tails), k, v)

    x, (states, tails, k, v) = lax.scan(period, x, params["periods"])
    return (x, states.reshape(-1, *states.shape[2:]),
            tails.reshape(-1, *tails.shape[2:]), k, v)


def decode_layers(params, cfg: HybridConfig, cache, tokens, active):
    """One token for every slot against the serving cache
    (``serving.cache.HybridKVCache``), scanned by period: each linear layer
    steps its layer of the stacked recurrent state in place
    (``apex_gdn_decode_fwd``; the state and the convolution tails are carries
    of the scan, never copied), each full layer attends over the pool in
    place. Returns ``(x (slots, hidden), state', conv', counters' (none
    here), k_rows, v_rows (full layers, slots, heads * head_dim))`` for the
    engine to write."""
    pos = cache.lengths
    bt = cache.block_tables
    x = embed(params, tokens)
    n = cfg.linear_per_period

    def period(carry, pp_at):
        x, state, conv = carry
        pp, at = pp_at
        for j, lp in enumerate(pp["linear"]):
            x, state, conv = linear_block_decode(
                lp, x, cfg, state, conv, at * n + j, active)
        x, k_row, v_row = full_block_decode(
            pp["full"], x, cfg, cache.k, cache.v, at, bt, pos)
        return (x, state, conv), (k_row, v_row)

    (x, state, conv), (k_rows, v_rows) = lax.scan(
        period, (x, cache.state, cache.conv),
        (params["periods"],
         jnp.arange(cache.k.shape[0], dtype=jnp.int32)))
    return x, state, conv, None, k_rows, v_rows


def apply_hybrid(params, cfg: HybridConfig, ids):
    """(s,) token ids -> (s, vocab) float32 logits."""
    x = embed(params, ids)
    x = prefill_layers(params, cfg, x, jnp.ones(ids.shape, jnp.int32))[0]
    return logits_of(params, cfg, x)
