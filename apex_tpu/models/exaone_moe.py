"""A decoder whose attention layers come in two kinds, by a fixed pattern
(the ``exaone_moe`` family: ``L L L G`` repeated): a SLIDING layer attends
the last ``window`` positions (query ``i`` sees key ``j`` iff ``0 <= i - j <
window``), a FULL layer every position before it. Grouped-query attention in
both (``num_heads`` query heads over ``num_kv_heads`` K/V heads of
``head_dim``); the MLP is a dense SwiGLU in the first ``first_k_dense``
layers and a sparse expert layer after them.

*What a cache keeps.* A full layer keeps every position (the serving engine's
page pool, walked by the host's block table). A sliding layer keeps ``window``
positions whatever the context: ``ring_pages`` pages a slot in a second pool
whose table is a static cycle (``serving.cache.WindowKVCache``). Both are
read in place by the one paged decode kernel, the sliding layers' call with a
lower bound (``apex_paged_window_decode_fwd``); over a prompt the sliding
layers run ``flash_attention(window=)``, which visits the band's tiles only.

*What the published config does not say* is the family's convention
(EXAONE 4.0, of which ``exaone_moe`` is the successor), written once here and
once in the benchmark's plain reference, and stated in the ``assumed`` block
of the benchmark's configuration file: each sub-layer's RMSNorm stands on its
OUTPUT (``x + norm(f(x))``, none in front: :func:`_sublayer`); an RMSNorm
over each head's values of q and of k, before rotary; rotary on the SLIDING
layers only (:func:`_qkv`); the window counts the token itself (``0 <= i - j
< sliding_window``). Real weights follow one convention: where the published
modelling code says otherwise, those lines change.

*Expert layer*: ``models.deepseek``'s, imported and not written again
(sigmoid scores in float32, a choice-only bias, one group here, normalised
weights times ``routed_scaling_factor``, SwiGLU experts and one shared
expert; the chip HOLDS experts ``expert_offset .. + experts_held - 1`` and
adds up their part alone). A final RMSNorm and an untied head; no biases.
The multi-token-prediction module of the published model is not part of
this file.

Parameters: ``dense`` is a list of the leading dense layers' trees
(unrolled), ``moe`` one tree whose every leaf leads with ``(expert
layers,)``. The expert layers are scanned BY PERIOD of the pattern, so that
each call site knows statically which kind of layer it is; what is left over
after the whole periods is unrolled. ``qkv`` and ``gate_up`` are stored
fused.

This file holds the blocks, once over a (bucket-padded) prompt and once for
one token per slot against the serving cache
(:meth:`ExaoneMoeConfig.prefill_core`, :meth:`ExaoneMoeConfig.decode_core`:
``serving.decode``, "the seam"), and :func:`apply`, the whole forward with no
cache. Precision as ``models.nemotron_h`` / ``models.deepseek``: the inputs
of every product into a bfloat16 matrix as two bfloat16 terms, float32
between two products, the router's product whole in float32; K/V rows
rounded to the cache's dtype before the prompt attends to them, in float32
as decode does.
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.models.deepseek import _held, expert_parts, swiglu_mlp
from apex_tpu.models.nemotron_h import _dense, _rms, embed
from apex_tpu.transformer.functional import flash_attention
from apex_tpu.transformer.functional.paged_attention import (
    paged_decode_attention,
)
from apex_tpu.utils.profiler import region

# rows of a prompt that one pass of the dense MLP takes: its fused gate and
# up are (2 * rows, 2 * ffn) float32, 1.2 GB at 8192 rows of the published
# widths
_MLP_ROWS = 1024

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_layers: int = 48
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 12
    first_k_dense: int = 1
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 128
    ffn_size: int = 18432            # the dense layers' SwiGLU
    moe_ffn_size: int = 2048         # each routed expert's
    shared_experts: int = 1          # of moe_ffn_size each, fused into one
    num_experts: int = 128           # the router's width
    experts_per_token: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    experts_held: int = 128          # of num_experts, on this chip
    expert_offset: int = 0           # the first of them
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144

    #: the seam (``serving.decode``): no per-slot state beside the pools, K
    #: and V apart, and a window (:attr:`window`)
    recurrent = False
    latent = False
    pools = "window"

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"{self.num_layers} layers of types "
                             f"{self.layer_types}")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(f"{self.first_k_dense} dense layers of "
                             f"{self.num_layers}")
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError(f"{self.num_heads} heads of {self.head_dim} "
                             f"over {self.num_kv_heads}")
        if not 0 <= self.expert_offset <= self.num_experts \
                - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")
        if len(set(self.layer_types)) < 2:
            raise ValueError("layers of one kind alone are not this file")

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    def windowed(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    @property
    def period(self) -> int:
        """The shortest distance after which ``layer_types`` repeat (4 for
        ``L L L G``)."""
        kinds = self.layer_types
        return next(p for p in range(1, len(kinds) + 1)
                    if kinds[p:] == kinds[:-p])

    @property
    def pattern(self) -> Tuple[bool, ...]:
        """Which of one period's expert layers are sliding, from the first
        expert layer on."""
        return tuple(self.windowed(i) for i in range(
            self.first_k_dense,
            min(self.first_k_dense + self.period, self.num_layers)))

    # -- what the serving engine asks (the seam) -----------------------------

    @property
    def window(self) -> int:
        """Positions a sliding layer sees, the token itself among them."""
        return self.sliding_window

    @property
    def kv_layers(self) -> int:
        """Layers of the page pool: the full ones."""
        return sum(not self.windowed(i) for i in range(self.num_layers))

    @property
    def window_layers(self) -> int:
        """Layers of the window pool: the sliding ones."""
        return self.num_layers - self.kv_layers

    @property
    def kv_row_width(self) -> int:
        return self.num_kv_heads * self.head_dim

    def pool_layer(self, layer: int) -> int:
        """``layer``'s index in the pool of its kind."""
        return sum(self.windowed(i) == self.windowed(layer)
                   for i in range(layer))

    def counter_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """As ``DeepseekConfig.counter_shapes``: assignments per held expert
        and held experts hit, per expert layer, and the decode steps."""
        n = self.moe_layers
        return {"moe_load": (n, self.experts_held), "moe_hit": (n,),
                "moe_steps": (1,)}

    def prefill_core(self, params, ids, mask, kv_dtype):
        x, (k, v), (wk, wv) = prefill_layers(
            params, self, embed(params, ids), mask, kv_dtype)[:3]
        return x, None, None, k, v, wk, wv

    def decode_core(self, params, cache, tokens, active):
        x, counters, (k, v), (wk, wv) = decode_layers(params, self, cache,
                                                      tokens, active)
        return x, None, None, counters, k, v, wk, wv

    def logits_of(self, params, x):
        return logits_of(params, self, x)


def k_exaone_236b_a23b() -> ExaoneMoeConfig:
    return ExaoneMoeConfig()


def exaone_moe_tiny(**changes) -> ExaoneMoeConfig:
    """One leading dense layer and one period (sliding, sliding, full,
    sliding: layers 1-4 of the published pattern)."""
    return ExaoneMoeConfig(**{**dict(
        vocab_size=512, hidden_size=64, num_layers=5,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
        first_k_dense=1, num_heads=4, num_kv_heads=2, head_dim=16,
        sliding_window=8, ffn_size=160, moe_ffn_size=48, num_experts=16,
        experts_per_token=4, experts_held=8, expert_offset=0,
        max_position_embeddings=256), **changes})


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: ExaoneMoeConfig,
         dtype=jnp.float32) -> Dict[str, Any]:
    """Random parameters: matrices ``N(0, 1/fan_in)``, the embedding 0.02,
    norms 1, the router's bias 0."""
    h, hd = cfg.hidden_size, cfg.head_dim
    q_width = cfg.num_heads * hd

    def normal(k, fan_in, *shape):
        return (math.sqrt(1.0 / fan_in)
                * jax.random.normal(k, shape)).astype(dtype)

    def dense(k, fan_in, *shape):
        return {"kernel": normal(k, fan_in, *shape)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def attention(k):
        k1, k2 = jax.random.split(k)
        return {"norm": norm(h), "q_norm": norm(hd), "k_norm": norm(hd),
                "qkv": dense(k1, h, h, q_width + 2 * cfg.kv_row_width),
                "out": dense(k2, q_width, q_width, h)}

    def dense_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"attn": attention(k1), "mlp_norm": norm(h),
                "gate_up": dense(k2, h, h, 2 * cfg.ffn_size),
                "down": dense(k3, cfg.ffn_size, cfg.ffn_size, h)}

    def moe_layer(k):
        ks = jax.random.split(k, 6)
        f, sf = cfg.moe_ffn_size, cfg.shared_experts * cfg.moe_ffn_size
        return {"attn": attention(ks[0]), "mlp_norm": norm(h),
                "router": dense(ks[1], h, h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate_up": normal(ks[2], h, cfg.experts_held, h, 2 * f),
                "w_down": normal(ks[3], f, cfg.experts_held, f, h),
                "shared_gate_up": dense(ks[4], h, h, 2 * sf),
                "shared_down": dense(ks[5], sf, sf, h)}

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, cfg.num_layers)
    return {
        "embedding": {"word": {"embedding": (0.02 * jax.random.normal(
            k_emb, (cfg.vocab_size, h))).astype(dtype)}},
        "dense": [dense_layer(k) for k in keys[:cfg.first_k_dense]],
        "moe": jax.vmap(moe_layer)(keys[cfg.first_k_dense:]),
        "final_norm": norm(h),
        "head": dense(k_head, h, h, cfg.vocab_size),
    }


# ---------------------------------------------------------------------------
# what the blocks share
# ---------------------------------------------------------------------------

@region("head")
def logits_of(params, cfg, x):
    """Final norm and the untied head: (rows, hidden) -> float32 logits."""
    return _dense(params["head"],
                  _rms(params["final_norm"], x, cfg.rms_norm_eps))


def _sublayer(norm, x, cfg, f):
    """``x + norm(f(x))``: the sub-layer's RMSNorm stands on its output;
    ``f`` may give more than its output, which is handed on."""
    out, *more = f(x)
    return (x + _rms(norm, out, cfg.rms_norm_eps), *more)


def rope(x, pos, theta: float):
    """``x`` (rows, heads, d) at positions ``pos`` (rows,): the default
    rotary over the whole head, pair ``i`` being ``(x[i], x[i + d / 2])``."""
    d = x.shape[-1]
    inv_freq = (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(
        np.float32)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-b, a], -1) * sin


def _qkv(lp, u, cfg, pos, windowed):
    """(rows, hidden) at positions ``pos`` -> q (rows, heads, hd), k (rows,
    kv_heads, hd), v (rows, kv_heads * hd): q and k normed per head, and
    rotated in a sliding layer (``windowed``) alone."""
    rows, hd = u.shape[0], cfg.head_dim
    qkv = _dense(lp["qkv"], u)
    q_width = cfg.num_heads * hd
    q = qkv[:, :q_width].reshape(rows, cfg.num_heads, hd)
    k = qkv[:, q_width:q_width + cfg.kv_row_width].reshape(
        rows, cfg.num_kv_heads, hd)
    q = _rms(lp["q_norm"], q, cfg.rms_norm_eps)
    k = _rms(lp["k_norm"], k, cfg.rms_norm_eps)
    if windowed:
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    return q, k, qkv[:, q_width + cfg.kv_row_width:]


# ---------------------------------------------------------------------------
# attention: over a prompt, and one token per slot against a pool
# ---------------------------------------------------------------------------

def _kept(t, dtype):
    """Float32 ``t`` as a cache of ``dtype`` keeps it, still float32. Cut
    with ``lax.reduce_precision``, which the compiler has to honour; a round
    trip through ``astype`` it folds away on the TPU (``nemotron_h.
    _two_terms``), and the prompt would attend to rows the cache does not
    hold."""
    if jnp.dtype(dtype) == jnp.float32:
        return t
    bits = jnp.finfo(dtype)
    return lax.reduce_precision(t, exponent_bits=bits.nexp,
                                mantissa_bits=bits.nmant)


@region("attention")
def attention_prefill(lp, x, cfg, mask, kv_dtype, windowed):
    """One layer's attention over a prompt: ``x`` (s, hidden). Returns ``(x',
    k, v)``, the (s, kv_heads * head_dim) rows the cache keeps, in
    ``kv_dtype``, the cache's: the prompt attends to the rows decode will
    read, and AS decode reads them: float32 queries, probabilities and
    context over the rounded rows, both products at the MXU's full precision
    (the decode kernel splits q and p into exact bfloat16 pieces; a prompt
    path that rounded them would put a bfloat16 error into every row of the
    cache that a later layer writes). K and V are repeated to the query
    heads for ``flash_attention``; a sliding layer (``windowed``, static)
    attends through its band."""
    s = x.shape[0]
    per = cfg.num_heads // cfg.num_kv_heads

    def attend(u):
        q, k, v = _qkv(lp, u, cfg, jnp.arange(s, dtype=jnp.int32), windowed)
        k, v = _kept(k.reshape(s, -1), kv_dtype), _kept(v, kv_dtype)

        def heads(t, repeat):
            t = t.reshape(1, s, -1, cfg.head_dim).transpose(0, 2, 1, 3)
            return jnp.repeat(t, repeat, axis=1) if repeat > 1 else t

        ctx = flash_attention(
            heads(q, 1), heads(k, per), heads(v, per), mask[None, :],
            causal=True, softmax_scale=1.0 / math.sqrt(cfg.head_dim),
            window=cfg.window if windowed else None,
            precision=lax.Precision.HIGHEST)
        return _dense(lp["out"], ctx.transpose(0, 2, 1, 3).reshape(s, -1)), \
            k.astype(kv_dtype), v.astype(kv_dtype)

    return _sublayer(lp["norm"], x, cfg, attend)


@region("attention")
def attention_decode(lp, x, cfg, pools, table, pos, layer, at, start=None):
    """One token for every slot, at positions ``at``, against ``pools`` (k,
    v), read in place by the paged decode kernel through ``table`` up to
    ``pos``; ``layer`` indexes the pools' leading axis. A sliding layer's
    call brings its lower bound ``start`` (and ``table``, ``pos`` counted
    from the first page of the slot's window:
    ``serving.cache.WindowKVCache.window_view``). Returns ``(x', k_row,
    v_row)`` for the caller to write."""
    slots = x.shape[0]

    def attend(u):
        q, k, v = _qkv(lp, u, cfg, at, start is not None)
        k, v = (t.reshape(slots, 1, -1).astype(pools[0].dtype)
                for t in (k, v))
        ctx = paged_decode_attention(
            q.reshape(slots, 1, -1), k, v, *pools, table, pos, layer,
            heads=cfg.num_heads, kv_heads=cfg.num_kv_heads, start=start)
        return _dense(lp["out"], ctx[:, 0]), k[:, 0], v[:, 0]

    return _sublayer(lp["norm"], x, cfg, attend)


# ---------------------------------------------------------------------------
# the MLPs
# ---------------------------------------------------------------------------

@region("mlp")
def dense_block(lp, x, cfg):
    """The dense SwiGLU sub-layer; a prompt longer than ``_MLP_ROWS`` goes
    through it that many rows at a time."""
    def mlp(u):
        rows = u.shape[0]
        if rows > _MLP_ROWS and rows % _MLP_ROWS == 0:
            return lax.map(lambda block: swiglu_mlp(lp, block), u.reshape(
                -1, _MLP_ROWS, u.shape[1])).reshape(rows, -1),
        return swiglu_mlp(lp, u),

    return _sublayer(lp["mlp_norm"], x, cfg, mlp)[0]


def expert_block(lp, x, cfg, real, held=None, first_group=None):
    """The expert sub-layer (``models.deepseek.expert_parts``) over ``x``
    (rows, hidden). Returns ``(x', sizes (experts_held,), chosen (rows,
    k))``."""
    def mlp(u):
        routed, shared, sizes, chosen = expert_parts(lp, u, cfg, real, held,
                                                     first_group)
        with region("experts"):
            return routed + shared, sizes, chosen

    with region("experts"):
        return _sublayer(lp["mlp_norm"], x, cfg, mlp)


# ---------------------------------------------------------------------------
# the layers: over a prompt, and one token per slot against the cache
# ---------------------------------------------------------------------------

def _layer(tree, at):
    return jax.tree.map(lambda w: w[at], tree)


def _by_kind(rows, kinds):
    """``rows``, a list of per-layer values in layer order with ``kinds``
    (sliding?) beside them, as ``(the full layers', the sliding layers')``."""
    return ([r for r, w in zip(rows, kinds) if not w],
            [r for r, w in zip(rows, kinds) if w])


def _scan_periods(cfg, layers, one, carry):
    """Run ``one(carry, lp, m, windowed, of_kind) -> (carry, row, more)``
    over the expert layers ``m`` = 0 .. in order (``of_kind``: how many
    expert layers of ``m``'s kind stand before it): whole periods of
    ``cfg.pattern`` under ONE scan, its body the period unrolled (so
    ``windowed`` is static at each call site), the layers left over unrolled
    after it. Returns
    ``(carry, full rows, sliding rows, more)``, each stacked over its layers
    in order."""
    pattern = cfg.pattern
    n = len(pattern)
    periods, left = divmod(cfg.moe_layers, n)

    def step(carry, p, j):
        """Expert layer ``p * n + j`` (``j`` static)."""
        windowed = pattern[j]
        m = p * n + j
        return one(carry, _layer(layers, m), m, windowed,
                   p * pattern.count(windowed) + pattern[:j].count(windowed))

    def period(carry, p):
        rows, more = [], []
        for j in range(n):
            carry, row, extra = step(carry, p, j)
            rows.append(row)
            more.append(extra)
        full, sliding = _by_kind(rows, pattern)
        return carry, (jax.tree.map(lambda *t: jnp.stack(t), *full),
                       jax.tree.map(lambda *t: jnp.stack(t), *sliding),
                       jax.tree.map(lambda *t: jnp.stack(t), *more))

    flat = lambda t: t.reshape(-1, *t.shape[2:])
    carry, scanned = lax.scan(period, carry,
                              jnp.arange(periods, dtype=jnp.int32))
    full, sliding, more = jax.tree.map(flat, scanned)
    tail = []
    for j in range(left):
        carry, row, extra = step(carry, periods, j)
        tail.append((row, extra, pattern[j]))
    return carry, _joined([], full, [r for r, _, w in tail if not w]), \
        _joined([], sliding, [r for r, _, w in tail if w]), \
        _joined([], more, [e for _, e, _ in tail])


def _joined(before, stacked, after=()):
    """``stacked`` (a tree of arrays that lead with a layer axis) with the
    per-layer trees ``before`` in front of it and ``after`` behind."""
    def stack(rows):
        return [jax.tree.map(lambda *t: jnp.stack(t), *rows)] if rows else []

    return jax.tree.map(lambda *t: jnp.concatenate(t), *stack(before),
                        stacked, *stack(after))


def prefill_layers(params, cfg: ExaoneMoeConfig, x, mask,
                   kv_dtype=jnp.float32):
    """Every layer over one prompt: ``x`` (s, hidden). Returns ``(x', (k, v)
    of the full layers (kv_layers, s, width), (wk, wv) of the sliding layers
    (window_layers, s, width), the routers' choices (expert layers, s,
    k))``."""
    real = mask.astype(bool)
    rows = []
    for i, lp in enumerate(params["dense"]):
        x, k, v = attention_prefill(lp["attn"], x, cfg, mask, kv_dtype,
                                    cfg.windowed(i))
        x = dense_block(lp, x, cfg)
        rows.append((k, v))
    layers, held = _held(params["moe"])

    def one(x, lp, m, windowed, of_kind):
        x, k, v = attention_prefill(lp["attn"], x, cfg, mask, kv_dtype,
                                    windowed)
        x, _, chosen = expert_block(lp, x, cfg, real, held,
                                    m * cfg.experts_held)
        return x, (k, v), chosen

    x, full, sliding, chosen = _scan_periods(cfg, layers, one, x)
    dense_full, dense_sliding = _by_kind(
        rows, [cfg.windowed(i) for i in range(cfg.first_k_dense)])
    return x, _joined(dense_full, full), _joined(dense_sliding, sliding), \
        chosen


def decode_layers(params, cfg: ExaoneMoeConfig, cache, tokens, active):
    """One token for every slot against the serving cache
    (``serving.cache.WindowKVCache``): a full layer attends over the page
    pool through the block tables, a sliding layer over the slot's cycle
    through the view of it that ``cache.window_view`` computes once a step;
    each expert layer counts what its held experts got. Returns ``(x (slots,
    hidden), counters', (k, v) rows of the full layers (kv_layers, slots,
    width), (wk, wv) rows of the sliding layers)`` for the engine to
    write."""
    at = cache.lengths
    pos = jnp.where(active, at, 0)
    table, w_pos, w_start = cache.window_view(cfg.window)
    w_pos, w_start = jnp.where(active, w_pos, 0), jnp.where(active, w_start, 0)

    def attention(lp, x, windowed, pool_layer):
        if windowed:
            return attention_decode(lp, x, cfg, (cache.wk, cache.wv), table,
                                    w_pos, pool_layer, at, start=w_start)
        return attention_decode(lp, x, cfg, (cache.k, cache.v),
                                cache.block_tables, pos, pool_layer, at)

    x = embed(params, tokens)
    rows = []
    for i, lp in enumerate(params["dense"]):
        x, k, v = attention(lp["attn"], x, cfg.windowed(i),
                            jnp.int32(cfg.pool_layer(i)))
        x = dense_block(lp, x, cfg)
        rows.append((k, v))
    dense_kinds = [cfg.windowed(i) for i in range(cfg.first_k_dense)]
    before = {True: sum(dense_kinds),     # pool layers the dense ones took
              False: len(dense_kinds) - sum(dense_kinds)}
    layers, held = _held(params["moe"])
    counters = {**cache.counters, "moe_steps": cache.counters["moe_steps"] + 1}

    def one(carry, lp, m, windowed, of_kind):
        x, counters = carry
        x, k, v = attention(lp["attn"], x, windowed,
                            before[windowed] + of_kind)
        x, sizes, _ = expert_block(lp, x, cfg, active, held,
                                   m * cfg.experts_held)
        with region("experts"):
            counters = {
                **counters,
                "moe_load": counters["moe_load"].at[m].add(sizes),
                "moe_hit": counters["moe_hit"].at[m].add(
                    jnp.sum(sizes > 0))}
        return (x, counters), (k, v), ()

    (x, counters), full, sliding, _ = _scan_periods(cfg, layers, one,
                                                    (x, counters))
    dense_full, dense_sliding = _by_kind(rows, dense_kinds)
    return x, counters, _joined(dense_full, full), \
        _joined(dense_sliding, sliding)


def apply(params, cfg: ExaoneMoeConfig, ids):
    """(s,) token ids -> (s, vocab) float32 logits: the whole forward, no
    cache."""
    x = prefill_layers(params, cfg, embed(params, ids),
                       jnp.ones(ids.shape, jnp.int32))[0]
    return logits_of(params, cfg, x)
