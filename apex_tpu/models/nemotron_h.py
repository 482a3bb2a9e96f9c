"""A decoder whose every layer is ONE mixer under a pre-norm residual, ``x <-
x + mixer(RMSNorm(x))`` (the ``nemotron_h`` family), the kind of each layer
read from the published ``hybrid_override_pattern``, one character a layer:

``M``, Mamba-2 (:mod:`apex_tpu.transformer.functional.ssd`): ``[z | xBC | dt]
= W_in u``; ``xBC <- SiLU(causal depthwise conv(xBC) + b_conv)`` split into
``x`` (H, P), ``B`` and ``C`` (G, N); ``delta = softplus(dt + dt_bias)``, ``A
= -exp(A_log)``; the recurrence gives ``y``, plus the skip ``D x``; the output
is ``W_out RMSNorm_grouped(y * SiLU(z))``, the norm over groups of ``d_inner /
G`` channels (the gate BEFORE the norm).

``*``, attention: ``heads`` query heads over ``kv_heads`` K/V heads (query
head ``h`` reads K/V head ``h // (heads / kv_heads)``), causal softmax, no
positional embedding of any kind (the Mamba-2 layers carry the order).

``E``, latent expert layer (:mod:`apex_tpu.transformer.functional.moe`): the
router scores all ``num_experts`` in float32 and takes ``experts_per_token``
of them; the token is projected to the latent width, the experts (two
matrices and ``relu(.)^2`` each, no gate) work there, the weighted sum is
projected back up; a shared expert works on the full width beside them. The
chip HOLDS experts ``expert_offset .. expert_offset + experts_held - 1`` and
adds up their part alone (expert parallelism without its exchange: what the
absent experts would add is left out, and that partial sum goes on to the
next layer).

No biases but the convolution's; a final RMSNorm and an untied head.

Parameters are stacked by place in the pattern's period (the shortest string
whose repeats give the pattern) and the model is scanned BY PERIOD, so a
compiled program holds one unrolled period: ``params["periods"]`` is a list
with one tree per character of the period, every leaf leading with
``(repeats,)``.

This file holds the three mixers, once over a (bucket-padded) prompt and once
for one token per slot against the serving cache, the two halves the serving
engine builds its programs from (:meth:`NemotronHConfig.prefill_core`,
:meth:`NemotronHConfig.decode_core`: the seam ``models.hybrid`` stands on
too), and :func:`apply`, the whole forward with no cache (the tests' middle
term between the two).
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.normalization import fused_rms_norm_affine
from apex_tpu.transformer.functional import flash_attention, moe
from apex_tpu.transformer.functional.gated_delta import causal_conv, conv_step
from apex_tpu.transformer.functional.ssd import CHUNK, ssd_chunked, ssd_step
from apex_tpu.utils.profiler import region

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = PUBLISHED_PATTERN
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 512           # the router's width
    experts_per_token: int = 22
    moe_latent_size: int = 1024
    moe_ffn_size: int = 2688
    shared_ffn_size: int = 5376
    routed_scaling_factor: float = 5.0
    experts_held: int = 512          # of num_experts, on this chip
    expert_offset: int = 0           # the first of them
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144

    #: the serving engine keeps per-slot state beside the page pool for it
    recurrent = True

    def __post_init__(self):
        bad = set(self.pattern) - {MAMBA, ATTENTION, EXPERTS}
        if bad or MAMBA not in self.pattern or ATTENTION not in self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: one of M, * and E per layer, "
                "with at least one M and one *")
        if self.mamba_heads % self.ssm_groups \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("heads are no multiple of their groups")
        if not 0 <= self.expert_offset <= self.num_experts \
                - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")

    # -- the pattern ---------------------------------------------------------

    @property
    def period(self) -> str:
        n = len(self.pattern)
        return next(self.pattern[:p] for p in range(1, n + 1)
                    if n % p == 0 and self.pattern[:p] * (n // p)
                    == self.pattern)

    @property
    def repeats(self) -> int:
        return len(self.pattern) // len(self.period)

    def layers_of(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    # -- widths --------------------------------------------------------------

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    # -- what the serving engine asks (the seam, with models.hybrid) ---------

    @property
    def kv_layers(self) -> int:
        """Layers of the page pool: the attention layers."""
        return self.layers_of(ATTENTION)

    @property
    def kv_row_width(self) -> int:
        """Width of one cached K (or V) row."""
        return self.num_kv_heads * self.head_dim

    def state_shapes(self, num_slots: int) -> Tuple[Tuple[int, ...], ...]:
        """(recurrent state, convolution tail) of ``num_slots`` slots."""
        n = self.layers_of(MAMBA)
        return ((n, num_slots, self.mamba_heads, self.mamba_head_dim,
                 self.ssm_state),
                (n, num_slots, self.conv_kernel - 1, self.conv_channels))

    def state_bytes_per_slot(self) -> int:
        """Bytes one prefill writes for its slot besides the pages
        (float32 state and tails)."""
        state, conv = self.state_shapes(1)
        return 4 * (math.prod(state) + math.prod(conv))

    def counter_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """int32 counters the decode program keeps on the device, in the
        donated cache: assignments per held expert and held experts with at
        least one row, per expert layer, summed over decode steps, and the
        steps."""
        n = self.layers_of(EXPERTS)
        return {"moe_load": (n, self.experts_held), "moe_hit": (n,),
                "moe_steps": (1,)}

    def prefill_core(self, params, ids, mask, kv_dtype):
        return prefill_layers(params, self, embed(params, ids), mask,
                              kv_dtype)

    def decode_core(self, params, cache, tokens, active):
        return decode_layers(params, self, cache, tokens, active)

    def logits_of(self, params, x):
        return logits_of(params, self, x)


def nemotron3_super_120b_a12b() -> NemotronHConfig:
    return NemotronHConfig()


def nemotron_h_tiny(**changes) -> NemotronHConfig:
    return NemotronHConfig(**{**dict(
        vocab_size=512, hidden_size=64, pattern="MEM*E", mamba_heads=8,
        mamba_head_dim=16, ssm_groups=2, ssm_state=32, num_heads=4,
        num_kv_heads=2, head_dim=16, num_experts=16, experts_per_token=4,
        moe_latent_size=32, moe_ffn_size=48, shared_ffn_size=96,
        experts_held=8, expert_offset=0, max_position_embeddings=256),
        **changes})


# ---------------------------------------------------------------------------
# init: one tree per place in the period, the repeats axis leading
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: NemotronHConfig,
         dtype=jnp.float32) -> Dict[str, Any]:
    """Random parameters: matrices ``N(0, 1/fan_in)``, the embedding 0.02,
    the convolution taps 0.5 and its bias 0, norms 1, the router's bias 0;
    ``A`` uniform in 1..16, ``dt`` log-uniform in 0.001..0.1 with its inverse
    softplus as ``dt_bias``, ``D`` 1 (the Mamba-2 paper's initialisation)."""
    h = cfg.hidden_size
    nh, di, cc = cfg.mamba_heads, cfg.d_inner, cfg.conv_channels

    def dense(k, fan_in, *shape):
        return {"kernel": (math.sqrt(1.0 / fan_in)
                           * jax.random.normal(k, shape)).astype(dtype)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def mamba(k):
        ks = jax.random.split(k, 5)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (nh,), minval=math.log(1e-3), maxval=math.log(0.1)))
        return {"norm": norm(h), "in_proj": dense(ks[0], h, h, di + cc + nh),
                "conv": {"weight": (0.5 * jax.random.normal(
                    ks[1], (cfg.conv_kernel, cc))).astype(dtype),
                    "bias": jnp.zeros((cc,), jnp.float32)},
                "a_log": jnp.log(jax.random.uniform(
                    ks[2], (nh,), minval=1.0, maxval=16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d": jnp.ones((nh,), jnp.float32), "y_norm": norm(di),
                "out": dense(ks[4], di, di, h)}

    def attention(k):
        k1, k2 = jax.random.split(k)
        width = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
        return {"norm": norm(h), "qkv": dense(k1, h, h, width),
                "out": dense(k2, cfg.num_heads * cfg.head_dim,
                             cfg.num_heads * cfg.head_dim, h)}

    def experts(k):
        ks = jax.random.split(k, 7)
        lat, f, sf = (cfg.moe_latent_size, cfg.moe_ffn_size,
                      cfg.shared_ffn_size)
        return {"norm": norm(h), "router": dense(ks[0], h, h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "down": dense(ks[1], h, h, lat),
                "w1": dense(ks[2], lat, cfg.experts_held, lat, f)["kernel"],
                "w2": dense(ks[3], f, cfg.experts_held, f, lat)["kernel"],
                "up": dense(ks[4], lat, lat, h),
                "shared_in": dense(ks[5], h, h, sf),
                "shared_out": dense(ks[6], sf, sf, h)}

    make = {MAMBA: mamba, ATTENTION: attention, EXPERTS: experts}
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, cfg.num_layers).reshape(
        cfg.repeats, len(cfg.period), -1)
    return {
        "embedding": {"word": {"embedding": (0.02 * jax.random.normal(
            k_emb, (cfg.vocab_size, h))).astype(dtype)}},
        "periods": [jax.vmap(make[kind])(keys[:, j])
                    for j, kind in enumerate(cfg.period)],
        "final_norm": norm(h),
        "head": dense(k_head, h, h, cfg.vocab_size),
    }


# ---------------------------------------------------------------------------
# what the mixers share
# ---------------------------------------------------------------------------

def _two_terms(x, dtype):
    """Float32 ``x`` as ``hi + lo`` in ``dtype`` (bfloat16): ``hi`` holds the
    leading 8 bits of the mantissa, ``lo`` the next 8. ``hi`` is cut with
    ``lax.reduce_precision``, which the compiler has to honour; a round trip
    through ``astype`` it may fold away on the TPU (it keeps "excess
    precision" by default), which leaves ``lo`` zero and the product as
    coarse as one term."""
    bits = jnp.finfo(dtype)
    hi = lax.reduce_precision(x, exponent_bits=bits.nexp,
                              mantissa_bits=bits.nmant)
    return hi.astype(dtype), (x - hi).astype(dtype)


def _dense(p, x):
    """``x @ kernel``, summed and handed on in float32; the residual stream
    and everything between two products stay float32. Into a bfloat16 kernel
    ``x`` goes as TWO bfloat16 terms, ``hi + lo`` (16 bits of mantissa: two
    MXU passes over a kernel that is read once, the rows laid one under the
    other). One term, as ``models.hybrid._dense`` has it, is enough where
    nothing downstream is discrete; here a router takes the 22 largest of 512
    scores, and with one term the choice parted from the float32 reference's
    at 7% of the tokens in the first expert layer and 65-70% in the fifth
    (each flip moves the residual by an expert's worth and feeds the next
    router), against 0-0.1% and 1-7% with two (chip runs, PERF.md, section 6,
    PR 33): the served tokens then read no closer to the reference than the
    benchmark's lower-precision control does."""
    kernel = p["kernel"]
    if kernel.dtype == x.dtype:
        return jnp.dot(x, kernel, preferred_element_type=jnp.float32)
    hi, lo = _two_terms(x, kernel.dtype)
    out = jnp.dot(jnp.concatenate([hi, lo]), kernel,
                  preferred_element_type=jnp.float32)
    return out[:x.shape[0]] + out[x.shape[0]:]


def _rms(p, x, eps):
    return fused_rms_norm_affine(x, p["weight"], x.shape[-1], eps)


@region("embed")
def embed(params, ids):
    return jnp.take(params["embedding"]["word"]["embedding"], ids,
                    axis=0).astype(jnp.float32)


@region("head")
def logits_of(params, cfg, x):
    """Final norm and the untied head: (rows, hidden) -> float32 logits."""
    return _dense(params["head"],
                  _rms(params["final_norm"], x, cfg.rms_norm_eps))


# ---------------------------------------------------------------------------
# M: the Mamba-2 mixer
# ---------------------------------------------------------------------------

def _mamba_in(lp, x, cfg):
    """(rows, hidden) -> z (rows, d_inner), xBC (rows, conv channels) before
    its convolution, delta (rows, H)."""
    proj = _dense(lp["in_proj"], _rms(lp["norm"], x, cfg.rms_norm_eps))
    di, cc = cfg.d_inner, cfg.conv_channels
    delta = jax.nn.softplus(proj[:, di + cc:] + lp["dt_bias"])
    return proj[:, :di], proj[:, di:di + cc], delta


def _mamba_heads(lp, conv_out, cfg):
    """Convolved channels (rows, C) -> x (rows, H, P), B, C (rows, G, N)."""
    y = jax.nn.silu(conv_out + lp["conv"]["bias"])
    rows, di = y.shape[0], cfg.d_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    return (y[:, :di].reshape(rows, cfg.mamba_heads, cfg.mamba_head_dim),
            y[:, di:di + gn].reshape(rows, cfg.ssm_groups, cfg.ssm_state),
            y[:, di + gn:].reshape(rows, cfg.ssm_groups, cfg.ssm_state))


def _mamba_out(lp, y, xs, z, cfg):
    """``W_out RMSNorm_grouped((y + D x) * SiLU(z))``."""
    rows = y.shape[0]
    y = (y + lp["d"][:, None] * xs).reshape(rows, cfg.d_inner)
    y = (y * jax.nn.silu(z)).reshape(rows, cfg.ssm_groups, -1)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.rms_norm_eps)
    return _dense(lp["out"], y.reshape(rows, -1) * lp["y_norm"]["weight"])


@region("mixer")
def mamba_block_prefill(lp, x, cfg, mask):
    """One Mamba-2 layer over a prompt: ``x`` (s, hidden), ``mask`` (s,) with
    1 = real token and the padding at the end. Returns ``(x', state (H, P, N)
    float32, tail (w-1, C))`` as the prompt's last real token leaves them:
    padded positions decay nothing and write nothing (``delta = 0``)."""
    s = x.shape[0]
    z, xbc, delta = _mamba_in(lp, x, cfg)
    conv_out, tail = causal_conv(
        xbc, lp["conv"]["weight"].astype(jnp.float32), jnp.sum(mask))
    xs, b, c = _mamba_heads(lp, conv_out, cfg)
    delta = jnp.where(mask.astype(bool)[:, None], delta, 0.0)
    pad = -s % CHUNK

    def whole(t):           # the sequence padded to whole chunks
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))

    y, state = ssd_chunked(whole(xs), whole(delta), -jnp.exp(lp["a_log"]),
                           whole(b), whole(c))
    return x + _mamba_out(lp, y[:s], xs, z, cfg), state, tail


@region("mixer")
def mamba_block_decode(lp, x, cfg, state, conv, layer, active):
    """One token for every slot: ``x`` (b, hidden); ``state`` and ``conv`` the
    WHOLE stacked arrays (``NemotronHConfig.state_shapes``), of which layer
    ``layer`` (a traced scalar) is read and written. Returns ``(x', state',
    conv')``."""
    z, xbc, delta = _mamba_in(lp, x, cfg)
    tail = lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
    conv_out, new_tail = conv_step(
        xbc, tail, lp["conv"]["weight"].astype(jnp.float32))
    new_tail = jnp.where(active[:, None, None], new_tail, tail)
    conv = lax.dynamic_update_index_in_dim(conv, new_tail, layer, 0)
    xs, b, c = _mamba_heads(lp, conv_out, cfg)
    y, state = ssd_step(xs, delta, -jnp.exp(lp["a_log"]), b, c, state, layer,
                        active)
    return x + _mamba_out(lp, y, xs, z, cfg), state, conv


# ---------------------------------------------------------------------------
# *: attention, fewer K/V heads than query heads
# ---------------------------------------------------------------------------

def _qkv(lp, x, cfg):
    """(rows, hidden) -> q (rows, heads * hd), k, v (rows, kv_heads * hd):
    heads side by side, a page's row layout."""
    qkv = _dense(lp["qkv"], _rms(lp["norm"], x, cfg.rms_norm_eps))
    q = cfg.num_heads * cfg.head_dim
    return qkv[:, :q], qkv[:, q:q + cfg.kv_row_width], \
        qkv[:, q + cfg.kv_row_width:]


@region("attention")
def attention_block_prefill(lp, x, cfg, mask, kv_dtype):
    """One attention layer over a prompt. Returns ``(x', k, v)``, the (s,
    kv_heads * head_dim) rows the cache keeps, in ``kv_dtype``, the cache's:
    the prompt attends to the rows decode will read. K and V are repeated to
    the query heads for ``flash_attention`` (one layer in eleven)."""
    s = x.shape[0]
    q, k, v = (t.astype(kv_dtype) for t in _qkv(lp, x, cfg))
    per = cfg.num_heads // cfg.num_kv_heads

    def heads(t, repeat):
        t = t.reshape(1, s, -1, cfg.head_dim).transpose(0, 2, 1, 3)
        return jnp.repeat(t, repeat, axis=1) if repeat > 1 else t

    ctx = flash_attention(heads(q, 1), heads(k, per), heads(v, per),
                          mask[None, :], causal=True,
                          softmax_scale=1.0 / math.sqrt(cfg.head_dim))
    return x + _dense(lp["out"], ctx.transpose(0, 2, 1, 3).reshape(s, -1)), \
        k, v


@region("attention")
def attention_block_decode(lp, x, cfg, k_pool, v_pool, layer, block_tables,
                           pos):
    """One token for every slot against the paged pool, read in place by
    ``apex_paged_decode_fwd``; ``layer`` indexes the pool's leading axis (the
    attention layers only). Returns ``(x', k_row, v_row)`` for the caller to
    write at ``pos``."""
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )

    q, k, v = _qkv(lp, x, cfg)
    k, v = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
    ctx = paged_decode_attention(
        q[:, None], k[:, None], v[:, None], k_pool, v_pool, block_tables,
        pos, layer, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads)[:, 0]
    return x + _dense(lp["out"], ctx), k, v


# ---------------------------------------------------------------------------
# E: the latent expert layer, the experts held here
# ---------------------------------------------------------------------------

def expert_block(lp, x, cfg, real):
    """One expert layer over ``x`` (rows, hidden), a prompt's positions or
    one token per slot alike; ``real`` (rows,) bool marks the rows that are
    tokens. Returns ``(x', sizes (experts_held,), chosen (rows, k))``: the
    assignments each held expert got, and the router's choice."""
    rows = x.shape[0]
    with region("router"):
        u = _rms(lp["norm"], x, cfg.rms_norm_eps)
        logits = jnp.dot(u, lp["router"]["kernel"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        chosen, weights = moe.route(logits, lp["router_bias"],
                                    cfg.experts_per_token,
                                    cfg.routed_scaling_factor)
    with region("experts"):
        d = moe.dispatch(chosen, weights, cfg.expert_offset,
                         cfg.experts_held, real)
        latent = _dense(lp["down"], u)
        mid = moe.grouped_matmul(latent[d.token], lp["w1"], d.sizes,
                                 activation="relu2")
        routed = moe.combine(moe.grouped_matmul(mid, lp["w2"], d.sizes), d,
                             rows)
    with region("mlp"):     # the shared expert
        shared = _dense(lp["shared_out"],
                        jnp.square(jax.nn.relu(_dense(lp["shared_in"], u))))
    with region("experts"):
        return x + _dense(lp["up"], routed) + shared, d.sizes, chosen


# ---------------------------------------------------------------------------
# the layers: over a prompt, and one token per slot against the cache
# ---------------------------------------------------------------------------

def _stacked(rows):
    """A scan's stacked ``(repeats, n, ...)`` results as ``(repeats * n,
    ...)``: the model's order of that kind of layer."""
    return rows.reshape(-1, *rows.shape[2:])


def prefill_layers(params, cfg: NemotronHConfig, x, mask,
                   kv_dtype=jnp.float32, routes=False):
    """Every layer over one prompt, scanned by period: ``x`` (s, hidden).
    Returns ``(x', states (M layers, H, P, N), tails (M layers, w-1, C), k, v
    (* layers, s, kv_heads * head_dim))`` and, when ``routes`` is asked for,
    the routers' choices ``(E layers, s, k)`` after them."""
    real = mask.astype(bool)

    def period(x, pp):
        states, tails, ks, vs, chosen = [], [], [], [], []
        for kind, lp in zip(cfg.period, pp):
            if kind == MAMBA:
                x, state, tail = mamba_block_prefill(lp, x, cfg, mask)
                states.append(state)
                tails.append(tail)
            elif kind == ATTENTION:
                x, k, v = attention_block_prefill(lp, x, cfg, mask, kv_dtype)
                ks.append(k)
                vs.append(v)
            else:
                x, _, picked = expert_block(lp, x, cfg, real)
                chosen.append(picked)
        kept = (states, tails, ks, vs) + ((chosen,) if routes else ())
        return x, tuple(jnp.stack(t) for t in kept)

    x, stacked = lax.scan(period, x, params["periods"])
    return (x, *map(_stacked, stacked))


def decode_layers(params, cfg: NemotronHConfig, cache, tokens, active):
    """One token for every slot against the serving cache
    (``serving.cache.HybridKVCache``), scanned by period: each Mamba-2 layer
    steps its layer of the stacked state in place (``apex_ssd_decode_fwd``;
    state, tails and counters are carries of the scan, never copied), each
    attention layer attends over the pool in place, each expert layer counts
    what its held experts got. Returns ``(x (slots, hidden), state', conv',
    counters', k_rows, v_rows (* layers, slots, kv_heads * head_dim))`` for
    the engine to write."""
    pos, bt = cache.lengths, cache.block_tables
    x = embed(params, tokens)
    n = {kind: cfg.period.count(kind) for kind in (MAMBA, ATTENTION, EXPERTS)}

    def period(carry, pp_at):
        x, state, conv, counters = carry
        pp, at = pp_at
        seen = {MAMBA: 0, ATTENTION: 0, EXPERTS: 0}
        k_rows, v_rows = [], []
        for kind, lp in zip(cfg.period, pp):
            layer = at * n[kind] + seen[kind]
            seen[kind] += 1
            if kind == MAMBA:
                x, state, conv = mamba_block_decode(lp, x, cfg, state, conv,
                                                    layer, active)
            elif kind == ATTENTION:
                x, k_row, v_row = attention_block_decode(
                    lp, x, cfg, cache.k, cache.v, layer, bt, pos)
                k_rows.append(k_row)
                v_rows.append(v_row)
            else:
                x, sizes, _ = expert_block(lp, x, cfg, active)
                with region("experts"):
                    counters = {
                        **counters,
                        "moe_load": counters["moe_load"].at[layer].add(
                            sizes),
                        "moe_hit": counters["moe_hit"].at[layer].add(
                            jnp.sum(sizes > 0))}
        return (x, state, conv, counters), (jnp.stack(k_rows),
                                            jnp.stack(v_rows))

    counters = {**cache.counters, "moe_steps": cache.counters["moe_steps"] + 1}
    (x, state, conv, counters), (k_rows, v_rows) = lax.scan(
        period, (x, cache.state, cache.conv, counters),
        (params["periods"], jnp.arange(cfg.repeats, dtype=jnp.int32)))
    return x, state, conv, counters, _stacked(k_rows), _stacked(v_rows)


def apply(params, cfg: NemotronHConfig, ids):
    """(s,) token ids -> (s, vocab) float32 logits: the whole forward, no
    cache."""
    x = prefill_layers(params, cfg, embed(params, ids),
                       jnp.ones(ids.shape, jnp.int32))[0]
    return logits_of(params, cfg, x)
