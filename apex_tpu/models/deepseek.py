"""A decoder of pre-norm blocks, ``x <- x + attention(RMSNorm(x))``, ``x <- x +
mlp(RMSNorm(x))``, whose attention is multi-head LATENT attention (MLA) in
every layer and whose MLP is a dense SwiGLU in the first ``first_k_dense``
layers and a sparse expert layer after them (the ``deepseek_v3`` family).

*MLA.* ``[c_q | c_kv | k_pe] = W_a u`` (widths ``q_lora_rank``,
``kv_lora_rank``, ``qk_rope_head_dim``); ``c_q`` and ``c_kv`` pass an RMSNorm
each; ``q = W_qb c_q`` gives every head a ``nope`` and a ``rope`` part;
``k_pe`` is ONE head shared by all; ``[k_nope | v][h] = W_kvb[h] c_kv``. ``q_pe``
and ``k_pe`` are rotated (YaRN frequencies, :func:`yarn_inv_freq`; the pairs
are the projection's neighbouring outputs ``(2i, 2i + 1)`` and the rotated
vector stands de-interleaved, as the published modelling code lays it).
``scores = (q_nope . k_nope + q_pe . k_pe) * softmax_scale``, causal; the
heads' contexts side by side pass ``W_o``. **The cache holds ``c_kv`` after
its norm and ``k_pe`` after its rotation and nothing else**: one row a token a
layer (``kv_row_width``: the two side by side, padded with zeros to whole
128-lane tiles), in ONE pool (``serving.cache.LatentKVCache``).

The prompt path EXPANDS (``k_nope``, ``v`` per head from the rows the cache
will hold, so that the prompt attends to what decode will read) and runs
``flash_attention`` with ``v`` padded to the key's width. The decode path
ABSORBS: ``q_lat[h] = q_nope[h] W_kvb^K[h]``, scores and the latent context
straight out of the mapped pages (``apex_mla_decode_fwd``), ``o[h] = o_lat[h]
W_kvb^V[h]``: the same numbers, and keys and values per head are never made.

*Expert layer* (:mod:`apex_tpu.transformer.functional.moe`): sigmoid scores in
float32, a choice-only bias, the choice limited to the best ``topk_group`` of
``n_group`` groups, normalised weights times ``routed_scaling_factor``; each
expert and the shared expert a SwiGLU, ``W_down(silu(W_gate u) * W_up u)``.
The chip HOLDS experts ``expert_offset .. + experts_held - 1`` and adds up
their part alone (expert parallelism without its exchange: what the absent
experts would add is left out, and that partial sum goes on).

A final RMSNorm and an untied head; no biases. The multi-token-prediction
module of the published model is not part of this file.

Parameters: ``dense`` is a list of the leading dense layers' trees (unrolled:
they stand outside the scan), ``moe`` one tree whose every leaf leads with
``(expert layers,)``, scanned. Gate and up projections are stored fused
(``[gate | up]``), as are the three first projections of the attention
(``a_proj``); ``kv_b_k`` ``(heads, nope, kv_lora_rank)`` and ``kv_b_v``
``(heads, kv_lora_rank, v)`` are ``W_kvb`` by head, in the layout the
absorbed products read without a transpose.

This file holds the blocks, once over a (bucket-padded) prompt and once for
one token per slot against the serving cache, the two halves the serving
engine builds its programs from (:meth:`DeepseekConfig.prefill_core`,
:meth:`DeepseekConfig.decode_core`: ``serving.decode``, "the seam"), and
:func:`apply`, the whole forward with no cache (the tests' middle term).
Precision as ``models.nemotron_h``: the inputs of every product into a
bfloat16 matrix as two bfloat16 terms, float32 between two products, the
router's product whole in float32.
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.models.nemotron_h import _dense, _rms, _two_terms, embed
from apex_tpu.transformer.functional import flash_attention, moe
from apex_tpu.transformer.functional.mla_attention import mla_decode_attention
from apex_tpu.utils.profiler import region

# rows of a prompt that one pass of the expert layer takes: its sorted
# assignments (rows * experts_per_token, most of them for experts held
# elsewhere) are gathered at the hidden width, 0.23 GB at 1024 rows
_MOE_ROWS = 1024


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 129280
    hidden_size: int = 7168
    num_layers: int = 61
    first_k_dense: int = 3
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_size: int = 18432            # the dense layers' SwiGLU
    moe_ffn_size: int = 2048         # each routed expert's
    shared_experts: int = 1          # of moe_ffn_size each, fused into one
    num_experts: int = 256           # the router's width
    experts_per_token: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    experts_held: int = 256          # of num_experts, on this chip
    expert_offset: int = 0           # the first of them
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 163840

    #: the seam (``serving.decode``): no per-slot state beside the pool, and
    #: the pool is one pool of rows that are key and value at once
    recurrent = False
    latent = True
    pools = "latent"

    def __post_init__(self):
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(f"{self.first_k_dense} dense layers of "
                             f"{self.num_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary pairs need an even qk_rope_head_dim")
        if not 0 <= self.expert_offset <= self.num_experts \
                - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5`` times the square of YaRN's attention
        factor ``0.1 * mscale_all_dim * ln(factor) + 1`` (1.3689 as
        published)."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    # -- what the serving engine asks (the seam) -----------------------------

    @property
    def kv_layers(self) -> int:
        """Layers of the page pool: every layer."""
        return self.num_layers

    @property
    def latent_width(self) -> int:
        """What a cached row holds: ``c_kv`` and ``k_pe``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_row_width(self) -> int:
        """Width of one cached row: :attr:`latent_width` in whole 128-lane
        tiles (576 -> 640 as published: a row that is no multiple of the
        lane width is padded to one by the device's tiled layout anyway, and
        stating the pad lets the kernel's DMA move whole tiles)."""
        return -(-self.latent_width // 128) * 128

    def rope_angles(self, pos):
        """(cos, sin) of the rotary pairs at ``pos``: what the latent
        attention below asks of a config (YaRN here)."""
        return _angles(self, pos)

    def counter_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """int32 counters the decode program keeps on the device, in the
        donated cache: assignments per held expert and held experts with at
        least one row, per expert layer, summed over decode steps, and the
        steps."""
        n = self.moe_layers
        return {"moe_load": (n, self.experts_held), "moe_hit": (n,),
                "moe_steps": (1,)}

    def prefill_core(self, params, ids, mask, kv_dtype):
        x, rows = prefill_layers(params, self, embed(params, ids), mask,
                                 kv_dtype)
        return x, None, None, rows, None

    def decode_core(self, params, cache, tokens, active):
        x, counters, rows = decode_layers(params, self, cache, tokens, active)
        return x, None, None, counters, rows, None

    def logits_of(self, params, x):
        return logits_of(params, self, x)


def deepseek_v3() -> DeepseekConfig:
    return DeepseekConfig()


def deepseek_tiny(**changes) -> DeepseekConfig:
    return DeepseekConfig(**{**dict(
        vocab_size=512, hidden_size=64, num_layers=3, first_k_dense=1,
        num_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, ffn_size=160, moe_ffn_size=48,
        num_experts=16, experts_per_token=4, n_group=4, topk_group=2,
        experts_held=8, expert_offset=0, rope_original_positions=64,
        max_position_embeddings=256), **changes})


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepseekConfig) -> np.ndarray:
    """The inverse frequency of each rotary pair (``qk_rope_head_dim / 2``,
    float32): a blend of ``theta ** (-2i / d)`` and that over ``factor``, by a
    linear ramp between the two correction dimensions the betas give (pairs
    that turn more than ``beta_fast`` times over the original context keep
    their frequency, those that turn less than ``beta_slow`` times are
    slowed by the whole factor)."""
    d = cfg.qk_rope_head_dim
    extra = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(turns):
        return d * math.log(cfg.rope_original_positions
                            / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / cfg.rope_factor * ramp
            + extra * (1 - ramp)).astype(np.float32)


def _angles(cfg, pos):
    """``pos`` (...,) int32 -> (cos, sin) (..., pairs) float32, scaled by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 as
    published)."""
    theta = pos.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(theta) * m, jnp.sin(theta) * m


def rope(x, cos, sin):
    """``x`` (..., 2 * pairs): pair ``i`` is ``(x[2i], x[2i + 1])``; the
    rotated pairs stand de-interleaved (first components, then second)."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: DeepseekConfig,
         dtype=jnp.float32) -> Dict[str, Any]:
    """Random parameters: matrices ``N(0, 1/fan_in)``, the embedding 0.02,
    norms 1, the router's bias 0."""
    h, nh = cfg.hidden_size, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank

    def normal(k, fan_in, *shape):
        return (math.sqrt(1.0 / fan_in)
                * jax.random.normal(k, shape)).astype(dtype)

    def dense(k, fan_in, *shape):
        return {"kernel": normal(k, fan_in, *shape)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def attention(k):
        ks = jax.random.split(k, 5)
        return {"norm": norm(h),
                "a_proj": dense(ks[0], h, h, qr + cfg.latent_width),
                "q_norm": norm(qr), "kv_norm": norm(kr),
                "q_b": dense(ks[1], qr, qr, nh * cfg.qk_head_dim),
                "kv_b_k": normal(ks[2], kr, nh, cfg.qk_nope_head_dim, kr),
                "kv_b_v": normal(ks[3], kr, nh, kr, cfg.v_head_dim),
                "out": dense(ks[4], nh * cfg.v_head_dim,
                             nh * cfg.v_head_dim, h)}

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

def _by_head(spec, x, w, axis):
    """``einsum(spec, x, w)`` over a per-head matrix ``w``, summed in float32:
    float32 ``x`` into a bfloat16 ``w`` goes as two bfloat16 terms laid one
    after the other along ``x``'s axis 0, which is the result's ``axis``
    (``models.nemotron_h._dense``, for a product with a head axis). Both
    operands are handed over as float32 HOLDING bfloat16 values, at the
    default precision: one MXU pass that rounds nothing, and a product the
    CPU backend has too (it has no batched bfloat16 one)."""
    if w.dtype == x.dtype:
        return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)
    terms = jnp.concatenate(_two_terms(x, w.dtype)).astype(jnp.float32)
    hi, lo = jnp.split(jnp.einsum(spec, terms, w.astype(jnp.float32),
                                  precision=lax.Precision.DEFAULT), 2,
                       axis=axis)
    return hi + lo


@region("head")
def logits_of(params, cfg, x):
    """Final norm and the untied head: (rows, hidden) -> float32 logits."""
    return _dense(params["head"],
                  _rms(params["final_norm"], x, cfg.rms_norm_eps))


def _swiglu(gate_up, limit: float = 0.0):
    """``silu(gate) * up`` of ``[gate | up]``; with a ``limit`` (a config's
    ``swiglu_limit``, ``models.glm_next``) the gate is held below it and the
    up part inside ``+-limit`` first. No limit adds no operation."""
    f = gate_up.shape[-1] // 2
    if not limit:
        return jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]
    return jax.nn.silu(jnp.minimum(gate_up[:, :f], limit)) \
        * jnp.clip(gate_up[:, f:], -limit, limit)


def _limit(cfg) -> float:
    return getattr(cfg, "swiglu_limit", 0.0)


# What follows up to the MLPs is the latent attention itself, for any config
# that states ``num_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
# ``qk_rope_head_dim``, ``v_head_dim``, ``latent_width``, ``kv_row_width``,
# ``softmax_scale`` and ``rope_angles(pos)`` (``models.bailing_hybrid`` has no
# query latent and gates each head's context: it brings its projections and
# its gate, and everything between them is here).

def latent_row_parts(lp, a, first, cfg, pos):
    """What a cache row is made of, for the caller to lay side by side, out
    of a projection ``a`` (rows, ...) that holds ``[c_kv | k_pe]`` from column
    ``first``: ``c_kv`` after its norm, ``k_pe`` after its rotation at
    ``pos``, and the zeros that pad the row to ``kv_row_width``; float32."""
    mid, end = first + cfg.kv_lora_rank, first + cfg.latent_width
    row = [_rms(lp["kv_norm"], a[:, first:mid], cfg.rms_norm_eps),
           rope(a[:, mid:end], *cfg.rope_angles(pos))]
    pad = cfg.kv_row_width - cfg.latent_width
    if pad:
        row.append(jnp.zeros((a.shape[0], pad), jnp.float32))
    return row


def split_queries(q, cfg, pos):
    """``q`` (rows, heads * qk_head_dim) -> ``q_nope`` (rows, heads, nope),
    roped ``q_pe`` (rows, heads, rope)."""
    q = q.reshape(q.shape[0], cfg.num_heads, cfg.qk_head_dim)
    cos, sin = cfg.rope_angles(pos)
    return q[..., :cfg.qk_nope_head_dim], rope(
        q[..., cfg.qk_nope_head_dim:], cos[:, None], sin[:, None])


def _latents(lp, x, cfg, pos):
    """(rows, hidden) at positions ``pos`` (rows,) -> the normed query latent
    ``c_q`` (rows, q_lora_rank) and the cache row (rows, kv_row_width)
    float32: normed ``c_kv``, roped ``k_pe``, zeros."""
    a = _dense(lp["a_proj"], _rms(lp["norm"], x, cfg.rms_norm_eps))
    qr = cfg.q_lora_rank
    row = latent_row_parts(lp, a, qr, cfg, pos)
    return _rms(lp["q_norm"], a[:, :qr], cfg.rms_norm_eps), \
        jnp.concatenate(row, -1)


def _queries(lp, c_q, cfg, pos):
    """``c_q`` (rows, q_lora_rank) -> ``q_nope`` (rows, heads, nope), roped
    ``q_pe`` (rows, heads, rope)."""
    return split_queries(_dense(lp["q_b"], c_q), cfg, pos)


# ---------------------------------------------------------------------------
# attention: expanded over a prompt, absorbed for one token per slot
# ---------------------------------------------------------------------------

def expanded_attention(lp, q_nope, q_pe, row, cfg, mask, kv_dtype):
    """A prompt's contexts ``(s, heads * v_head_dim)`` float32 from its
    queries and its cache rows ``row`` (s, kv_row_width) in ``kv_dtype``:
    keys and values are expanded from the ROWS, so the prompt attends to
    what decode will read. ``v`` is padded to the key's width for
    ``flash_attention``, which takes one width."""
    s, nh = row.shape[0], cfg.num_heads
    kr = cfg.kv_lora_rank
    c_kv, k_pe = row[:, :kr], row[:, kr:cfg.latent_width]
    # rounded to the cache's dtype the latent IS one term of it
    k_nope = _by_head("sc,hdc->hsd", c_kv, lp["kv_b_k"].astype(kv_dtype), 1)
    v = _by_head("sc,hcd->hsd", c_kv, lp["kv_b_v"].astype(kv_dtype), 1)
    q = jnp.concatenate([q_nope, q_pe], -1).transpose(1, 0, 2)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe.astype(jnp.float32)[None], (nh, s, k_pe.shape[-1]))], -1)
    v = jnp.pad(v, ((0, 0), (0, 0), (0, cfg.qk_head_dim - cfg.v_head_dim)))
    ctx = flash_attention(
        *(t.astype(kv_dtype)[None] for t in (q, k, v)), mask[None, :],
        causal=True, softmax_scale=cfg.softmax_scale)[0, :, :,
                                                      :cfg.v_head_dim]
    return ctx.transpose(1, 0, 2).reshape(s, -1).astype(jnp.float32)


def absorbed_attention(lp, q_nope, q_pe, row, cfg, pool, layer, block_tables,
                       pos, active):
    """One token's contexts ``(slots, heads, v_head_dim)`` for every slot
    against the latent pool, read in place by ``apex_mla_decode_fwd``: the
    queries absorb ``W_kvb``'s key half, the latent contexts pass its value
    half. ``row`` (slots, kv_row_width) is the new token's, in the pool's
    dtype. A slot that is not ``active`` reads no page."""
    q_lat = _by_head("bhd,hdc->bhc", q_nope, lp["kv_b_k"], 0)
    q = jnp.concatenate([q_lat, q_pe], -1) * cfg.softmax_scale
    q = jnp.pad(q, ((0, 0), (0, 0), (0, cfg.kv_row_width - q.shape[-1])))
    o_lat = mla_decode_attention(
        q, row, pool, block_tables, jnp.where(active, pos, 0), layer,
        value_width=cfg.kv_lora_rank)
    return _by_head("bhc,hcd->bhd", o_lat, lp["kv_b_v"], 0)


@region("attention")
def attention_prefill(lp, x, cfg, mask, kv_dtype):
    """One layer's attention over a prompt: ``x`` (s, hidden). Returns
    ``(x', rows (s, kv_row_width))``, the rows in ``kv_dtype``, the cache's
    (:func:`expanded_attention`)."""
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    c_q, row = _latents(lp, x, cfg, pos)
    row = row.astype(kv_dtype)
    q_nope, q_pe = _queries(lp, c_q, cfg, pos)
    ctx = expanded_attention(lp, q_nope, q_pe, row, cfg, mask, kv_dtype)
    return x + _dense(lp["out"], ctx), row


@region("attention")
def attention_decode(lp, x, cfg, pool, layer, block_tables, pos, active):
    """One token for every slot against the latent pool
    (:func:`absorbed_attention`); ``layer`` indexes the pool's leading axis.
    Returns ``(x', row (slots, kv_row_width))`` for the caller to write at
    ``pos``."""
    c_q, row = _latents(lp, x, cfg, pos)
    row = row.astype(pool.dtype)
    q_nope, q_pe = _queries(lp, c_q, cfg, pos)
    ctx = absorbed_attention(lp, q_nope, q_pe, row, cfg, pool, layer,
                             block_tables, pos, active)
    return x + _dense(lp["out"], ctx.reshape(x.shape[0], -1)), row


# ---------------------------------------------------------------------------
# the MLPs: dense, and the experts held here
# ---------------------------------------------------------------------------

def swiglu_mlp(lp, u, limit: float = 0.0):
    """``W_down(silu(W_gate u) * W_up u)`` of rows ``u`` as the layer takes
    them (normed, where the family norms in front); ``limit``:
    :func:`_swiglu`'s."""
    return _dense(lp["down"], _swiglu(_dense(lp["gate_up"], u), limit))


@region("mlp")
def dense_mlp(lp, x, cfg):
    return x + swiglu_mlp(lp, _rms(lp["mlp_norm"], x, cfg.rms_norm_eps))


def _held(moe_params):
    """The expert layers' trees for a scan, WITHOUT the experts' matrices,
    and those as ``(expert layers * experts_held, k, n)`` for
    ``grouped_matmul`` to read a layer's experts out of in place
    (``first_group``): as a scanned operand a layer's matrices would be
    sliced out of the stack for the kernel, a copy of every expert held, hit
    or not, each step (1.4 GB a layer at the published widths)."""
    flat = lambda w: w.reshape(-1, *w.shape[2:])
    scanned = {k: v for k, v in moe_params.items()
               if k not in ("w_gate_up", "w_down")}
    return scanned, (flat(moe_params["w_gate_up"]),
                     flat(moe_params["w_down"]))


def _experts(lp, u, cfg, real, held=None, first_group=None):
    """The routed part over normed rows ``u`` (rows, hidden): ``(sum over
    the experts chosen AND held, sizes (experts_held,), chosen (rows, k))``.
    ``held``: the matrices ``(w_gate_up, w_down)`` where they are not
    ``lp``'s own, this layer's from ``first_group`` on."""
    w_gate_up, w_down = held or (lp["w_gate_up"], lp["w_down"])
    with region("router"):
        logits = jnp.dot(u, lp["router"]["kernel"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        chosen, weights = moe.route(logits, lp["router_bias"],
                                    cfg.experts_per_token,
                                    cfg.routed_scaling_factor, cfg.n_group,
                                    cfg.topk_group)
    with region("experts"):
        d = moe.dispatch(chosen, weights, cfg.expert_offset,
                         cfg.experts_held, real)
        mid = _swiglu(moe.grouped_matmul(u[d.token], w_gate_up, d.sizes,
                                         first_group=first_group),
                      _limit(cfg))
        out = moe.grouped_matmul(mid, w_down, d.sizes,
                                 first_group=first_group)
        return moe.combine(out, d, u.shape[0]), d.sizes, chosen


def expert_parts(lp, u, cfg, real, held=None, first_group=None):
    """One expert layer over rows ``u`` (rows, hidden) as the layer takes
    them (normed, where the family norms in front), a prompt's positions or
    one token per slot alike; ``real`` (rows,) bool marks the rows that are
    tokens. A prompt longer than ``_MOE_ROWS`` goes through the routed
    experts that many rows at a time. Returns ``(routed, shared, sizes
    (experts_held,), chosen (rows, k))``: the held experts' part and the
    shared expert's, for the caller to add."""
    rows = u.shape[0]
    if rows > _MOE_ROWS and rows % _MOE_ROWS == 0:
        routed, sizes, chosen = lax.map(
            lambda block: _experts(lp, block[0], cfg, block[1], held,
                                   first_group),
            (u.reshape(-1, _MOE_ROWS, u.shape[1]),
             real.reshape(-1, _MOE_ROWS)))
        with region("experts"):
            routed, sizes = routed.reshape(rows, -1), jnp.sum(sizes, 0)
            chosen = chosen.reshape(rows, -1)
    else:
        routed, sizes, chosen = _experts(lp, u, cfg, real, held, first_group)
    with region("mlp"):     # the shared expert
        shared = _dense(lp["shared_down"],
                        _swiglu(_dense(lp["shared_gate_up"], u), _limit(cfg)))
    return routed, shared, sizes, chosen


def expert_mlp(lp, x, cfg, real, held=None, first_group=None):
    """``x + experts(RMSNorm(x))``: :func:`expert_parts` behind this family's
    norm. Returns ``(x', sizes (experts_held,), chosen (rows, k))``."""
    with region("router"):
        u = _rms(lp["mlp_norm"], x, cfg.rms_norm_eps)
    routed, shared, sizes, chosen = expert_parts(lp, u, cfg, real, held,
                                                 first_group)
    with region("experts"):
        return x + routed + shared, sizes, chosen


# ---------------------------------------------------------------------------
# the layers: over a prompt, and one token per slot against the cache
# ---------------------------------------------------------------------------

def prefill_layers(params, cfg: DeepseekConfig, x, mask,
                   kv_dtype=jnp.float32, routes=False):
    """Every layer over one prompt: ``x`` (s, hidden). Returns ``(x', rows
    (layers, s, kv_row_width))`` and, when ``routes`` is asked for, the
    routers' choices ``(expert layers, s, k)`` after them."""
    real = mask.astype(bool)
    rows = []
    for lp in params["dense"]:
        x, row = attention_prefill(lp["attn"], x, cfg, mask, kv_dtype)
        x = dense_mlp(lp, x, cfg)
        rows.append(row)

    layers, held = _held(params["moe"])

    def layer(x, lp_at):
        lp, at = lp_at
        x, row = attention_prefill(lp["attn"], x, cfg, mask, kv_dtype)
        x, _, chosen = expert_mlp(lp, x, cfg, real, held,
                                  at * cfg.experts_held)
        return x, (row, chosen) if routes else (row,)

    x, scanned = lax.scan(
        layer, x, (layers, jnp.arange(cfg.moe_layers, dtype=jnp.int32)))
    rows = jnp.concatenate([jnp.stack(rows), scanned[0]]) if rows \
        else scanned[0]
    return (x, rows) + tuple(scanned[1:])


def decode_layers(params, cfg: DeepseekConfig, cache, tokens, active):
    """One token for every slot against the serving cache
    (``serving.cache.LatentKVCache``): each layer attends over the pool in
    place, each expert layer counts what its held experts got (the counters
    are carries of the scan, never copied). Returns ``(x (slots, hidden),
    counters', rows (layers, slots, kv_row_width))`` for the engine to
    write."""
    pos, bt = cache.lengths, cache.block_tables
    x = embed(params, tokens)
    rows = []
    for at, lp in enumerate(params["dense"]):
        x, row = attention_decode(lp["attn"], x, cfg, cache.k, jnp.int32(at),
                                  bt, pos, active)
        x = dense_mlp(lp, x, cfg)
        rows.append(row)

    def layer(carry, lp_at):
        x, counters = carry
        lp, at = lp_at
        x, row = attention_decode(lp["attn"], x, cfg, cache.k,
                                  cfg.first_k_dense + at, bt, pos, active)
        x, sizes, _ = expert_mlp(lp, x, cfg, active, held,
                                 at * cfg.experts_held)
        with region("experts"):
            counters = {
                **counters,
                "moe_load": counters["moe_load"].at[at].add(sizes),
                "moe_hit": counters["moe_hit"].at[at].add(
                    jnp.sum(sizes > 0))}
        return (x, counters), row

    layers, held = _held(params["moe"])
    counters = {**cache.counters, "moe_steps": cache.counters["moe_steps"] + 1}
    (x, counters), scanned = lax.scan(
        layer, (x, counters),
        (layers, jnp.arange(cfg.moe_layers, dtype=jnp.int32)))
    rows = jnp.concatenate([jnp.stack(rows), scanned]) if rows else scanned
    return x, counters, rows


def apply(params, cfg: DeepseekConfig, ids):
    """(s,) token ids -> (s, vocab) float32 logits: the whole forward, no
    cache."""
    x = prefill_layers(params, cfg, embed(params, ids),
                       jnp.ones(ids.shape, jnp.int32))[0]
    return logits_of(params, cfg, x)
