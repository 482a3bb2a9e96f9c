"""A decoder on FOUR residual streams mixed by manifold-constrained
hyper-connections, whose mixer is Kimi Delta Attention (KDA) in three layers
of four and LEARNED-SPARSE latent attention in the fourth, and whose MLP is a
dense SwiGLU in the first ``first_k_dense`` layers and a sparse expert layer
after them (the ``glm5_next_text`` family: GLM-5.3-Flash).

*The residual path* (``models.layers.hyper_connect``, arXiv 2512.24880). Every
sub-layer ``F`` (mixer or attention, then MLP or experts) reads ONE stream
mixed out of the ``hc_mult`` and writes back into all of them: ``X <- Hres X +
Hpost^T F(RMSNorm(Hpre X))`` with three small maps computed from ``X``, ``Hres``
made doubly stochastic by ``hc_sinkhorn_iters`` Sinkhorn passes. The embedding
is copied into the streams; the final norm and the head read their sum.

*KDA* exactly as ``models.bailing_hybrid`` has it, whose pieces are IMPORTED
(``kda_mix_prefill`` / ``kda_mix_decode``: what the layer adds, the norm in
front its own), at 64 heads of 128, the decay and the output gate through a
bottleneck of ``kda_gate_rank`` (``in_proj = [q~ | k~ | v~ | a1 | g1 | b]``,
``a_up`` / ``g_up`` widen).

*Sparse latent attention* (DeepSeek-V3.2's sparse attention over MLA, this
config's pooling). ``[c_q | c] = W_a u``, each under an RMSNorm; ``q_h = W_qb,h
c_q``; ``c`` (``kv_lora_rank`` wide) IS the cached row, key and value at once:
no rotary anywhere (``qk_rope_head_dim`` 0), ``[k_h | v_h] = W_kvb,h c``. An
INDEXER picks what a query attends: ``qI_j = rope(W_qI,j c_q)`` over
``index_n_heads`` heads of ``index_head_dim``, ``kI_s = rope(LayerNorm(W_kI
u_s))``, ``w = W_w u``, the rotary on the first ``index_rope_dim`` channels;
the keys are POOLED, ``kbar_g = mean(kI_{pg..pg+p-1})`` with ``p =
index_kpool``; the query at ``t`` scores the groups that are whole before it,
``I[g] = sum_j w_j relu(qI_j . kbar_g)`` for ``g < t // p``, and attends the
positions of the ``index_topk / p`` best groups and, always, its own group up
to itself. The serving cache holds ``c`` in the latent pool and ``kbar`` beside
it (``serving.cache.HybridKVCache.index``). The prompt path scores, picks and
masks a block of queries at a time over the EXPANDED attention, reading the
rows and pooled keys as the cache will hold them; the decode path runs
``apex_dsa_index_fwd``, an exact top-k, a gather of the picked rows and
``apex_mla_decode_fwd`` over them
(:mod:`apex_tpu.transformer.functional.sparse_index`).

*Expert layer*: ``models.deepseek.expert_parts`` with one group (no group
limit) and the SwiGLU CLAMPED (``swiglu_limit``: ``silu(min(g, l)) * clip(u,
-l, l)``), in the experts, the shared expert and the dense MLP alike.

What the published config does not say is written down ONE way here, named
in :data:`ASSUMED`; the benchmark's configuration file states the same names,
and its runner and reference refuse a file that states another form.

Precision as ``models.bailing_hybrid``: two bfloat16 terms into every product
with a bfloat16 matrix, float32 between two products; the router's and the
indexer's products, the delta rule and the hyper-connections' maps in float32.
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.bailing_hybrid import kda_mix_decode, kda_mix_prefill
from apex_tpu.models.deepseek import (
    _by_head, expert_parts, rope, swiglu_mlp,
)
from apex_tpu.models.layers import (
    hyper_close, hyper_connect, hyper_open, init_hyper_connection,
)
from apex_tpu.models.nemotron_h import _dense, _rms, _two_terms, embed
from apex_tpu.transformer.functional.gated_delta import ring_of_tail
from apex_tpu.transformer.functional.mla_attention import mla_decode_attention
from apex_tpu.transformer.functional.sparse_index import (
    _NEG, gather_picked, index_scores, pick_groups,
)
from apex_tpu.utils.profiler import region

KDA, DSA = "kda", "dsa"

#: what the published config leaves open, as this file implements it
ASSUMED = {
    "kda_gates": "rank_128_bottleneck",
    "kda_decay": "lower_bound_times_sigmoid_of_a_times_x_plus_dt_bias",
    "index_pooling": "float32_mean_of_roped_keys_rounded_once",
    "index_topk_counts": "positions",
    "index_tail": "own_group_up_to_the_query_never_scored",
    "index_rope": "first_64_interleaved_pairs_theta_10000",
    "index_scales": "layernorm_key_heads_pow_minus_half_dim_pow_minus_half",
    "hyper_connections": "mhc_paper_one_set_a_sublayer_copied_in_summed_out",
    "swiglu_clamp": "silu_of_min_gate_times_clipped_up",
    "state_dtype": "float32",
    "router_bias": "balances_the_seeded_routers_load_as_noaux_tc_leaves_it",
}

# queries of a prompt that are scored, picked and attended at a time
_QUERY_BLOCK = 128
# positions of a long prompt that go through all the layers at a time
_STRETCH = 1024
# a stretch of a long prompt that ends within this many positions attends
# them alone, every later one all that the prompt's bucket holds
_KEY_EXTENT = 4096


def layer_types_of(first_layer: int, num_layers: int,
                   period: int = 4) -> Tuple[str, ...]:
    """The kinds of layers ``first_layer .. first_layer + num_layers - 1`` of
    the model: layer ``l`` is sparse attention iff ``l % period == period -
    1``."""
    return tuple(DSA if l % period == period - 1 else KDA
                 for l in range(first_layer, first_layer + num_layers))


@dataclasses.dataclass(frozen=True)
class GlmNextConfig:
    vocab_size: int = 154880
    hidden_size: int = 4096
    layer_types: Tuple[str, ...] = layer_types_of(0, 45)
    first_k_dense: int = 3
    num_heads: int = 64              # of both kinds of mixer
    head_dim: int = 128              # KDA's key and value channels a head
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0    # the config's gate_lower_bound
    kda_gate_rank: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 256
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048           # POSITIONS: index_topk / index_kpool groups
    index_kpool: int = 4
    index_rope_dim: int = 64
    index_rope_theta: float = 10000.0
    index_norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    ffn_size: int = 12288            # the dense layers' SwiGLU
    moe_ffn_size: int = 2048         # each routed expert's
    shared_experts: int = 1
    num_experts: int = 288           # the router's width
    experts_per_token: int = 8
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    experts_held: int = 288          # of num_experts, on this chip
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576

    #: the seam (``serving.decode``): per-slot state beside ONE pool of rows
    #: that are key and value at once, whose rows the attention picks
    recurrent = True
    latent = True
    indexed = True
    # one group of experts: no group limit (``moe.route``)
    n_group = 1
    topk_group = 1
    # no rotary in the attention: the cached row is the latent alone
    qk_rope_head_dim = 0

    def __post_init__(self):
        bad = set(self.layer_types) - {KDA, DSA}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad) or 'nothing'}; "
                             f"a layer is {KDA!r} or {DSA!r}")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(f"{self.first_k_dense} dense layers of "
                             f"{self.num_layers}")
        if self.index_topk % self.index_kpool or self.index_rope_dim % 2 \
                or self.index_rope_dim > self.index_head_dim:
            raise ValueError(
                f"index_topk {self.index_topk} counts positions in whole "
                f"groups of {self.index_kpool}, and the indexer's rotary "
                f"{self.index_rope_dim} is an even part of its "
                f"{self.index_head_dim} channels")
        if not 0 <= self.expert_offset <= self.num_experts \
                - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")
        if not -5.5 <= self.kda_lower_bound < 0:
            raise ValueError(f"kda_lower_bound {self.kda_lower_bound} is "
                             "outside [-5.5, 0): the chunked form's 16-row "
                             "blocks would overflow float32")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def kda_layers(self) -> int:
        return self.layer_types.count(KDA)

    @property
    def softmax_scale(self) -> float:
        return self.qk_nope_head_dim ** -0.5

    @property
    def kda_width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return 3 * self.kda_width

    @property
    def top_groups(self) -> int:
        return self.index_topk // self.index_kpool

    # -- what the serving engine asks (the seam) -----------------------------

    @property
    def kv_layers(self) -> int:
        """Layers of the page pool: the sparse-attention layers."""
        return self.layer_types.count(DSA)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank

    @property
    def kv_row_width(self) -> int:
        """Width of one cached row: the normed latent and nothing else."""
        return -(-self.latent_width // 128) * 128

    def state_shapes(self, num_slots: int) -> Tuple[Tuple[int, ...], ...]:
        """(recurrent state, convolution tail) of ``num_slots`` slots."""
        n = self.kda_layers
        return ((n, num_slots, self.num_heads, self.head_dim, self.head_dim),
                (n, num_slots, self.conv_kernel - 1, self.conv_channels))

    def state_bytes_per_slot(self) -> int:
        """Bytes one prefill writes for its slot besides the pages (float32
        state, convolution tails and the indexer's tail)."""
        state, conv = self.state_shapes(1)
        tail = self.index_shapes(1, 1, self.index_kpool)[1]
        return 4 * (math.prod(state) + math.prod(conv) + math.prod(tail))

    def index_shapes(self, num_slots: int, num_pages: int,
                     page_size: int) -> Tuple[Tuple[int, ...], ...]:
        """(pooled keys by page, the slots' tails) of the indexer's cache
        (``serving.cache.HybridKVCache.index``)."""
        if page_size % self.index_kpool:
            raise ValueError(f"pages of {page_size} positions do not hold "
                             f"whole groups of {self.index_kpool}")
        n = self.kv_layers
        return ((n, num_pages, page_size // self.index_kpool,
                 self.index_head_dim),
                (n, num_slots, self.index_kpool - 1, self.index_head_dim))

    def index_bytes_per_page(self, page_size: int, itemsize: int) -> int:
        return self.kv_layers * (page_size // self.index_kpool) \
            * self.index_head_dim * itemsize

    def counter_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """int32 counters the decode program keeps on the device:
        ``models.deepseek``'s, and the rows the sparse layers attended and
        the rows they could have, summed over active slots and steps."""
        n = self.moe_layers
        return {"moe_load": (n, self.experts_held), "moe_hit": (n,),
                "moe_steps": (1,), "dsa_rows_read": (1,),
                "dsa_rows_mapped": (1,)}

    def prefill_core(self, params, ids, mask, kv_dtype):
        x, states, tails, rows, index = prefill_layers(
            params, self, ids, mask, kv_dtype, last=True)
        return x, states, tails, rows, None, index

    def decode_core(self, params, cache, tokens, active):
        x, state, conv, counters, rows, index = decode_layers(
            params, self, cache, tokens, active)
        return x, state, conv, counters, rows, None, index

    def logits_of(self, params, x):
        return logits_of(params, self, x)


def glm_5_3_flash() -> GlmNextConfig:
    return GlmNextConfig()


def glm_next_tiny(**changes) -> GlmNextConfig:
    """Five layers as the benchmark's cut has them (layer 2 of the model,
    dense, then ``D K K K`` sparse), tiny."""
    return GlmNextConfig(**{**dict(
        vocab_size=512, hidden_size=64, layer_types=layer_types_of(2, 5),
        first_k_dense=1, num_heads=4, head_dim=16, kda_gate_rank=8,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, v_head_dim=16,
        index_n_heads=2, index_head_dim=16, index_topk=16, index_kpool=4,
        index_rope_dim=8, ffn_size=160, moe_ffn_size=48, num_experts=16,
        experts_per_token=4, experts_held=8, expert_offset=0,
        max_position_embeddings=256), **changes})


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: GlmNextConfig, dtype=jnp.float32,
         hc_spread: float = 0.5) -> Dict[str, Any]:
    """Random parameters: matrices ``N(0, 1/fan_in)``, the embedding 0.02,
    the convolution taps 0.5, norms 1, the router's bias 0, KDA's ``A`` and
    ``dt`` as ``models.bailing_hybrid``; the hyper-connections float32, their
    biases spread by ``hc_spread`` so that no stream idles."""
    h, nh, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    qr, kr, w, r = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.kda_width, \
        cfg.kda_gate_rank
    ih, iw = cfg.index_n_heads, cfg.index_head_dim

    def normal(k, fan_in, *shape):
        return (math.sqrt(1.0 / fan_in)
                * jax.random.normal(k, shape)).astype(dtype)

    def dense(k, fan_in, *shape):
        return {"kernel": normal(k, fan_in, *shape)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def kda(k):
        ks = jax.random.split(k, 7)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (w,), minval=math.log(1e-3), maxval=math.log(0.1)))
        return {"norm": norm(h),
                "in_proj": dense(ks[0], h, h, cfg.conv_channels + 2 * r + nh),
                "a_up": dense(ks[5], r, r, w), "g_up": dense(ks[6], r, r, w),
                "conv": {"weight": (0.5 * jax.random.normal(
                    ks[1], (cfg.conv_kernel, cfg.conv_channels))
                ).astype(dtype)},
                "a_log": jnp.log(jax.random.uniform(
                    ks[2], (nh,), minval=1.0, maxval=16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": norm(d), "out": dense(ks[4], w, w, h)}

    def dsa(k):
        ks = jax.random.split(k, 7)
        return {"norm": norm(h),
                "a_proj": dense(ks[0], h, h, qr + kr),
                "q_norm": norm(qr), "kv_norm": norm(kr),
                "q_b": dense(ks[1], qr, qr, nh * cfg.qk_nope_head_dim),
                "kv_b_k": normal(ks[2], kr, nh, cfg.qk_nope_head_dim, kr),
                "kv_b_v": normal(ks[3], kr, nh, kr, cfg.v_head_dim),
                "index_q": dense(ks[4], qr, qr, ih * iw),
                "index_kw": dense(ks[5], h, h, iw + ih),
                "index_norm": {"weight": jnp.ones((iw,), jnp.float32),
                               "bias": jnp.zeros((iw,), jnp.float32)},
                "out": dense(ks[6], nh * cfg.v_head_dim,
                             nh * cfg.v_head_dim, h)}

    def dense_mlp_params(k):
        k1, k2 = jax.random.split(k)
        return {"mlp_norm": norm(h),
                "gate_up": dense(k1, h, h, 2 * cfg.ffn_size),
                "down": dense(k2, cfg.ffn_size, cfg.ffn_size, h)}

    def expert_params(k):
        ks = jax.random.split(k, 5)
        f, sf = cfg.moe_ffn_size, cfg.shared_experts * cfg.moe_ffn_size
        return {"mlp_norm": norm(h),
                "router": dense(ks[0], h, h, cfg.num_experts),
                "router_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
                "w_gate_up": normal(ks[1], h, cfg.experts_held, h, 2 * f),
                "w_down": normal(ks[2], f, cfg.experts_held, f, h),
                "shared_gate_up": dense(ks[3], h, h, 2 * sf),
                "shared_down": dense(ks[4], sf, sf, h)}

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for at, k in enumerate(jax.random.split(k_layers, cfg.num_layers)):
        k_mix, k_mlp, k_hc1, k_hc2 = jax.random.split(k, 4)
        mixer = (kda if cfg.layer_types[at] == KDA else dsa)(k_mix)
        mlp = (dense_mlp_params if at < cfg.first_k_dense
               else expert_params)(k_mlp)
        layers.append({**mixer, **mlp,
                       "hc_mixer": init_hyper_connection(
                           k_hc1, cfg.hc_mult, h, hc_spread),
                       "hc_mlp": init_hyper_connection(
                           k_hc2, cfg.hc_mult, h, hc_spread)})
    return {
        "embedding": {"word": {"embedding": (0.02 * jax.random.normal(
            k_emb, (cfg.vocab_size, h))).astype(dtype)}},
        "layers": layers,
        "final_norm": norm(h),
        "head": dense(k_head, h, h, cfg.vocab_size),
    }


# ---------------------------------------------------------------------------
# what both paths share
# ---------------------------------------------------------------------------

def _connect(streams, hp, cfg, f):
    return hyper_connect(streams, hp, f, iters=cfg.hc_sinkhorn_iters,
                         eps=cfg.hc_eps)


def to_streams(x, cfg):
    """The embedding copied into the residual streams: (rows, hidden) ->
    (rows, hc_mult, hidden)."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg.hc_mult, x.shape[1]))


@region("head")
def logits_of(params, cfg, x):
    """The streams' sum under the final norm, and the untied head: (rows,
    hc_mult, hidden) -> float32 logits."""
    return _dense(params["head"], _rms(params["final_norm"], jnp.sum(x, 1),
                                       cfg.rms_norm_eps))


def _float32_dense(p, x):
    """``x @ kernel`` with both in float32, at full precision: the indexer's
    products, as the router's."""
    return jnp.dot(x, p["kernel"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def _index_rope(x, cfg, pos):
    """The rotary of the indexer on the first ``index_rope_dim`` channels of
    ``x`` (rows, ..., index_head_dim) at ``pos`` (rows,): interleaved pairs,
    which stand de-interleaved after it (queries and keys alike)."""
    d = cfg.index_rope_dim
    inv_freq = jnp.asarray([cfg.index_rope_theta ** (-i / d)
                            for i in range(0, d, 2)], jnp.float32)
    theta = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(theta), jnp.sin(theta)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([rope(x[..., :d], cos, sin), x[..., d:]], -1)


def _dsa_in(lp, u, cfg, pos):
    """Normed rows ``u`` (rows, hidden) at ``pos`` -> each head's query (rows,
    heads, nope), the cache row (rows, kv_row_width) float32, the indexer's
    queries (rows, index heads, width), its key (rows, width) and its head
    weights (rows, index heads) with both scales folded in."""
    a = _dense(lp["a_proj"], u)
    qr, rows = cfg.q_lora_rank, u.shape[0]
    c_q = _rms(lp["q_norm"], a[:, :qr], cfg.rms_norm_eps)
    row = _rms(lp["kv_norm"], a[:, qr:], cfg.rms_norm_eps)
    pad = cfg.kv_row_width - cfg.latent_width
    if pad:
        row = jnp.pad(row, ((0, 0), (0, pad)))
    q = _dense(lp["q_b"], c_q).reshape(rows, cfg.num_heads, -1)
    iw, ih = cfg.index_head_dim, cfg.index_n_heads
    iq = _index_rope(_float32_dense(lp["index_q"], c_q).reshape(rows, ih, iw),
                     cfg, pos)
    kw = _float32_dense(lp["index_kw"], u)
    ik = kw[:, :iw]
    ik = ik - jnp.mean(ik, -1, keepdims=True)
    ik = ik * lax.rsqrt(jnp.mean(ik * ik, -1, keepdims=True)
                        + cfg.index_norm_eps)
    ik = _index_rope(ik * lp["index_norm"]["weight"]
                     + lp["index_norm"]["bias"], cfg, pos)
    return q, row, iq, ik, kw[:, iw:] * (ih ** -0.5 * iw ** -0.5)


def _cut(x, dtype):
    """``x`` (float32) rounded to ``dtype``, as a cache of that dtype keeps
    it; cut with ``lax.reduce_precision``, which the compiler has to honour
    (``models.nemotron_h._two_terms``)."""
    if jnp.dtype(dtype) != jnp.float32:
        bits = jnp.finfo(dtype)
        x = lax.reduce_precision(x, exponent_bits=bits.nexp,
                                 mantissa_bits=bits.nmant)
    return x.astype(dtype)


def prompt_index_scores(iq, w, keys):
    """``iq`` (rows, heads, width), ``w`` (rows, heads) float32 against the
    pooled keys ``keys`` (groups, width) as the cache holds them: (rows,
    groups) float32, the arithmetic of ``apex_dsa_index_fwd`` (the query as
    two terms of the keys' dtype, float32 sums)."""
    if keys.dtype == jnp.float32:
        s = jnp.einsum("rhd,gd->rhg", iq, keys,
                       precision=lax.Precision.HIGHEST)
    else:
        rows = iq.shape[0]
        terms = jnp.concatenate(_two_terms(iq, keys.dtype))
        s = jnp.einsum("rhd,gd->rhg", terms, keys,
                       preferred_element_type=jnp.float32)
        s = s[:rows] + s[rows:]
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], 1)


def attended(scores, pos, at, cfg):
    """Which positions ``at`` (n,) the queries at ``pos`` (rows,) attend,
    given their scores of every group (rows, groups): the positions of the
    ``top_groups`` best among the groups whole before the query, and the
    query's own group up to itself: ``(allowed (rows, n), picked (rows,
    groups))`` bool. The groups picked are
    ``lax.top_k``'s exactly, ties to the lower group (a score is exactly 0
    where no head's product is positive), as a mask and without a scatter:
    above the least score picked, or equal to it and early enough."""
    p = cfg.index_kpool
    whole = jnp.arange(scores.shape[1])[None, :] < (pos // p)[:, None]
    scores = jnp.where(whole, scores, _NEG)
    k = min(cfg.top_groups, scores.shape[1])
    least = lax.top_k(scores, k)[0][:, -1:]
    above, ties = whole & (scores > least), whole & (scores == least)
    room = k - jnp.sum(above, 1, keepdims=True)
    picked = above | (ties & (jnp.cumsum(ties, 1) <= room))
    own = (at[None, :] // p == (pos // p)[:, None]) \
        & (at[None, :] <= pos[:, None])
    return jnp.repeat(picked, p, axis=1)[:, :at.shape[0]] | own, picked


def _masked_attention(q, k, v, allowed, scale, dtype):
    """``q`` (rows, heads, d), ``k`` / ``v`` (heads, n, d) in ``dtype``,
    ``allowed`` (rows, n) bool: each query's softmax over the positions it is
    allowed. (rows, heads, d_v) float32."""
    if jnp.dtype(dtype) == jnp.float32:
        how = dict(precision=lax.Precision.HIGHEST)
    else:
        how = dict(preferred_element_type=jnp.float32)
        q = q.astype(dtype)
    s = jnp.einsum("rhd,hnd->hrn", q, k, **how) * scale
    s = jnp.where(allowed[None], s, _NEG)
    p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = p / jnp.sum(p, -1, keepdims=True)
    return jnp.einsum("hrn,hnd->rhd", p.astype(dtype), v, **how)


# ---------------------------------------------------------------------------
# the sparse-attention layer
# ---------------------------------------------------------------------------

def dsa_mix_prefill(lp, x, cfg, kv_dtype, held, first, picks=False):
    """What one sparse-attention layer adds over ONE STRETCH of a prompt: ``x``
    (n, hidden), the positions ``first .. first + n - 1``. ``held`` is what
    the layer keeps of the whole prompt (:func:`_held_of_a_prompt`): the cache
    rows, each head's keys and values expanded from them, the pooled keys,
    all in ``kv_dtype``, and the indexer's keys in float32; the stretch writes
    its own part and attends what is held up to itself. Returns ``(y, held')``
    and, when ``picks`` are asked for, the groups each query picked (n,
    groups) bool after them. The prompt attends the rows and picks by the
    pooled keys AS THE CACHE WILL HOLD THEM."""
    n, p = x.shape[0], cfg.index_kpool
    pos = first + jnp.arange(n, dtype=jnp.int32)
    q, row, iq, ik, w = _dsa_in(lp, _rms(lp["norm"], x, cfg.rms_norm_eps),
                                cfg, pos)
    row = _cut(row, kv_dtype)
    latent = row[:, :cfg.kv_lora_rank]
    put = lambda name, new, at: lax.dynamic_update_slice(
        held[name], new.astype(held[name].dtype), at)
    with jax.named_scope("dsa_index"):
        # (a last group that is not whole is never scored)
        pooled = jnp.mean(jnp.pad(ik, ((0, -n % p), (0, 0))).reshape(
            -1, p, ik.shape[1]), 1)
        keys = put("keys", _cut(pooled, kv_dtype), (first // p, 0))
    held = {
        "rows": put("rows", row, (first, 0)), "keys": keys,
        "ik": put("ik", ik, (first, 0)),
        "k": put("k", _by_head("sc,hdc->hsd", latent,
                               lp["kv_b_k"].astype(kv_dtype), 1),
                 (0, first, 0)),
        "v": put("v", _by_head("sc,hcd->hsd", latent,
                               lp["kv_b_v"].astype(kv_dtype), 1),
                 (0, first, 0))}
    s = held["rows"].shape[0]
    b = _QUERY_BLOCK if n % _QUERY_BLOCK == 0 else n
    queries = tuple(t.reshape(n // b, b, *t.shape[1:])
                    for t in (q, iq, w, pos))

    def over(extent):       # every block of queries against ``extent`` keys
        every = jnp.arange(extent, dtype=jnp.int32)

        def block(of):
            q, iq, w, at = of
            with jax.named_scope("dsa_index"):
                scores = prompt_index_scores(iq, w, keys[:-(-extent // p)])
            with jax.named_scope("dsa_topk"):
                allowed, picked = attended(scores, at, every, cfg)
            with jax.named_scope("dsa_attend"):
                ctx = _masked_attention(
                    q, held["k"][:, :extent], held["v"][:, :extent], allowed,
                    cfg.softmax_scale, kv_dtype)
            return (ctx, picked) if picks else (ctx,)

        return lambda queries: lax.map(block, queries)

    # (the groups picked are handed back over the whole prompt's groups; a
    # third, middle extent of 8,192 compiled to a score fusion twenty times
    # slower than either of these: my chip run, PR 47)
    if picks or s <= _KEY_EXTENT or _KEY_EXTENT % n:
        out = over(s)(queries)
    else:
        out = lax.cond(first + n <= _KEY_EXTENT, over(_KEY_EXTENT), over(s),
                       queries)
    ctx, *picked = (t.reshape(n, *t.shape[2:]) for t in out)
    return (_dense(lp["out"], ctx.reshape(n, -1)), held, *picked)


def _held_of_a_prompt(cfg, s: int, kv_dtype):
    """What a sparse-attention layer keeps of a prompt of ``s`` positions
    while it is taken a stretch at a time, zeroed."""
    p, nh = cfg.index_kpool, cfg.num_heads
    return {"rows": jnp.zeros((s, cfg.kv_row_width), kv_dtype),
            "keys": jnp.zeros((-(-s // p), cfg.index_head_dim), kv_dtype),
            "ik": jnp.zeros((s + p, cfg.index_head_dim), jnp.float32),
            "k": jnp.zeros((nh, s, cfg.qk_nope_head_dim), kv_dtype),
            "v": jnp.zeros((nh, s, cfg.v_head_dim), kv_dtype)}


def dsa_mix_decode(lp, x, cfg, cache, layer: int, pos, active):
    """What one sparse-attention layer adds for one token of every slot, at
    ``pos`` (b,): the indexer scores the slot's whole groups out of the
    indexer's cache (``apex_dsa_index_fwd``), the best are picked, their
    latent rows and the slot's tail are gathered, and ``apex_mla_decode_fwd``
    attends them with the queries absorbed. Returns ``(y, row (b,
    kv_row_width): the new latent, key (b, width): the pooled key of the group
    this token closes (whatever, where it closes none), tail' (b, index_kpool
    - 1, width), attended (b,): how many positions each slot attended)``."""
    p = cfg.index_kpool
    q, row, iq, ik, w = _dsa_in(lp, _rms(lp["norm"], x, cfg.rms_norm_eps),
                                cfg, pos)
    row = _cut(row, cache.k.dtype)
    at = jnp.where(active, pos, 0)
    groups = at // p
    with jax.named_scope("dsa_index"):
        scores = index_scores(iq, w, cache.index["rows"],
                              cache.block_tables, groups, jnp.int32(layer))
    with jax.named_scope("dsa_topk"):
        picked, count = pick_groups(scores, groups, cfg.top_groups)
    with jax.named_scope("dsa_gather"):
        buffer, table, length = gather_picked(
            cache.k, layer, cache.block_tables, picked, count, at, p)
    with jax.named_scope("dsa_attend"):
        q_lat = _by_head("bhd,hdc->bhc", q, lp["kv_b_k"], 0) \
            * cfg.softmax_scale
        pad = cfg.kv_row_width - q_lat.shape[-1]
        if pad:
            q_lat = jnp.pad(q_lat, ((0, 0), (0, 0), (0, pad)))
        o_lat = mla_decode_attention(q_lat, row, buffer, table, length,
                                     jnp.int32(0),
                                     value_width=cfg.kv_lora_rank)
        ctx = _by_head("bhc,hcd->bhd", o_lat, lp["kv_b_v"], 0)
    with jax.named_scope("dsa_index"):
        tail = cache.index["tail"][layer]
        key = (jnp.sum(tail, 1) + ik) / p
        new = (jnp.arange(p - 1) == (pos % p)[:, None]) & active[:, None]
        tail = jnp.where(new[..., None], ik[:, None], tail)
    return _dense(lp["out"], ctx.reshape(x.shape[0], -1)), row, key, tail, \
        length + 1


# ---------------------------------------------------------------------------
# the MLPs on the streams
# ---------------------------------------------------------------------------

def _dense_mlp(lp, streams, cfg):
    with region("mlp"):
        return _connect(streams, lp["hc_mlp"], cfg, lambda u: swiglu_mlp(
            lp, _rms(lp["mlp_norm"], u, cfg.rms_norm_eps), cfg.swiglu_limit))


def _expert_mlp(lp, streams, cfg, real):
    """The expert sub-layer on the streams. Returns ``(streams', sizes
    (experts_held,), chosen (rows, k))``."""
    with region("experts"):
        mix = hyper_open(streams, lp["hc_mlp"], iters=cfg.hc_sinkhorn_iters,
                         eps=cfg.hc_eps)
    with region("router"):
        u = _rms(lp["mlp_norm"], mix[0], cfg.rms_norm_eps)
    routed, shared, sizes, chosen = expert_parts(lp, u, cfg, real)
    with region("experts"):
        return hyper_close(streams, mix, routed + shared), sizes, chosen


# ---------------------------------------------------------------------------
# the layers: over a prompt, and one token per slot against the cache
# ---------------------------------------------------------------------------

def prefill_layers(params, cfg: GlmNextConfig, ids, mask,
                   kv_dtype=jnp.float32, routes=False, last=False):
    """Every layer over one prompt: ``ids`` (s,). Returns ``(streams (s,
    hc_mult, hidden), or with ``last`` the last real token's alone (1,
    hc_mult, hidden): all a server reads of them, states (KDA layers, H, d, d), tails (KDA layers, w-1, 3
    H d), rows (sparse layers, s, kv_row_width), index: (pooled keys (sparse
    layers, s / index_kpool, width), which the serving seam folds into pages,
    tails (sparse layers, index_kpool - 1, width)))`` and, when ``routes``
    are asked for, the routers' choices ``(expert layers, s, k)`` and the
    groups the sparse layers' queries picked ``(sparse layers, s, groups)``
    after them.

    A long prompt is taken ``_STRETCH`` positions at a time through ALL the
    layers: a KDA layer goes on from the state and the tail the stretch
    before left, a sparse layer attends what it holds of the stretches before
    (:func:`_held_of_a_prompt`). Nothing but that and the streams handed back
    is then of the prompt's length: at 64 heads the chunked delta rule's
    operands alone are 3 GB at 12,288 positions. A server (``last`` and no
    ``routes``) runs the stretches its prompt has and no more, so one bucket
    of the longest prompt costs a short one what its own would; everyone else
    gets every stretch's streams, from a scan."""
    s, p = ids.shape[0], cfg.index_kpool
    n = _STRETCH if s % _STRETCH == 0 else s
    state, conv = cfg.state_shapes(1)
    length = jnp.sum(mask).astype(jnp.int32)
    start = {"state": jnp.zeros(state[:1] + state[2:], jnp.float32),
             "tail": jnp.zeros(conv[:1] + conv[2:], jnp.float32),
             "held": [_held_of_a_prompt(cfg, s, kv_dtype)
                      for _ in range(cfg.kv_layers)]}
    if last:
        start["last"] = jnp.zeros((1, cfg.hc_mult, cfg.hidden_size),
                                  jnp.float32)

    def stretch(carry, of):
        ids, mask, first = of
        real = mask.astype(bool)
        x = to_streams(embed(params, ids), cfg)
        states, tails, held, chosen, picks = [], [], [], [], []
        for at, lp in enumerate(params["layers"]):
            if cfg.layer_types[at] == KDA:
                i = len(states)
                with region("mixer"):
                    x, state, tail = _connect(
                        x, lp["hc_mixer"], cfg,
                        lambda u: kda_mix_prefill(
                            lp, u, cfg, mask,
                            (carry["state"][i], carry["tail"][i])))
                states.append(state)
                tails.append(tail)
            else:
                with region("attention"):
                    x, kept, *picked = _connect(
                        x, lp["hc_mixer"], cfg,
                        lambda u: dsa_mix_prefill(
                            lp, u, cfg, kv_dtype, carry["held"][len(held)],
                            first, picks=routes))
                held.append(kept)
                picks += picked
            if at < cfg.first_k_dense:
                x = _dense_mlp(lp, x, cfg)
            else:
                x, _, picked = _expert_mlp(lp, x, cfg, real)
                chosen.append(picked)
        new = {"state": jnp.stack(states), "tail": jnp.stack(tails),
               "held": held}
        if last:
            at = length - 1 - first
            new["last"] = jnp.where(
                (at >= 0) & (at < n), lax.dynamic_slice_in_dim(
                    x, jnp.clip(at, 0, n - 1), 1, 0), carry["last"])
            x = x[:0]
        return new, (x, jnp.stack(chosen), jnp.stack(picks)) if routes \
            else (x,)

    if last and not routes:
        # what a server runs: the stretches behind the prompt's last token
        # are not run at all (they would hold the state and write rows that
        # nobody reads)
        def go_on(i, carry):
            cut = lambda t: lax.dynamic_slice_in_dim(t, i * n, n, 0)
            return stretch(carry, (cut(ids), cut(mask), i * n))[0]

        end, out = lax.fori_loop(0, jnp.maximum(-(-length // n), 1), go_on,
                                 start), (None,)
    else:
        end, out = lax.scan(stretch, start, (
            ids.reshape(-1, n), mask.reshape(-1, n),
            jnp.arange(0, s, n, dtype=jnp.int32)))
    x, *decided = out
    # the tails as the rings the decode path goes on from: position t in row
    # t % (w - 1), and of the last group in row t % index_kpool
    result = (
        end["last"] if last else x.reshape(s, *x.shape[2:]), end["state"],
        jax.vmap(lambda tail: ring_of_tail(tail, length))(end["tail"]),
        jnp.stack([h["rows"] for h in end["held"]]),
        (jnp.stack([h["keys"] for h in end["held"]]),
         jnp.stack([lax.dynamic_slice_in_dim(h["ik"], length // p * p, p - 1,
                                             0) for h in end["held"]])))
    # (expert or sparse layers, stretches, n, ...) -> (layers, s, ...)
    return result + tuple(
        jnp.moveaxis(t, 0, 1).reshape(t.shape[1], s, *t.shape[3:])
        for t in decided)


def decode_layers(params, cfg: GlmNextConfig, cache, tokens, active):
    """One token for every slot against the serving cache
    (``serving.cache.HybridKVCache`` with a latent pool and an index). Returns
    ``(streams (slots, hc_mult, hidden), state', conv', counters', rows
    (sparse layers, slots, kv_row_width), index: (keys (sparse layers, slots,
    width), tails'))`` for the engine to write."""
    pos = cache.lengths
    x = to_streams(embed(params, tokens), cfg)
    state, conv = cache.state, cache.conv
    tails = cache.index["tail"]
    counters = {**cache.counters, "moe_steps": cache.counters["moe_steps"] + 1}
    rows, keys = [], []
    n_kda = 0
    for at, lp in enumerate(params["layers"]):
        if cfg.layer_types[at] == KDA:
            with region("mixer"):
                x, state, conv = _connect(
                    x, lp["hc_mixer"], cfg,
                    lambda u: kda_mix_decode(lp, u, cfg, state, conv, n_kda,
                                             pos, active))
            n_kda += 1
        else:
            with region("attention"):
                x, row, key, tail, read = _connect(
                    x, lp["hc_mixer"], cfg,
                    lambda u: dsa_mix_decode(lp, u, cfg, cache, len(rows),
                                             pos, active))
                tails = lax.dynamic_update_index_in_dim(tails, tail,
                                                        len(rows), 0)
                counters = {
                    **counters,
                    "dsa_rows_read": counters["dsa_rows_read"] + jnp.sum(
                        jnp.where(active, read, 0)),
                    "dsa_rows_mapped": counters["dsa_rows_mapped"] + jnp.sum(
                        jnp.where(active, pos + 1, 0))}
            rows.append(row)
            keys.append(key)
        if at < cfg.first_k_dense:
            x = _dense_mlp(lp, x, cfg)
            continue
        x, sizes, _ = _expert_mlp(lp, x, cfg, active)
        with region("experts"):
            e = at - cfg.first_k_dense
            counters = {
                **counters,
                "moe_load": counters["moe_load"].at[e].add(sizes),
                "moe_hit": counters["moe_hit"].at[e].add(jnp.sum(sizes > 0))}
    return x, state, conv, counters, jnp.stack(rows), \
        (jnp.stack(keys), tails)


def apply(params, cfg: GlmNextConfig, ids):
    """(s,) token ids -> (s, vocab) float32 logits: the whole forward, no
    cache."""
    x = prefill_layers(params, cfg, ids, jnp.ones(ids.shape, jnp.int32))[0]
    return logits_of(params, cfg, x)
