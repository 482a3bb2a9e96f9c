"""A decoder of pre-norm blocks, ``x <- x + mixer(RMSNorm(x))``, ``x <- x +
mlp(RMSNorm(x))``, whose mixer is Kimi Delta Attention (KDA: linear attention
with a recurrent state whose decay is a VECTOR over the key channels) in five
layers of six and multi-head LATENT attention (MLA) in the sixth, and whose
MLP is a dense SwiGLU in the first ``first_k_dense`` layers and a sparse
expert layer after them (the ``bailing_hybrid`` family: Ling 3.0).

*KDA* (arXiv:2510.26692), ``H`` heads of ``d`` key and value channels: ``q~,
k~, v~`` are projections of ``H d`` channels each; each channel passes a
causal depthwise convolution of ``conv_kernel`` taps and SiLU; per head ``q =
l2norm(q~) / sqrt(d)``, ``k = l2norm(k~)``, ``v = v~``; ``log a =
kda_lower_bound * sigmoid(exp(A_log[h]) * (W_a u + dt_bias))`` per CHANNEL,
in ``(kda_lower_bound, 0)``; ``b = sigmoid(W_b u)`` per head; the recurrence

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t

(:mod:`apex_tpu.transformer.functional.gated_delta`, the per-channel calls
``apex_kda_chunk_fwd`` / ``apex_kda_decode_fwd``); the layer's output is ``W_o
[RMSNorm_d(o) * sigmoid(W_g u)]``, the gate per channel. The six projections
of ``u`` are stored fused, ``in_proj = [q~ | k~ | v~ | a | g | b]``.

*MLA* as ``models.deepseek`` has it, whose pieces it IMPORTS
(``latent_row_parts``, ``split_queries``, ``expanded_attention``,
``absorbed_attention``: the latent's norm, the rotation, the expansion over a
prompt, the absorption of ``W_kvb`` against the pool), with no query latent
(``q = W_q u`` in one product), a plain rotary table and one gate a head:
``y = W_o concat_h(o_h * sigmoid(w_g,h . u))``; ``a_proj = [q | c_kv | k_pe |
gate]``. The cache holds the normed latent and the roped shared key of the
MLA layers only, one row a token a layer (``kv_row_width``), in ONE pool.

The serving cache is therefore ``serving.cache.HybridKVCache`` with ``v``
``None``: per-slot state and convolution tails of the KDA layers beside one
latent pool (``recurrent`` and ``latent`` both: ``serving.decode``, "the
seam").

*Expert layer*: ``models.deepseek``'s (``expert_mlp``: sigmoid scores in
float32, a choice-only bias, the best ``topk_group`` of ``n_group`` groups,
normalised weights times ``routed_scaling_factor``, SwiGLU experts and one
shared expert; the chip HOLDS ``experts_held`` of the router's
``num_experts``). No held layer clamps its SwiGLU: layers 34-41 of the model
do, and which of the two published clamp forms that is the config does not
say, so whoever reads a configuration file refuses a non-zero entry of a held
layer (``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``).

Layers are UNROLLED (``params["layers"]`` is a list, ``cfg.layer_types`` says
which kind each is), so that every call site knows its kind and an expert
layer's matrices are its own arrays. What the published config does not say
is written down ONE way here, named in :data:`ASSUMED`: the benchmark's
configuration file states the same names under ``assumed``, and its runner
and reference refuse a file that states another form.

Precision as ``models.nemotron_h`` / ``models.deepseek``: the inputs of every
product into a bfloat16 matrix as two bfloat16 terms, float32 between two
products, the router's product and the whole delta rule in float32.
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.deepseek import (
    absorbed_attention, dense_mlp, expanded_attention, expert_mlp,
    latent_row_parts, logits_of, split_queries,
)
from apex_tpu.models.hybrid import _l2norm
from apex_tpu.models.nemotron_h import _dense, _rms, embed
from apex_tpu.transformer.functional.gated_delta import (
    CHUNK, causal_conv, conv_ring_step, gated_delta_chunked,
    gated_delta_step, ring_of_tail,
)
from apex_tpu.utils.profiler import region

KDA, MLA = "kda", "mla"

#: what the published config leaves open, as this file implements it
ASSUMED = {
    "norm_placement": "pre",
    "kda_output_gate": "per_channel_full_rank",
    "mla_output_gate": "head_wise",
    "mla_qk_norm": "latent_only",
    "kda_decay": "lower_bound_times_sigmoid_of_a_times_x_plus_dt_bias",
    "state_dtype": "float32",
    "group_score": "top2_sum",
}


def layer_types_of(first_layer: int, num_layers: int,
                   layer_group_size: int) -> Tuple[str, ...]:
    """The kinds of layers ``first_layer .. first_layer + num_layers - 1`` of
    the model: layer ``l`` is MLA iff ``(l + 1) % layer_group_size == 0``."""
    return tuple(MLA if (l + 1) % layer_group_size == 0 else KDA
                 for l in range(first_layer, first_layer + num_layers))


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    layer_types: Tuple[str, ...] = layer_types_of(0, 42, 6)
    first_k_dense: int = 2
    num_heads: int = 32              # of both kinds of mixer
    head_dim: int = 128              # KDA's key and value channels a head
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_size: int = 6144             # the dense layers' SwiGLU
    moe_ffn_size: int = 768          # each routed expert's
    shared_experts: int = 1          # of moe_ffn_size each, fused into one
    num_experts: int = 512           # the router's width
    experts_per_token: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    experts_held: int = 512          # of num_experts, on this chip
    expert_offset: int = 0           # the first of them
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6e6
    max_position_embeddings: int = 131072

    #: the seam (``serving.decode``): per-slot state beside the pool, AND the
    #: pool is one pool of rows that are key and value at once
    recurrent = True
    latent = True

    def __post_init__(self):
        bad = set(self.layer_types) - {KDA, MLA}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types holds {sorted(bad) or 'nothing'}; "
                             f"a layer is {KDA!r} or {MLA!r}")
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
        # exp((block - 1) * -bound) has to stay a float32 (gated_delta)
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
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def kda_width(self) -> int:
        """Channels of each of q~, k~, v~, the decay and the output gate."""
        return self.num_heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return 3 * self.kda_width

    def rope_angles(self, pos):
        """(cos, sin) (..., pairs) float32 of the rotary pairs at ``pos``:
        pair ``i`` turns at ``rope_theta ** (-2i / qk_rope_head_dim)``."""
        d = self.qk_rope_head_dim
        inv_freq = jnp.asarray(
            [self.rope_theta ** (-i / d) for i in range(0, d, 2)],
            jnp.float32)
        theta = pos.astype(jnp.float32)[..., None] * inv_freq
        return jnp.cos(theta), jnp.sin(theta)

    # -- what the serving engine asks (the seam) -----------------------------

    @property
    def kv_layers(self) -> int:
        """Layers of the page pool: the MLA layers."""
        return self.layer_types.count(MLA)

    @property
    def latent_width(self) -> int:
        """What a cached row holds: ``c_kv`` and ``k_pe``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_row_width(self) -> int:
        """Width of one cached row: :attr:`latent_width` in whole 128-lane
        tiles (``models.deepseek``)."""
        return -(-self.latent_width // 128) * 128

    def state_shapes(self, num_slots: int) -> Tuple[Tuple[int, ...], ...]:
        """(recurrent state, convolution tail) of ``num_slots`` slots."""
        n = self.kda_layers
        return ((n, num_slots, self.num_heads, self.head_dim, self.head_dim),
                (n, num_slots, self.conv_kernel - 1, self.conv_channels))

    def state_bytes_per_slot(self) -> int:
        """Bytes one prefill writes for its slot besides the pages
        (float32 state and tails)."""
        state, conv = self.state_shapes(1)
        return 4 * (math.prod(state) + math.prod(conv))

    def counter_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """int32 counters the decode program keeps on the device
        (``models.deepseek``'s)."""
        n = self.moe_layers
        return {"moe_load": (n, self.experts_held), "moe_hit": (n,),
                "moe_steps": (1,)}

    def prefill_core(self, params, ids, mask, kv_dtype):
        x, states, tails, rows = prefill_layers(
            params, self, embed(params, ids), mask, kv_dtype)
        return x, states, tails, rows, None

    def decode_core(self, params, cache, tokens, active):
        x, state, conv, counters, rows = decode_layers(
            params, self, cache, tokens, active)
        return x, state, conv, counters, rows, None

    def logits_of(self, params, x):
        return logits_of(params, self, x)


def ling3_flash() -> BailingHybridConfig:
    return BailingHybridConfig()


def bailing_hybrid_tiny(**changes) -> BailingHybridConfig:
    """Seven layers as the benchmark's cut has them (layer 1 of the model,
    dense, then ``K K K M K K`` sparse), tiny."""
    return BailingHybridConfig(**{**dict(
        vocab_size=512, hidden_size=64, layer_types=layer_types_of(1, 7, 6),
        first_k_dense=1, num_heads=4, head_dim=16, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, ffn_size=160,
        moe_ffn_size=48, num_experts=16, experts_per_token=4, n_group=4,
        topk_group=2, experts_held=8, expert_offset=0,
        max_position_embeddings=256), **changes})


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(key: jax.Array, cfg: BailingHybridConfig,
         dtype=jnp.float32) -> Dict[str, Any]:
    """Random parameters: matrices ``N(0, 1/fan_in)``, the embedding 0.02,
    the convolution taps 0.5, norms 1, the router's bias 0; ``A`` uniform in
    1..16 and ``dt`` log-uniform in 0.001..0.1 with its inverse softplus as
    ``dt_bias`` (the layer's published initialisation)."""
    h, nh, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    kr, w = cfg.kv_lora_rank, cfg.kda_width

    def normal(k, fan_in, *shape):
        return (math.sqrt(1.0 / fan_in)
                * jax.random.normal(k, shape)).astype(dtype)

    def dense(k, fan_in, *shape):
        return {"kernel": normal(k, fan_in, *shape)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def kda(k):
        ks = jax.random.split(k, 5)
        dt = jnp.exp(jax.random.uniform(
            ks[3], (w,), minval=math.log(1e-3), maxval=math.log(0.1)))
        return {"norm": norm(h),
                "in_proj": dense(ks[0], h, h, cfg.conv_channels + 2 * w + nh),
                "conv": {"weight": (0.5 * jax.random.normal(
                    ks[1], (cfg.conv_kernel, cfg.conv_channels))
                ).astype(dtype)},
                "a_log": jnp.log(jax.random.uniform(
                    ks[2], (nh,), minval=1.0, maxval=16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": norm(d), "out": dense(ks[4], w, w, h)}

    def mla(k):
        ks = jax.random.split(k, 4)
        return {"norm": norm(h),
                "a_proj": dense(ks[0], h, h, nh * cfg.qk_head_dim
                                + cfg.latent_width + nh),
                "kv_norm": norm(kr),
                "kv_b_k": normal(ks[1], kr, nh, cfg.qk_nope_head_dim, kr),
                "kv_b_v": normal(ks[2], kr, nh, kr, cfg.v_head_dim),
                "out": dense(ks[3], nh * cfg.v_head_dim,
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
        k_mix, k_mlp = jax.random.split(k)
        mixer = (kda if cfg.layer_types[at] == KDA else mla)(k_mix)
        mlp = (dense_mlp_params if at < cfg.first_k_dense
               else expert_params)(k_mlp)
        layers.append({**mixer, **mlp})
    return {
        "embedding": {"word": {"embedding": (0.02 * jax.random.normal(
            k_emb, (cfg.vocab_size, h))).astype(dtype)}},
        "layers": layers,
        "final_norm": norm(h),
        "head": dense(k_head, h, h, cfg.vocab_size),
    }


# ---------------------------------------------------------------------------
# the KDA layer
# ---------------------------------------------------------------------------

def _kda_in(lp, x, cfg):
    """(rows, hidden) -> the convolution's input (rows, 3 H d), ``log a``
    (rows, H, d), the output gate's input (rows, H d), ``b`` (rows, H). A
    config with a ``kda_gate_rank`` (``models.glm_next``) has the decay and
    the output gate pass a bottleneck of that rank: ``in_proj`` then holds
    their two narrow inputs where this family's holds the two wide ones, and
    ``a_up`` / ``g_up`` widen them."""
    proj = _dense(lp["in_proj"], _rms(lp["norm"], x, cfg.rms_norm_eps))
    c, w = cfg.conv_channels, cfg.kda_width
    rows = x.shape[0]
    r = getattr(cfg, "kda_gate_rank", 0)
    n = r or w                  # the width of each gate's input in ``proj``
    a = _dense(lp["a_up"], proj[:, c:c + n]) if r else proj[:, c:c + n]
    a = (a + lp["dt_bias"]).reshape(rows, cfg.num_heads, -1)
    log_decay = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[:, None] * a)
    conv_in, gate = proj[:, :c], proj[:, c + n:c + 2 * n]
    if r:
        gate = _dense(lp["g_up"], gate)
    return conv_in, log_decay, gate, jax.nn.sigmoid(proj[:, c + 2 * n:])


def _kda_heads(conv_out, cfg):
    """Convolved channels (rows, 3 H d) -> q, k, v (rows, H, d) float32,
    after SiLU, the norms and q's scale."""
    nh, d = cfg.num_heads, cfg.head_dim
    y = jax.nn.silu(conv_out).reshape(conv_out.shape[0], 3, nh, d)
    return _l2norm(y[:, 0]) / math.sqrt(d), _l2norm(y[:, 1]), y[:, 2]


def _kda_out(lp, o, gate, cfg):
    """``W_o [RMSNorm_d(o) * sigmoid(gate)]``: ``o`` (rows, H, d), ``gate``
    (rows, H d)."""
    rows = o.shape[0]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
    o = (o * lp["o_norm"]["weight"]).reshape(rows, -1)
    return _dense(lp["out"], o * jax.nn.sigmoid(gate))


def kda_mix_prefill(lp, x, cfg, mask, start=None, residual=None):
    """What one KDA layer ADDS to its input over a prompt (the norm in front
    is the layer's own): ``x`` (s, hidden), ``mask`` (s,) with 1 = real token
    and the padding at the end. Returns ``(y, state (H, d, d) float32, tail
    (w-1, 3 H d))`` as the prompt's last real token leaves them, the tail as
    the ring :func:`kda_mix_decode` goes on from: padded positions decay
    nothing (``log a = 0``) and write nothing (``b = 0``). With ``start``, a
    ``(state, tail)`` pair, ``x`` is one STRETCH of a prompt taken a stretch
    at a time (``models.glm_next``): the layer goes on from what the stretch
    before left, and the tail comes back as it is carried, oldest first
    (:func:`~apex_tpu.transformer.functional.gated_delta.ring_of_tail` makes
    the ring of the last one). ``residual`` is added to ``y`` where this
    family's block adds its input."""
    s = x.shape[0]
    real = mask.astype(bool)
    conv_in, log_decay, gate, beta = _kda_in(lp, x, cfg)
    length = jnp.sum(mask)
    state0, tail0 = start or (None, None)
    conv_out, tail = causal_conv(
        conv_in, lp["conv"]["weight"].astype(jnp.float32), length, tail0)
    q, k, v = _kda_heads(conv_out, cfg)
    log_decay = jnp.where(real[:, None, None], log_decay, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    pad = -s % CHUNK

    def lead(t):        # (s, H, ...) -> (H, s padded to whole chunks, ...)
        t = jnp.moveaxis(t, 1, 0)
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    o, state = gated_delta_chunked(lead(q), lead(k), lead(v),
                                   lead(log_decay), lead(beta),
                                   initial_state=state0)
    o = jnp.moveaxis(o[:, :s], 0, 1)
    y = _kda_out(lp, o, gate, cfg)
    if residual is not None:
        y = residual + y
    return y, state, ring_of_tail(tail, length) if start is None else tail


@region("mixer")
def kda_prefill(lp, x, cfg, mask):
    """``x + `` :func:`kda_mix_prefill`: one KDA layer on one residual
    stream. Returns ``(x', state, tail)``."""
    return kda_mix_prefill(lp, x, cfg, mask, residual=x)


def kda_mix_decode(lp, x, cfg, state, conv, layer: int, pos, active):
    """What one KDA layer adds for one token of every slot, at ``pos`` (b,):
    ``x`` (b, hidden); ``state`` and ``conv`` the WHOLE stacked arrays
    (``state_shapes``), of which KDA layer ``layer`` is read and written; the
    tail is a ring (``conv_ring_step``). Returns ``(y, state', conv')``."""
    conv_in, log_decay, gate, beta = _kda_in(lp, x, cfg)
    ring = lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
    conv_out, ring = conv_ring_step(
        conv_in, ring, lp["conv"]["weight"].astype(jnp.float32), pos, active)
    conv = lax.dynamic_update_index_in_dim(conv, ring, layer, 0)
    q, k, v = _kda_heads(conv_out, cfg)
    o, state = gated_delta_step(q, k, v, log_decay, beta, state,
                                jnp.int32(layer), active)
    return _kda_out(lp, o, gate, cfg), state, conv


@region("mixer")
def kda_decode(lp, x, cfg, state, conv, layer: int, pos, active):
    """``x + `` :func:`kda_mix_decode`. Returns ``(x', state', conv')``."""
    y, state, conv = kda_mix_decode(lp, x, cfg, state, conv, layer, pos,
                                    active)
    return x + y, state, conv


# ---------------------------------------------------------------------------
# the MLA layer: models.deepseek's, with this family's projections and gate
# ---------------------------------------------------------------------------

def _mla_in(lp, x, cfg, pos):
    """(rows, hidden) at ``pos`` -> ``q_nope``, roped ``q_pe`` (rows, heads,
    .), the cache row (rows, kv_row_width) float32, and each head's gate
    (rows, heads, 1)."""
    a = _dense(lp["a_proj"], _rms(lp["norm"], x, cfg.rms_norm_eps))
    wq = cfg.num_heads * cfg.qk_head_dim
    row = jnp.concatenate(latent_row_parts(lp, a, wq, cfg, pos), -1)
    q_nope, q_pe = split_queries(a[:, :wq], cfg, pos)
    gate = jax.nn.sigmoid(a[:, wq + cfg.latent_width:])[..., None]
    return q_nope, q_pe, row, gate


@region("attention")
def mla_prefill(lp, x, cfg, mask, kv_dtype):
    """One MLA layer over a prompt. Returns ``(x', rows (s,
    kv_row_width))`` in ``kv_dtype``, the cache's: keys and values are
    expanded from THOSE (``models.deepseek.expanded_attention``)."""
    s = x.shape[0]
    q_nope, q_pe, row, gate = _mla_in(lp, x, cfg,
                                      jnp.arange(s, dtype=jnp.int32))
    row = row.astype(kv_dtype)
    ctx = expanded_attention(lp, q_nope, q_pe, row, cfg, mask, kv_dtype)
    ctx = ctx.reshape(s, cfg.num_heads, -1) * gate
    return x + _dense(lp["out"], ctx.reshape(s, -1)), row


@region("attention")
def mla_decode(lp, x, cfg, pool, layer: int, block_tables, pos, active):
    """One token for every slot against the latent pool
    (``models.deepseek.absorbed_attention``: ``apex_mla_decode_fwd``).
    Returns ``(x', row (slots, kv_row_width))`` for the caller to write at
    ``pos``."""
    q_nope, q_pe, row, gate = _mla_in(lp, x, cfg, pos)
    row = row.astype(pool.dtype)
    ctx = absorbed_attention(lp, q_nope, q_pe, row, cfg, pool,
                             jnp.int32(layer), block_tables, pos, active)
    return x + _dense(lp["out"], (ctx * gate).reshape(x.shape[0], -1)), row


# ---------------------------------------------------------------------------
# the layers: over a prompt, and one token per slot against the cache
# ---------------------------------------------------------------------------

def prefill_layers(params, cfg: BailingHybridConfig, x, mask,
                   kv_dtype=jnp.float32, routes=False):
    """Every layer over one prompt: ``x`` (s, hidden). Returns ``(x', states
    (KDA layers, H, d, d), tails (KDA layers, w-1, 3 H d), rows (MLA layers,
    s, kv_row_width))`` and, when ``routes`` is asked for, the routers'
    choices ``(expert layers, s, k)`` after them."""
    real = mask.astype(bool)
    states, tails, rows, chosen = [], [], [], []
    for at, lp in enumerate(params["layers"]):
        if cfg.layer_types[at] == KDA:
            x, state, tail = kda_prefill(lp, x, cfg, mask)
            states.append(state)
            tails.append(tail)
        else:
            x, row = mla_prefill(lp, x, cfg, mask, kv_dtype)
            rows.append(row)
        if at < cfg.first_k_dense:
            x = dense_mlp(lp, x, cfg)
        else:
            x, _, picked = expert_mlp(lp, x, cfg, real)
            chosen.append(picked)
    out = (x, jnp.stack(states), jnp.stack(tails), jnp.stack(rows))
    return out + (jnp.stack(chosen),) if routes else out


def decode_layers(params, cfg: BailingHybridConfig, cache, tokens, active):
    """One token for every slot against the serving cache
    (``serving.cache.HybridKVCache`` whose pool is latent): each KDA layer
    steps its layer of the stacked state in place (``apex_kda_decode_fwd``),
    each MLA layer attends over the pool in place, each expert layer counts
    what its held experts got. Returns ``(x (slots, hidden), state', conv',
    counters', rows (MLA layers, slots, kv_row_width))`` for the engine to
    write."""
    pos, bt = cache.lengths, cache.block_tables
    x = embed(params, tokens)
    state, conv = cache.state, cache.conv
    counters = {**cache.counters, "moe_steps": cache.counters["moe_steps"] + 1}
    rows = []
    n_kda = 0
    for at, lp in enumerate(params["layers"]):
        if cfg.layer_types[at] == KDA:
            x, state, conv = kda_decode(lp, x, cfg, state, conv, n_kda,
                                        pos, active)
            n_kda += 1
        else:
            x, row = mla_decode(lp, x, cfg, cache.k, len(rows), bt, pos,
                                active)
            rows.append(row)
        if at < cfg.first_k_dense:
            x = dense_mlp(lp, x, cfg)
            continue
        x, sizes, _ = expert_mlp(lp, x, cfg, active)
        with region("experts"):
            e = at - cfg.first_k_dense
            counters = {
                **counters,
                "moe_load": counters["moe_load"].at[e].add(sizes),
                "moe_hit": counters["moe_hit"].at[e].add(jnp.sum(sizes > 0))}
    return x, state, conv, counters, jnp.stack(rows)


def apply(params, cfg: BailingHybridConfig, ids):
    """(s,) token ids -> (s, vocab) float32 logits: the whole forward, no
    cache."""
    x = prefill_layers(params, cfg, embed(params, ids),
                       jnp.ones(ids.shape, jnp.int32))[0]
    return logits_of(params, cfg, x)
