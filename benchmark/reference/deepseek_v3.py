"""Plain reference of the ``deepseek_v3`` forward pass: pre-norm blocks, ``x <-
x + attention(RMSNorm(x))``, ``x <- x + mlp(RMSNorm(x))``, multi-head latent
attention (MLA) in every layer, a dense SwiGLU in the leading
``first_k_dense_replace`` layers and a sparse expert layer after them, a final
RMSNorm and an untied head. Straightforward ``jax.numpy`` in float32 with
matrix products at ``highest`` precision, the attention in its EXPANDED form
(keys and values per head, a full causal softmax); no absorption, no cache, no
paging, no sort, no grouped product, no kernel, no batching. It imports
nothing of the program under test.

*MLA.* ``[c_q | c_kv | k_pe] = W_a u`` (1536 | 512 | 64); ``c_q <-
RMSNorm(c_q)``, ``c_kv <- RMSNorm(c_kv)``; ``q = W_qb c_q``, per head ``[q_nope
(128) | q_pe (64)]``; ``k_pe`` is ONE head shared by all 128; ``k_nope[h] =
W_kvb^K[h] c_kv``, ``v[h] = W_kvb^V[h] c_kv``; ``q_pe``, ``k_pe`` rotated
(YaRN, below); ``scores = (q_nope . k_nope + q_pe . k_pe) * s``, causal, ``s =
192^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; the heads'
contexts side by side pass ``W_o``.

*YaRN* (``rope_scaling``): pair ``i`` of 32 turns at a blend of
``theta^(-2i/64)`` and that over ``factor``, by a linear ramp between the two
correction dimensions ``beta_fast`` and ``beta_slow`` give (``floor`` and
``ceil`` of ``64 ln(original / (beta 2 pi)) / (2 ln theta)``); cos and sin are
scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1). The
pairs are the projection's neighbouring outputs ``(2i, 2i + 1)``; the rotated
vector stands de-interleaved, as the published modelling code lays it.

*Expert layer.* ``sc = sigmoid(W_g u)`` in float32; ``c = sc + b`` (the
score-correction bias, choice only); a group's score is the sum of the 2
largest ``c`` among its ``router_experts / n_group`` experts; the ``topk_group``
best groups stay (ties to the lower index); the ``num_experts_per_tok`` largest
``c`` inside them are chosen; ``w = routed_scaling_factor * sc[chosen] / (sum +
1e-20)``; every HELD expert in turn (a loop, each token's weight for it zero
where it was not chosen) gives ``w W_down(silu(W_gate u) * W_up u)``; the
shared expert, the same shape, works beside them. The reference is given the
same share of the model as the program: it holds experts ``expert_offset .. +
experts_held - 1`` of the router's ``router_experts`` and adds up their part
alone, and its vocabulary is the slice the configuration keeps. What the
absent experts would add is left out here as there. The multi-token-prediction
module is left out (``num_nextn_predict_layers`` under ``reduced``).

What the published config does not say is listed under ``assumed`` in the
configuration file; program and reference follow the same list.

Departures: the projections are stored fused, in the order the program
consumes (``a_proj`` = ``[c_q | c_kv | k_pe]``, ``gate_up`` = ``[gate | up]``),
and ``W_kvb`` by head (``kv_b_k`` ``(heads, 128, 512)``, ``kv_b_v`` ``(heads,
512, 128)``): with seeded random weights this only names the columns. The
leading dense layers are a list of trees; the expert layers one tree whose
leaves lead with ``(expert layers,)``.

The weights are served in bfloat16 (norms and the router's bias in float32),
so the seeded weights are rounded to bfloat16 once, here, and both sides get
those values. Layer ``l`` is drawn from ``fold_in(key, l)`` and expert ``e``
(its number in the whole model) of it from ``fold_in(., e)``, so a chip that
holds other experts draws the same model. Float32 copies of what one chip
holds are 18 GB and the scores of 128 heads over 6,400 positions another 21:
the forward upcasts ONE layer at a time (one expert at a time inside an
expert layer) and attends one head at a time; ``Scorer`` instances of one
seed share the one bfloat16 tree.

The lower precision the control runs (``Scorer(..., precision=
"bfloat16_activations")``), which ``correct`` has to refuse: what the
configuration states as float32 is bfloat16. The inputs of every matrix
product are rounded to bfloat16 (ONE term, where the program hands over two),
the latents (``c_kv``, ``k_pe``) are kept in bfloat16 and the attention is
computed in bfloat16 (queries, expanded keys and values, scores,
probabilities and contexts each rounded to it); sums, norms, the residual
stream and the router's product stay float32. ``"bfloat16_attention"`` is
the attention's part of it alone (the first control tried: on the chip it
read 3 times the served tokens' gap on the same seed, too near the spread
between seeds to set a limit by, because the program's own prompt path runs
its flash attention on bfloat16 operands: ``PERF.md``, section 6).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16_attention", "bfloat16_activations")


def sizes_of(config: dict) -> dict:
    published = config.get("published", {})
    layers = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    scaling = config["rope_scaling"]
    if scaling["type"] != "yarn" or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or int(config["moe_layer_freq"]) != 1 \
            or not config["norm_topk_prob"]:
        raise ValueError("only the published routing and rotary rules are "
                         "written down here")
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "layers": layers, "dense_layers": dense,
            "expert_layers": layers - dense,
            "heads": int(config["num_attention_heads"]),
            "q_rank": int(config["q_lora_rank"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v_dim": int(config["v_head_dim"]),
            "dense_ffn": int(config["intermediate_size"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["n_shared_experts"])
            * int(config["moe_intermediate_size"]),
            "router_experts": int(published.get("n_routed_experts",
                                                config["n_routed_experts"])),
            "experts_held": int(config["n_routed_experts"]),
            "expert_offset": int(config.get("expert_offset", 0)),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "n_group": int(config["n_group"]),
            "topk_group": int(config["topk_group"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_theta"]),
            "rope_factor": float(scaling["factor"]),
            "rope_original": int(scaling["original_max_position_embeddings"]),
            "rope_beta_fast": float(scaling["beta_fast"]),
            "rope_beta_slow": float(scaling["beta_slow"]),
            "rope_mscale": float(scaling["mscale"]),
            "rope_mscale_all_dim": float(scaling["mscale_all_dim"]),
            # what the served cache holds of a token in a layer, and the row
            # it lies in (whole 128-lane tiles)
            "latent_width": int(config["kv_lora_rank"])
            + int(config["qk_rope_head_dim"]),
            "row_width": int(config["serving"]["row_width"]),
            # the longest sequence the served cache row holds
            "positions": int(config["serving"]["max_len"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (made outside ``jit``:
    a new seed is no new program)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# the router's score-correction bias: N(0, this), the scale of the gaps
# between neighbouring scores among the 8 largest of 256 (so that it changes
# choices; a trained model's bias is what balanced its experts' load)
ROUTER_BIAS_STD = 0.01


def make_weights(sz: dict, key):
    """The weights from ``key`` as they are served, layer ``l`` (in the
    model's order) from ``fold_in(key, l)``: matrices ``N(0, 1/fan_in)``, the
    embedding 0.02, the router's bias ``N(0, ROUTER_BIAS_STD)`` in float32,
    norms 1; rounded to bfloat16, one layer (one expert) at a time. Traced:
    call under ``jax.jit`` with the key as an argument."""
    h, nh = sz["hidden"], sz["heads"]
    qr, kr = sz["q_rank"], sz["kv_rank"]

    def drawer(key):
        count = [0]

        def normal(std, *shape, dtype=jnp.bfloat16):
            count[0] += 1
            return (std * jax.random.normal(
                jax.random.fold_in(key, count[0]), shape, jnp.float32)
            ).astype(dtype)

        return normal

    def dense(normal, i, o):
        return {"kernel": normal(math.sqrt(1.0 / i), i, o)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def attention(normal):
        return {"norm": norm(h),
                "a_proj": dense(normal, h, qr + sz["latent_width"]),
                "q_norm": norm(qr), "kv_norm": norm(kr),
                "q_b": dense(normal, qr, nh * (sz["nope"] + sz["rope"])),
                "kv_b_k": normal(math.sqrt(1.0 / kr), nh, sz["nope"], kr),
                "kv_b_v": normal(math.sqrt(1.0 / kr), nh, kr, sz["v_dim"]),
                "out": dense(normal, nh * sz["v_dim"], h)}

    def dense_layer(layer):
        normal = drawer(jax.random.fold_in(key, layer))
        return {"attn": attention(normal), "mlp_norm": norm(h),
                "gate_up": dense(normal, h, 2 * sz["dense_ffn"]),
                "down": dense(normal, sz["dense_ffn"], h)}

    def expert_layer(layer):
        k_layer = jax.random.fold_in(key, layer)
        normal = drawer(k_layer)
        f, sf = sz["expert_ffn"], sz["shared_ffn"]

        def expert(e):          # its number in the whole model
            normal = drawer(jax.random.fold_in(
                jax.random.fold_in(k_layer, 1 << 20), e))
            return (normal(math.sqrt(1.0 / h), h, 2 * f),
                    normal(math.sqrt(1.0 / f), f, h))

        w_gate_up, w_down = jax.lax.map(
            expert, sz["expert_offset"] + jnp.arange(sz["experts_held"]))
        return {"attn": attention(normal), "mlp_norm": norm(h),
                "router": dense(normal, h, sz["router_experts"]),
                "router_bias": normal(ROUTER_BIAS_STD, sz["router_experts"],
                                      dtype=jnp.float32),
                "w_gate_up": w_gate_up, "w_down": w_down,
                "shared_gate_up": dense(normal, h, 2 * sf),
                "shared_down": dense(normal, sf, h)}

    normal = drawer(jax.random.fold_in(key, sz["layers"]))
    return {
        "embedding": {"word": {"embedding": normal(0.02, sz["vocab"], h)}},
        "dense": [dense_layer(layer) for layer in range(sz["dense_layers"])],
        "moe": jax.lax.map(expert_layer, sz["dense_layers"]
                           + jnp.arange(sz["expert_layers"])),
        "final_norm": norm(h),
        "head": dense(normal, h, sz["vocab"]),
    }


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _bf16(t):
    return t.astype(jnp.bfloat16).astype(jnp.float32)


def _same(t):
    return t


def _swiglu(gate_up):
    f = gate_up.shape[-1] // 2
    return _silu(gate_up[:, :f]) * gate_up[:, f:]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(sz: dict) -> np.ndarray:
    """The inverse frequency of each of the ``rope / 2`` pairs, float32."""
    d, theta = sz["rope"], sz["rope_theta"]
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(turns):
        return d * math.log(sz["rope_original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(sz["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sz["rope_beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / sz["rope_factor"] * ramp
            + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(sz: dict) -> float:
    m = yarn_mscale(sz["rope_factor"], sz["rope_mscale_all_dim"])
    return (sz["nope"] + sz["rope"]) ** -0.5 * m * m


def rope(sz, x, pos):
    """``x`` (s, ..., rope) at positions ``pos`` (s,): pair ``i`` is ``(x[2i],
    x[2i + 1])``; the rotated pairs stand de-interleaved."""
    theta = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(sz)
    m = yarn_mscale(sz["rope_factor"], sz["rope_mscale"]) \
        / yarn_mscale(sz["rope_factor"], sz["rope_mscale_all_dim"])
    cos, sin = jnp.cos(theta) * m, jnp.sin(theta) * m
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_layer(lp, sz, x, cut=_same, into=_same):
    """``lp`` float32. The controls' arithmetic: ``cut`` rounds the latents
    and everything inside the attention, ``into`` the inputs of the
    projections around it."""
    s, nh = x.shape[0], sz["heads"]
    qr, kr, nope = sz["q_rank"], sz["kv_rank"], sz["nope"]
    pos = jnp.arange(s)
    a = into(_rms(lp["norm"]["weight"], x, sz["eps"])) @ lp["a_proj"]["kernel"]
    c_q = _rms(lp["q_norm"]["weight"], a[:, :qr], sz["eps"])
    c_kv = cut(_rms(lp["kv_norm"]["weight"], a[:, qr:qr + kr], sz["eps"]))
    k_pe = cut(rope(sz, a[:, qr + kr:], pos))
    q = (into(c_q) @ lp["q_b"]["kernel"]).reshape(s, nh, -1)
    q_nope = q[..., :nope].transpose(1, 0, 2)
    q_pe = rope(sz, q[..., nope:], pos).transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(sz)

    def head(args):                     # one head at a time: (s, s) scores
        q_nope, q_pe, w_k, w_v = args
        k_nope, v = cut(c_kv @ w_k.T), cut(c_kv @ w_v)
        scores = cut((cut(q_nope) @ k_nope.T + cut(q_pe) @ k_pe.T) * scale)
        p = cut(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1))
        return cut(p @ v)

    ctx = jax.lax.map(head, (q_nope, q_pe, lp["kv_b_k"], lp["kv_b_v"]))
    return x + into(ctx.transpose(1, 0, 2).reshape(s, -1)) \
        @ lp["out"]["kernel"]


def dense_mlp(lp, sz, x, into=_same):
    u = into(_rms(lp["mlp_norm"]["weight"], x, sz["eps"]))
    return x + into(_swiglu(u @ lp["gate_up"]["kernel"])) \
        @ lp["down"]["kernel"]


def route(lp, sz, u):
    """(chosen (s, k), dense weights (s, router_experts)): float32."""
    scores = jax.nn.sigmoid(u @ lp["router"]["kernel"].astype(jnp.float32))
    choice = scores + lp["router_bias"]
    s, e = choice.shape
    g, k = sz["n_group"], sz["experts_per_token"]
    if g > 1:
        grouped = choice.reshape(s, g, e // g)
        group_score = jnp.sum(-jnp.sort(-grouped, -1)[..., :2], -1)
        kept = jnp.argsort(-group_score, -1, stable=True)[:, :sz["topk_group"]]
        eligible = jnp.zeros((s, g), bool).at[
            jnp.arange(s)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(eligible, e // g, axis=1), choice,
                           -jnp.inf)
    chosen = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(scores, chosen, -1)
    w = sz["routed_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(scores).at[
        jnp.arange(s)[:, None], chosen].set(w)
    return chosen, dense


def expert_mlp(lp, sz, x, into=_same):
    """``lp`` as served (bfloat16): one expert at a time is made float32.
    Returns ``(x', chosen (s, k))``. The router reads the rows as they are,
    whatever ``into`` makes of the experts' inputs."""
    outer = _f32({k: v for k, v in lp.items()
                  if k not in ("attn", "w_gate_up", "w_down")})
    u = _rms(outer["mlp_norm"]["weight"], x, sz["eps"])
    chosen, weights = route(outer, sz, u)
    mine = jax.lax.dynamic_slice_in_dim(
        weights, sz["expert_offset"], sz["experts_held"], axis=1)
    u = into(u)

    def one(total, expert):
        w_gate_up, w_down, w = expert
        mid = into(_swiglu(u @ w_gate_up.astype(jnp.float32)))
        return total + w[:, None] * (mid @ w_down.astype(jnp.float32)), None

    routed = jax.lax.scan(one, jnp.zeros_like(x),
                          (lp["w_gate_up"], lp["w_down"], mine.T))[0]
    shared = into(_swiglu(u @ outer["shared_gate_up"]["kernel"])) \
        @ outer["shared_down"]["kernel"]
    return x + routed + shared, chosen


def hidden_states(params, sz: dict, ids, precision="float32"):
    """(seq,) token ids -> ((seq, hidden) before the final norm, the experts
    each expert layer's router chose (expert layers, seq, k)). ``params`` as
    served (bfloat16); one layer at a time is made float32."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    cut = _same if precision == "float32" else _bf16
    into = _bf16 if precision == "bfloat16_activations" else _same
    x = params["embedding"]["word"]["embedding"][ids].astype(jnp.float32)
    for lp in params["dense"]:
        x = attention_layer(_f32(lp["attn"]), sz, x, cut, into)
        x = dense_mlp(_f32({k: v for k, v in lp.items() if k != "attn"}),
                      sz, x, into)

    def layer(x, lp):
        x = attention_layer(_f32(lp["attn"]), sz, x, cut, into)
        return expert_mlp(lp, sz, x, into)

    return jax.lax.scan(layer, x, params["moe"])


def logits_at(params, sz: dict, ids, positions, precision="float32"):
    """Logits at ``positions`` of ``ids`` over the vocabulary kept."""
    hid = hidden_states(params, sz, ids, precision)[0][positions]
    hid = _rms(params["final_norm"]["weight"], hid, sz["eps"])
    if precision == "bfloat16_activations":
        hid = _bf16(hid)
    return hid @ params["head"]["kernel"].astype(jnp.float32)


_SERVED = {}        # (seed, sizes) -> the one bfloat16 tree of that seed


def served_weights(sz: dict, seed: int):
    at = (seed, tuple(sorted(sz.items())))
    if at not in _SERVED:
        _SERVED.clear()                     # one model fits, not two
        _SERVED[at] = jax.jit(lambda key: make_weights(sz, key))(
            seed_key(seed))
    return _SERVED[at]


class Scorer:
    """Scores served tokens against the reference: for a prompt and the
    tokens served after it, the gap by which each served token's logit lies
    below the reference's best at that position. Every sequence is padded to
    a whole number of ``BLOCK`` positions (causal, so a real position never
    sees the padding after it): one compiled program a length, a handful."""

    BLOCK = 1024

    def __init__(self, sz: dict, seed: int, precision: str = "float32"):
        self.sz = sz
        self.params = served_weights(sz, seed)

        def score(params, ids, served, first, n):
            pos = jnp.clip(first - 1 + jnp.arange(served.shape[0]), 0,
                           ids.shape[0] - 1)
            logits = logits_at(params, sz, ids, pos, precision)
            best = jnp.max(logits, -1)
            got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
            top = jnp.argmax(logits, -1)
            valid = jnp.arange(served.shape[0]) < n
            return jnp.where(valid, best - got, 0.0), top

        self._score = jax.jit(score)
        self._routes = jax.jit(
            lambda params, ids: hidden_states(params, sz, ids, precision)[1])

    def _padded(self, seq):
        n_pos = self.sz["positions"]
        if len(seq) > n_pos:
            raise ValueError(f"{len(seq)} tokens pass the {n_pos} positions "
                             "of a cache row")
        ids = np.zeros((min(-(-len(seq) // self.BLOCK) * self.BLOCK, n_pos),),
                       np.int32)
        ids[:len(seq)] = seq
        return ids

    def gaps(self, prompt, served, judged=None):
        """(gaps, this model's own best tokens) at the positions that
        produced ``served``. The tokens judged are the served ones, or
        ``judged`` (the control: another model's best tokens at the same
        positions of the same teacher-forced sequence)."""
        ids = self._padded(list(prompt) + list(served))
        out = np.zeros(ids.shape, np.int32)
        out[:len(served)] = served if judged is None else judged
        with jax.default_matmul_precision("highest"):
            gaps, top = self._score(self.params, jnp.asarray(ids),
                                    jnp.asarray(out),
                                    jnp.int32(len(prompt)),
                                    jnp.int32(len(served)))
        return (np.asarray(gaps)[:len(served)],
                np.asarray(top)[:len(served)])

    def routes(self, tokens):
        """The experts each expert layer's router chose at each of
        ``tokens``' positions: (expert layers, len(tokens), k), sorted along
        k."""
        ids = self._padded(list(tokens))
        with jax.default_matmul_precision("highest"):
            chosen = self._routes(self.params, jnp.asarray(ids))
        return np.sort(np.asarray(chosen)[:, :len(tokens)], axis=-1)
