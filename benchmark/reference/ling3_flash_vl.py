"""Plain reference of the ``bailing_hybrid`` forward pass (Ling 3.0 flash, the
language model of ``Ling-3.0-flash-VL``): pre-norm blocks, ``x <- x +
mixer(RMSNorm(x))``, ``x <- x + mlp(RMSNorm(x))``; the mixer of layer ``l`` is
multi-head latent attention (MLA) iff ``(l + 1) % layer_group_size == 0`` and
Kimi Delta Attention (KDA, arXiv:2510.26692) otherwise; a dense SwiGLU in the
leading ``first_k_dense_replace`` layers and a sparse expert layer after them;
a final RMSNorm and an untied head. Straightforward ``jax.numpy`` in float32
with matrix products at ``highest`` precision; KDA as the recurrence itself,
one token at a time in a ``lax.scan`` (no chunks); MLA in its EXPANDED form
(keys and values per head, a full causal softmax, no absorption); no kernel,
no cache, no paging, no sort, no grouped product, no batching. It imports
nothing of the program under test.

*KDA*, ``H`` heads of ``d`` channels. ``q~, k~, v~ = W_q u, W_k u, W_v u``;
each channel passes a causal depthwise convolution of
``short_conv_kernel_size`` taps, then SiLU; per head ``q = l2norm(q~) /
sqrt(d)``, ``k = l2norm(k~)``, ``v = v~``; ``log a = kda_lower_bound *
sigmoid(exp(A_log[h]) * (W_a u + dt_bias))`` per CHANNEL; ``b = sigmoid(W_b
u)`` per head;

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t;

``y = W_o (RMSNorm_d(o_h) * sigmoid(W_g u))``.

*MLA.* ``q = W_q u`` per head ``[q_nope | q_pe]``; ``[c | k_pe] = W_kva u``;
``c <- RMSNorm(c)``; ``q_pe`` and ``k_pe`` rotated (pairs ``(2i, 2i + 1)`` of
the projection's outputs, ``rope_interleave``; pair ``i`` at ``rope_theta **
(-2i / qk_rope_head_dim)``; the rotated vector stands de-interleaved, for both
alike); ``k_pe`` is ONE head shared by all; ``k_nope[h] = W_kvb^K[h] c``,
``v[h] = W_kvb^V[h] c``; ``scores = (q_nope . k_nope + q_pe . k_pe) /
sqrt(qk_head_dim)``, causal; ``y = W_o concat_h(o_h * sigmoid(w_g,h . u))``.
What the served cache keeps of a token (``c`` and ``k_pe``) is rounded to the
stated ``cache_dtype`` in every precision: that rounding is the
configuration's, not the program's.

*Expert layer.* ``sc = sigmoid(W_r u)`` in float32; ``c = sc + bias`` (choice
only); a group's score is the sum of the 2 largest ``c`` among its experts;
the ``topk_group`` best of ``n_group`` groups stay (ties to the lower index);
the ``num_experts_per_tok`` largest ``c`` inside them are chosen; weights
``routed_scaling_factor * sc / (sum of the chosen + 1e-20)``; every expert and
the shared expert ``W_d (silu(W_g u) * W_u u)``. THIS chip holds experts
``expert_offset .. + num_experts - 1`` of the router's
``published.num_experts`` and adds up their part alone, and its vocabulary is
the slice the configuration keeps; what the absent experts would add is left
out here as in the program. A non-zero entry of either SwiGLU limit list in a
held layer is REFUSED: the config does not say which of the two published
clamp forms it means.

What the published config does not say is listed under ``assumed`` in the
configuration file; the seven entries that are a choice between forms
(``ASSUMED``) are read here and by the program, and a file that states
another value is refused.

Departures: projections are stored fused in the order the program consumes
(KDA ``in_proj = [q~ | k~ | v~ | a | g | b]``, MLA ``a_proj = [q | c | k_pe |
gate]``, ``gate_up = [gate | up]``) and ``W_kvb`` by head; with seeded random
weights this only names the columns. Layers are a list, in the model's order.

The weights are served in bfloat16 (norms, ``A_log``, ``dt_bias`` and the
router's bias in float32), rounded once, here; layer ``l`` (its number in the
whole model) is drawn from ``fold_in(key, l)`` and expert ``e`` (its number in
the whole model) of it from ``fold_in(., e)``, so a chip that holds other
experts draws the same model. The forward upcasts ONE layer (one expert) at a
time, takes the MLPs ``BLOCK`` rows at a time and attends one head and one
block of ``BLOCK`` queries at a time; ``Scorer`` instances of one seed share
the one bfloat16 tree.

Two controls, which ``correct`` has to refuse: ``bfloat16_activations`` (what
the configuration states as float32 is bfloat16: ONE bfloat16 term into every
product, the delta rule's inputs and its state rounded to bfloat16 at every
token, the attention computed in bfloat16; every cut made with
``lax.reduce_precision``, which the compiler has to honour) and
``scalar_gate`` (float32, each head's decay replaced by its mean over the
channels: Gated DeltaNet's rule, as a program that dropped the per-channel
gate would compute).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16_activations", "scalar_gate")
KDA, MLA = "kda", "mla"
_L2_EPS = 1e-6
BLOCK = 1024        # rows of an MLP pass, queries of an attention pass

#: the choices between forms that the published config leaves open, as they
#: are written down here (the configuration file's ``assumed`` states each)
ASSUMED = {
    "norm_placement": "pre",
    "kda_output_gate": "per_channel_full_rank",
    "mla_output_gate": "head_wise",
    "mla_qk_norm": "latent_only",
    "kda_decay": "lower_bound_times_sigmoid_of_a_times_x_plus_dt_bias",
    "state_dtype": "float32",
    "group_score": "top2_sum",
}

#: keys of the published config whose value decides a form written down here
_PUBLISHED_FORMS = {
    "q_lora_rank": None, "score_function": "sigmoid", "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "linear_silu": True,
    "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "use_qk_norm": True, "use_mla_nope": False, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
}


def sizes_of(config: dict) -> dict:
    for key, form in _PUBLISHED_FORMS.items():
        if config[key] != form:
            raise ValueError(f"{key} = {config[key]!r}: only {form!r} is "
                             "written down here")
    for name, form in ASSUMED.items():
        said = config["assumed"][name][0]
        if said != form:
            raise ValueError(f"assumed {name} = {said!r}: this reference "
                             f"implements {form!r}")
    layers = int(config["num_hidden_layers"])
    first = int(config["first_layer_held"])
    group = int(config["layer_group_size"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = config[key]
        if len(limits) != layers or any(limits):
            raise ValueError(
                f"{key} {limits}: one entry a held layer, and a non-zero "
                "entry (a clamped SwiGLU) is refused: the config does not "
                "say which of the two published clamp forms it is")
    heads = int(config["num_attention_heads"])
    if int(config["num_key_value_heads"]) != heads \
            or int(config["rotary_dim"]) != int(config["qk_rope_head_dim"]):
        raise ValueError("fewer K/V than query heads, or a rotary width "
                         "other than qk_rope_head_dim, is not written down "
                         "here")
    published = config.get("published", {})
    dense = int(config["first_k_dense_replace"])
    types = tuple(MLA if (l + 1) % group == 0 else KDA
                  for l in range(first, first + layers))
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "depth": layers, "first_layer": first,
            "layer_types": types,
            "kda_layers": types.count(KDA), "mla_layers": types.count(MLA),
            "dense_layers": dense, "expert_layers": layers - dense,
            "heads": heads, "head_dim": int(config["head_dim"]),
            "conv_kernel": int(config["short_conv_kernel_size"]),
            "kda_lower_bound": float(config["kda_lower_bound"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v_dim": int(config["v_head_dim"]),
            "dense_ffn": int(config["intermediate_size"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "expert_width": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["num_shared_experts"])
            * int(config["moe_shared_expert_intermediate_size"]),
            "router_experts": int(published.get("num_experts",
                                                config["num_experts"])),
            "experts_held": int(config["num_experts"]),
            "expert_offset": int(config.get("expert_offset", 0)),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "n_group": int(config["n_group"]),
            "topk_group": int(config["topk_group"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_theta"]),
            # what the served cache holds of a token in an MLA layer, the row
            # it lies in (whole 128-lane tiles), and the dtype it is kept in
            "latent_width": int(config["kv_lora_rank"])
            + int(config["qk_rope_head_dim"]),
            "row_width": int(config["serving"]["row_width"]),
            "cache_dtype": config["serving"]["cache_dtype"],
            # the longest sequence the served cache row holds
            "positions": int(config["serving"]["max_len"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (made outside ``jit``:
    a new seed is no new program)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# the router's bias: N(0, this), the scale of the gaps between neighbouring
# scores among the largest (so that it changes choices and not weights)
ROUTER_BIAS_STD = 0.01


def make_weights(sz: dict, key):
    """The weights from ``key`` as they are served, layer ``l`` (its number in
    the whole model) from ``fold_in(key, l)``: matrices ``N(0, 1/fan_in)``,
    the embedding 0.02, the convolution taps 0.5, ``A`` uniform in 1..16 (its
    log is ``A_log``), ``dt`` log-uniform in 0.001..0.1 with its inverse
    softplus as ``dt_bias`` (the layer's published initialisation), the
    router's bias ``N(0, ROUTER_BIAS_STD)``, norms 1; matrices rounded to
    bfloat16, one layer (one expert) at a time. Traced: call under
    ``jax.jit`` with the key as an argument."""
    h, nh, d = sz["hidden"], sz["heads"], sz["head_dim"]
    kr, w = sz["kv_rank"], sz["heads"] * sz["head_dim"]

    def drawer(key):
        count = [0]

        def at():
            count[0] += 1
            return jax.random.fold_in(key, count[0])

        def normal(std, *shape, dtype=jnp.bfloat16):
            return (std * jax.random.normal(at(), shape, jnp.float32)
                    ).astype(dtype)

        def uniform(lo, hi, *shape):
            return jax.random.uniform(at(), shape, jnp.float32, lo, hi)

        return normal, uniform

    def dense(normal, i, o):
        return {"kernel": normal(math.sqrt(1.0 / i), i, o)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def kda(normal, uniform):
        dt = jnp.exp(uniform(math.log(1e-3), math.log(0.1), w))
        return {"norm": norm(h),
                "in_proj": dense(normal, h, 5 * w + nh),
                "conv": {"weight": normal(0.5, sz["conv_kernel"], 3 * w)},
                "a_log": jnp.log(uniform(1.0, 16.0, nh)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": norm(d), "out": dense(normal, w, h)}

    def mla(normal, uniform):
        return {"norm": norm(h),
                "a_proj": dense(normal, h, nh * (sz["nope"] + sz["rope"])
                                + sz["latent_width"] + nh),
                "kv_norm": norm(kr),
                "kv_b_k": normal(math.sqrt(1.0 / kr), nh, sz["nope"], kr),
                "kv_b_v": normal(math.sqrt(1.0 / kr), nh, kr, sz["v_dim"]),
                "out": dense(normal, nh * sz["v_dim"], h)}

    def layer(at):
        number = sz["first_layer"] + at
        k_layer = jax.random.fold_in(key, number)
        normal, uniform = drawer(k_layer)
        out = (kda if sz["layer_types"][at] == KDA else mla)(normal, uniform)
        out["mlp_norm"] = norm(h)
        if at < sz["dense_layers"]:
            out["gate_up"] = dense(normal, h, 2 * sz["dense_ffn"])
            out["down"] = dense(normal, sz["dense_ffn"], h)
            return out
        f, sf = sz["expert_ffn"], sz["shared_ffn"]

        def expert(e):          # its number in the whole model
            normal, _ = drawer(jax.random.fold_in(
                jax.random.fold_in(k_layer, 1 << 20), e))
            return (normal(math.sqrt(1.0 / h), h, 2 * f),
                    normal(math.sqrt(1.0 / f), f, h))

        out["router"] = dense(normal, h, sz["router_experts"])
        out["router_bias"] = normal(ROUTER_BIAS_STD, sz["router_experts"],
                                    dtype=jnp.float32)
        out["w_gate_up"], out["w_down"] = jax.lax.map(
            expert, sz["expert_offset"] + jnp.arange(sz["experts_held"]))
        out["shared_gate_up"] = dense(normal, h, 2 * sf)
        out["shared_down"] = dense(normal, sf, h)
        return out

    normal, _ = drawer(jax.random.fold_in(key, 1 << 24))
    return {
        "embedding": {"word": {"embedding": normal(0.02, sz["vocab"], h)}},
        "layers": [layer(at) for at in range(sz["depth"])],
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


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def _bf16(t):
    """Float32 ``t`` rounded to bfloat16's 8 bits of mantissa, still float32.
    Cut with ``lax.reduce_precision``, which the compiler has to honour: a
    round trip through ``astype`` it folds away on the TPU (PERF.md, section
    6, PR 40)."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def _same(t):
    return t


def _kept(sz):
    """What keeping a cache row does to it: rounds it to the stated
    ``cache_dtype`` (and hands it back as float32)."""
    return {"bfloat16": _bf16, "float32": _same}[sz["cache_dtype"]]


def _swiglu(gate_up):
    f = gate_up.shape[-1] // 2
    return _silu(gate_up[:, :f]) * gate_up[:, f:]


def _blocks(f, x):
    """``f`` over ``x`` (s, ...) ``BLOCK`` rows at a time where ``s`` is a
    whole number of them."""
    s = x.shape[0]
    if s <= BLOCK or s % BLOCK:
        return f(x)
    out = jax.lax.map(f, x.reshape(s // BLOCK, BLOCK, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def rope(sz, x, pos):
    """``x`` (s, ..., rope) at positions ``pos`` (s,): pair ``i`` is ``(x[2i],
    x[2i + 1])``; the rotated pairs stand de-interleaved."""
    d = sz["rope"]
    inv_freq = jnp.asarray([sz["rope_theta"] ** (-i / d)
                            for i in range(0, d, 2)], jnp.float32)
    theta = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(theta), jnp.sin(theta)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def causal_conv(x, weight):
    """``x`` (s, c), ``weight`` (w, c): ``y_t = sum_j weight[j] x_{t-w+1+j}``
    with zeros before the sequence."""
    w = weight.shape[0]
    xp = jnp.pad(x, ((w - 1, 0), (0, 0)))
    return sum(weight[j] * xp[j:j + x.shape[0]] for j in range(w))


def recurrence(q, k, v, log_a, beta, cut=_same):
    """``q``, ``k``, ``v``, ``log_a`` (s, H, d), ``beta`` (s, H): the KDA
    recurrence from a zero state, one token at a time. ``cut`` rounds the
    state after every token (the lower-precision control)."""
    heads, d = q.shape[1], q.shape[2]

    def step(S, row):
        q, k, v, log_a, beta = row
        S = jnp.exp(log_a)[:, :, None] * S                  # Diag(a) S
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S))
        S = cut(S + k[:, :, None] * u[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q, S)

    return jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, log_a, beta))[1]


def kda_layer(lp, sz, x, cut=_same, into=_same, scalar_gate=False):
    """``lp`` float32. ``cut`` rounds the delta rule's inputs and its state,
    ``into`` the inputs of the projections around it; ``scalar_gate``
    replaces each head's decay by its mean over the channels."""
    s, nh, d = x.shape[0], sz["heads"], sz["head_dim"]
    w = nh * d
    u = _rms(lp["norm"]["weight"], x, sz["eps"])
    proj = _blocks(lambda u: into(u) @ lp["in_proj"]["kernel"], u)
    y = _silu(causal_conv(proj[:, :3 * w], lp["conv"]["weight"]))
    y = y.reshape(s, 3, nh, d)
    q, k, v = _l2norm(y[:, 0]) / math.sqrt(d), _l2norm(y[:, 1]), y[:, 2]
    a = (proj[:, 3 * w:4 * w] + lp["dt_bias"]).reshape(s, nh, d)
    log_a = sz["kda_lower_bound"] * _sigmoid(
        jnp.exp(lp["a_log"])[:, None] * a)
    if scalar_gate:
        log_a = jnp.broadcast_to(jnp.mean(log_a, -1, keepdims=True),
                                 log_a.shape)
    beta = _sigmoid(proj[:, 5 * w:])
    o = recurrence(cut(q), cut(k), cut(v), cut(log_a), cut(beta), cut)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + sz["eps"])
    o = (o * lp["o_norm"]["weight"]).reshape(s, w) \
        * _sigmoid(proj[:, 4 * w:5 * w])
    return x + _blocks(lambda o: into(o) @ lp["out"]["kernel"], o)


def mla_layer(lp, sz, x, cut=_same, into=_same):
    """``lp`` float32. The latent and the shared key are rounded to the
    stated ``cache_dtype`` in every precision. The control's arithmetic:
    ``cut`` rounds queries, keys, values and everything inside the attention,
    ``into`` the inputs of the projections around it."""
    kept = _kept(sz)
    s, nh = x.shape[0], sz["heads"]
    kr, nope, rp = sz["kv_rank"], sz["nope"], sz["rope"]
    wq = nh * (nope + rp)
    pos = jnp.arange(s)
    u = _rms(lp["norm"]["weight"], x, sz["eps"])
    a = _blocks(lambda u: into(u) @ lp["a_proj"]["kernel"], u)
    q = a[:, :wq].reshape(s, nh, nope + rp)
    c = cut(kept(_rms(lp["kv_norm"]["weight"], a[:, wq:wq + kr], sz["eps"])))
    k_pe = cut(kept(rope(sz, a[:, wq + kr:wq + kr + rp], pos)))
    gate = _sigmoid(a[:, wq + kr + rp:])                     # (s, heads)
    q_nope = cut(q[..., :nope]).transpose(1, 0, 2)
    q_pe = cut(rope(sz, q[..., nope:], pos)).transpose(1, 0, 2)
    scale = (nope + rp) ** -0.5

    def head(args):             # one head, one block of queries at a time
        q_nope, q_pe, w_k, w_v = args
        k_nope, v = cut(c @ w_k.T), cut(c @ w_v)

        def block(q_at):
            q_nope, q_pe, at = q_at
            scores = cut((q_nope @ k_nope.T + q_pe @ k_pe.T) * scale)
            p = cut(jax.nn.softmax(jnp.where(
                at[:, None] >= pos[None, :], scores, -jnp.inf), -1))
            return cut(p @ v)

        if s <= BLOCK or s % BLOCK:
            return block((q_nope, q_pe, pos))
        return jax.lax.map(block, (
            q_nope.reshape(-1, BLOCK, nope), q_pe.reshape(-1, BLOCK, rp),
            pos.reshape(-1, BLOCK))).reshape(s, -1)

    ctx = jax.lax.map(head, (q_nope, q_pe, lp["kv_b_k"], lp["kv_b_v"]))
    ctx = ctx.transpose(1, 0, 2) * gate[:, :, None]
    return x + _blocks(lambda o: into(o) @ lp["out"]["kernel"],
                       ctx.reshape(s, -1))


def dense_mlp(lp, sz, x, into=_same):
    u = _rms(lp["mlp_norm"]["weight"], x, sz["eps"])
    return x + _blocks(lambda u: into(_swiglu(
        into(u) @ lp["gate_up"]["kernel"])) @ lp["down"]["kernel"], u)


def route(lp, sz, u):
    """(chosen (s, k), dense weights (s, router_experts)): float32."""
    scores = _sigmoid(u @ lp["router"]["kernel"].astype(jnp.float32))
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


def experts_of(lp, sz, u, into=_same):
    """The expert sub-layer's function of its normed input rows ``u`` (s,
    hidden): ``(the held experts' part, the shared expert's, chosen (s,
    k))``. ``lp`` as served (bfloat16): one expert at a time is made float32.
    The router reads the rows as they are, whatever ``into`` makes of the
    experts' inputs."""
    outer = _f32({k: lp[k] for k in ("router", "router_bias",
                                     "shared_gate_up", "shared_down")})
    chosen, weights = route(outer, sz, u)
    mine = jax.lax.dynamic_slice_in_dim(
        weights, sz["expert_offset"], sz["experts_held"], axis=1)
    u = into(u)

    def one(total, expert):
        w_gate_up, w_down, w = expert
        out = _blocks(lambda u: into(_swiglu(
            u @ w_gate_up.astype(jnp.float32)))
            @ w_down.astype(jnp.float32), u)
        return total + w[:, None] * out, None

    routed = jax.lax.scan(one, jnp.zeros_like(u),
                          (lp["w_gate_up"], lp["w_down"], mine.T))[0]
    shared = _blocks(lambda u: into(_swiglu(
        u @ outer["shared_gate_up"]["kernel"]))
        @ outer["shared_down"]["kernel"], u)
    return routed, shared, chosen


def expert_mlp(lp, sz, x, into=_same):
    """Returns ``(x', chosen (s, k))``."""
    u = _rms(lp["mlp_norm"]["weight"].astype(jnp.float32), x, sz["eps"])
    routed, shared, chosen = experts_of(lp, sz, u, into)
    return x + routed + shared, chosen


_MIXER_KEYS = {KDA: ("norm", "in_proj", "conv", "a_log", "dt_bias", "o_norm",
                     "out"),
               MLA: ("norm", "a_proj", "kv_norm", "kv_b_k", "kv_b_v", "out")}


def hidden_states(params, sz: dict, ids, precision="float32"):
    """(seq,) token ids -> ((seq, hidden) before the final norm, the experts
    each expert layer's router chose (expert layers, seq, k)). ``params`` as
    served (bfloat16); one layer at a time is made float32."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    cut = into = _bf16 if precision == "bfloat16_activations" else _same
    x = params["embedding"]["word"]["embedding"][ids].astype(jnp.float32)
    chosen = []
    for at, lp in enumerate(params["layers"]):
        kind = sz["layer_types"][at]
        mixer = _f32({k: lp[k] for k in _MIXER_KEYS[kind]})
        if kind == KDA:
            x = kda_layer(mixer, sz, x, cut, into,
                          scalar_gate=precision == "scalar_gate")
        else:
            x = mla_layer(mixer, sz, x, cut, into)
        if at < sz["dense_layers"]:
            x = dense_mlp(_f32({k: lp[k] for k in ("mlp_norm", "gate_up",
                                                   "down")}), sz, x, into)
        else:
            x, picked = expert_mlp(lp, sz, x, into)
            chosen.append(picked)
    return x, jnp.stack(chosen)


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
    """Scores served tokens against the reference, as
    ``reference/deepseek_v3.py``'s does: for a prompt and the tokens served
    after it, the gap by which each served token's logit lies below the
    reference's best at that position. Every sequence is padded to a whole
    number of ``BLOCK`` positions (causal, so a real position never sees the
    padding after it): one compiled program a length, a handful."""

    BLOCK = BLOCK

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
        ``judged`` (a control: another model's best tokens at the same
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
