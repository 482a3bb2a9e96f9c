"""Plain reference of the ``glm5_next_text`` forward pass (GLM-5.3-Flash):
``hc_mult`` residual streams mixed by manifold-constrained hyper-connections
around every sub-layer; the mixer of layer ``l`` is learned-sparse latent
attention iff ``l % 4 == 3`` and Kimi Delta Attention (KDA) otherwise; a dense
SwiGLU in the leading ``first_k_dense_replace`` layers and a sparse expert
layer after them; a final RMSNorm over the streams' sum and an untied head.
Straightforward ``jax.numpy`` in float32 with matrix products at ``highest``
precision; KDA as the recurrence itself, one token at a time in a
``lax.scan``; the attention NOT absorbed (keys and values per head made
explicitly), the indexer's score of every query against every whole group
before it, a ``top_k``, a boolean mask, a masked softmax; no kernel, no cache,
no paging, no sort, no grouped product, no batching. It imports nothing of the
program under test.

*The residual path*, once per sub-layer ``F`` with its own parameters, ``X``
(n, hidden) a token's streams: ``x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)``;
``Hpre = sigmoid(a_pre (x~ P_pre) + b_pre)`` (n); ``Hpost = 2 sigmoid(a_post
(x~ P_post) + b_post)`` (n); ``Hres = Sinkhorn(exp(a_res mat(x~ P_res) +
b_res))`` (n x n: rows, then columns, normalised to sum 1, ``hc_sinkhorn_iters``
times); ``X <- Hres X + Hpost^T F(RMSNorm(Hpre X))``. The embedding is copied
into the streams; the final norm reads their sum.

*KDA*, ``H`` heads of ``d`` channels: as ``reference/ling3_flash_vl.py`` has it
(convolution, SiLU, l2norm, ``log a = gate_lower_bound * sigmoid(exp(A_log[h])
* (a + dt_bias))``, ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t
v_t^T``, ``y = W_o (RMSNorm_d(o_h) * sigmoid(g))``), with ``a = W_a2 W_a1 u``
and ``g = W_g2 W_g1 u`` through a bottleneck of ``kda_gate_rank``.

*Sparse latent attention.* ``c_q = RMSNorm(W_qa u)``; ``q_h = W_qb,h c_q``;
``c = RMSNorm(W_kva u)`` is what the served cache keeps of a token, rounded to
the stated ``cache_dtype`` in every precision; ``[k_h | v_h] = W_kvb,h c``; no
rotary. Indexer: ``qI_j = rope(W_qI,j c_q)``, ``kI = rope(LayerNorm(W_kI u))``,
``w = W_w u / sqrt(index_n_heads)``, the rotary on the first ``index_rope_dim``
channels (interleaved pairs, which stand de-interleaved after it, for both
alike); ``kbar_g = mean(kI_{pg..pg+p-1})``, ``p = index_kpool``, rounded to the
``cache_dtype`` too; for the query at ``t``, ``G = t // p``: ``I[g] = sum_j w_j
relu(qI_j . kbar_g) / sqrt(index_head_dim)`` for ``g < G``; ``S`` the
``min(index_topk / p, G)`` groups of largest ``I`` (ties to the lower group);
it attends the positions of ``S`` and ``pG .. t``; ``o_h = softmax(q_h . k_h /
sqrt(qk_nope_head_dim)) v_h`` over those; ``y = W_o concat(o_h)``.

*Expert layer.* ``sc = sigmoid(W_r u)`` in float32; the ``num_experts_per_tok``
largest ``sc + bias`` (one group: no limit); weights ``routed_scaling_factor *
sc / (sum of the chosen + 1e-20)``; every expert and the shared expert ``W_d
(silu(min(W_g x, l)) * clip(W_u x, -l, l))`` with ``l = swiglu_limit``; the
dense MLP the same form. THIS chip holds experts ``expert_offset .. +
n_routed_experts - 1`` of the router's ``published.n_routed_experts`` and adds
up their part alone, and its vocabulary is the slice the configuration keeps.

What the published config does not say is listed under ``assumed`` in the
configuration file; each entry that is a choice between forms (``ASSUMED``) is
read here and by the program, and a file that states another is refused.

The weights are served in bfloat16 (norms, ``A_log``, ``dt_bias``, the router's
bias and the hyper-connections in float32), rounded once, here; layer ``l``
(its number in the whole model) is drawn from ``fold_in(key, l)`` and expert
``e`` of it from ``fold_in(., e)``. The forward upcasts ONE layer (one expert)
at a time and works ``BLOCK`` rows at a time.

Controls, which ``correct`` has to refuse: ``bfloat16_activations`` (what the
configuration states as float32 is bfloat16, cut with ``lax.reduce_precision``);
``dense_attention`` (float32, every position before ``t`` attended: a program
that did not apply the selection); ``single_stream`` (float32, ``Hres`` the
identity, ``Hpre`` 1/n, ``Hpost`` 1: a program that did not apply the residual
maps).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16_activations", "dense_attention",
              "single_stream")
KDA, DSA = "kda", "dsa"
_L2_EPS = 1e-6
BLOCK = 512         # rows of an MLP pass, queries of an attention pass

#: the choices between forms that the published config leaves open, as they
#: are written down here (the configuration file's ``assumed`` states each)
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

#: keys of the published config whose value decides a form written down here
_PUBLISHED_FORMS = {
    "scoring_func": "sigmoid", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "qk_rope_head_dim": 0,
    "mla_use_nope": True, "mhc": True, "index_kpool_compress": True,
    "index_kpool_always_select_tail": True, "indexer_rope_interleave": True,
    "attention_bias": False, "hidden_act": "silu", "n_shared_experts": 1,
    "tie_word_embeddings": False,
}
_KINDS = {"linear_attention": KDA, "deepseek_sparse_attention": DSA}


def sizes_of(config: dict) -> dict:
    for key, form in _PUBLISHED_FORMS.items():
        if config[key] != form:
            raise ValueError(f"{key} = {config[key]!r}: only {form!r} is "
                             "written down here")
    for name in sorted(set(ASSUMED) | {
            k for k, v in config["assumed"].items() if isinstance(v, list)}):
        said = config["assumed"].get(name, [None])[0]
        if said != ASSUMED.get(name):
            raise ValueError(f"assumed {name} = {said!r}: this reference "
                             f"implements {ASSUMED.get(name)!r}")
    layers = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    types = tuple(_KINDS[t] for t in config["layer_types"])
    mlps = list(config["mlp_layer_types"])
    if len(types) != layers or mlps != ["dense"] * dense + ["sparse"] * (
            layers - dense) or set(config["indexer_types"]) != {"full"}:
        raise ValueError("layer_types / mlp_layer_types / indexer_types do "
                         "not describe the layers held")
    kda = config["linear_attn_config"]
    heads = int(config["num_attention_heads"])
    if int(kda["num_heads"]) != heads \
            or int(config["num_key_value_heads"]) != heads \
            or int(config["qk_head_dim"]) != int(config["qk_nope_head_dim"]):
        raise ValueError("KDA and attention heads differ, or fewer K/V "
                         "heads, or a roped part: not written down here")
    published = config.get("published", {})
    sizes = config["assumed_sizes"]
    pool = int(config["index_kpool"])
    if int(config["index_topk"]) % pool \
            or int(config["serving"]["page_size"]) % pool:
        raise ValueError("index_topk and the page size count positions in "
                         f"whole groups of {pool}")
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "depth": layers, "first_layer": int(config["first_layer_held"]),
            "layer_types": types,
            "kda_layers": types.count(KDA), "mla_layers": types.count(DSA),
            "dense_layers": dense, "expert_layers": layers - dense,
            "heads": heads, "head_dim": int(kda["head_dim"]),
            "conv_kernel": int(kda["short_conv_kernel_size"]),
            "kda_lower_bound": float(kda["gate_lower_bound"]),
            "kda_gate_rank": int(sizes["kda_gate_rank"]),
            "q_rank": int(config["q_lora_rank"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "v_dim": int(config["v_head_dim"]),
            "index_heads": int(config["index_n_heads"]),
            "index_width": int(config["index_head_dim"]),
            "index_topk": int(config["index_topk"]),
            "index_pool": pool,
            "index_rope": int(sizes["index_rope_dim"]),
            "index_rope_theta": float(sizes["index_rope_theta"]),
            "index_norm_eps": float(sizes["index_norm_eps"]),
            "streams": int(config["hc_mult"]),
            "sinkhorn_iters": int(config["hc_sinkhorn_iters"]),
            "hc_eps": float(config["hc_eps"]),
            "hc_spread": float(sizes["hc_init_spread"]),
            "dense_ffn": int(config["intermediate_size"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "expert_width": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["n_shared_experts"])
            * int(config["moe_intermediate_size"]),
            "router_experts": int(published.get("n_routed_experts",
                                                config["n_routed_experts"])),
            "experts_held": int(config["n_routed_experts"]),
            "expert_offset": int(config.get("expert_offset", 0)),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "swiglu_limit": float(config["swiglu_limit"]),
            "eps": float(config["rms_norm_eps"]),
            "latent_width": int(config["kv_lora_rank"]),
            "row_width": int(config["serving"]["row_width"]),
            "cache_dtype": config["serving"]["cache_dtype"],
            "page_size": int(config["serving"]["page_size"]),
            "positions": int(config["serving"]["max_len"]),
            # served tokens of a request that are judged (the forward of a
            # prompt of 12,288 and these fits beside the served weights)
            "judged_tokens": int(config["correct"]["tokens_per_request"]),
            # every judged sequence is padded to at least this many
            # positions: ONE compiled scorer, whatever requests a seed draws
            "judged_positions": int(config["correct"].get(
                "padded_positions", 0))}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (made outside ``jit``:
    a new seed is no new program)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


# the router's bias is what ``noaux_tc`` training leaves: the one that
# balances the experts' load. Here it is found for the seed's own router, on
# this many tokens drawn from the seed, by that training's own rule (the bias
# of an expert with less than its share of the tokens goes up a step, of one
# with more down: DeepSeek-V3's auxiliary-loss-free balancing)
BALANCE_TOKENS, BALANCE_STEPS, BALANCE_RATE = 2048, 300, 0.002


def make_weights(sz: dict, key):
    """The weights from ``key`` as they are served, layer ``l`` (its number in
    the whole model) from ``fold_in(key, l)``: matrices ``N(0, 1/fan_in)``,
    the embedding 0.02, the convolution taps 0.5, ``A`` uniform in 1..16,
    ``dt`` log-uniform in 0.001..0.1 with its inverse softplus as ``dt_bias``,
    the router's bias the one that balances its load (:func:`balanced_bias`,
    found layer by layer on ``BALANCE_TOKENS`` tokens drawn from the key),
    norms 1 (the indexer's LayerNorm 1 and 0); a hyper-connection's ``P`` ``N(0, 1/fan_in)`` float32,
    its scalars ``a_*`` uniform in ``hc_spread * (0.5 .. 1.5)`` and its biases
    ``N(., hc_spread)`` around the values that make ``Hpre = 1/n``, ``Hpost =
    1`` and ``Hres`` near the identity (``4 I``), so that every stream and
    every Sinkhorn pass matters; matrices rounded to bfloat16, one layer (one
    expert) at a time. Traced: call under ``jax.jit`` with the key as an
    argument."""
    h, nh, d = sz["hidden"], sz["heads"], sz["head_dim"]
    qr, kr, w, r = sz["q_rank"], sz["kv_rank"], nh * d, sz["kda_gate_rank"]
    ih, iw, n = sz["index_heads"], sz["index_width"], sz["streams"]
    spread = sz["hc_spread"]

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

    def hyper(normal, uniform):
        f32 = dict(dtype=jnp.float32)
        return {"proj": normal(math.sqrt(1.0 / (n * h)), n * h,
                               2 * n + n * n, **f32),
                "a_pre": uniform(0.5 * spread, 1.5 * spread),
                "a_post": uniform(0.5 * spread, 1.5 * spread),
                "a_res": uniform(0.5 * spread, 1.5 * spread),
                "b_pre": -math.log(n - 1.0) + normal(spread, n, **f32),
                "b_post": normal(spread, n, **f32),
                "b_res": 4.0 * jnp.eye(n) + normal(spread, n, n, **f32)}

    def kda(normal, uniform):
        dt = jnp.exp(uniform(math.log(1e-3), math.log(0.1), w))
        return {"norm": norm(h),
                "in_proj": dense(normal, h, 3 * w + 2 * r + nh),
                "a_up": dense(normal, r, w), "g_up": dense(normal, r, w),
                "conv": {"weight": normal(0.5, sz["conv_kernel"], 3 * w)},
                "a_log": jnp.log(uniform(1.0, 16.0, nh)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": norm(d), "out": dense(normal, w, h)}

    def dsa(normal, uniform):
        return {"norm": norm(h),
                "a_proj": dense(normal, h, qr + kr),
                "q_norm": norm(qr), "kv_norm": norm(kr),
                "q_b": dense(normal, qr, nh * sz["nope"]),
                "kv_b_k": normal(math.sqrt(1.0 / kr), nh, sz["nope"], kr),
                "kv_b_v": normal(math.sqrt(1.0 / kr), nh, kr, sz["v_dim"]),
                "index_q": dense(normal, qr, ih * iw),
                "index_kw": dense(normal, h, iw + ih),
                "index_norm": {"weight": jnp.ones((iw,), jnp.float32),
                               "bias": jnp.zeros((iw,), jnp.float32)},
                "out": dense(normal, nh * sz["v_dim"], h)}

    def layer(at):
        number = sz["first_layer"] + at
        k_layer = jax.random.fold_in(key, number)
        normal, uniform = drawer(k_layer)
        out = (kda if sz["layer_types"][at] == KDA else dsa)(normal, uniform)
        out["hc_mixer"] = hyper(normal, uniform)
        out["hc_mlp"] = hyper(normal, uniform)
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
        out["router_bias"] = jnp.zeros((sz["router_experts"],), jnp.float32)
        out["w_gate_up"], out["w_down"] = jax.lax.map(
            expert, sz["expert_offset"] + jnp.arange(sz["experts_held"]))
        out["shared_gate_up"] = dense(normal, h, 2 * sf)
        out["shared_down"] = dense(normal, sf, h)
        return out

    normal, _ = drawer(jax.random.fold_in(key, 1 << 24))
    params = {
        "embedding": {"word": {"embedding": normal(0.02, sz["vocab"], h)}},
        "layers": [layer(at) for at in range(sz["depth"])],
        "final_norm": norm(h),
        "head": dense(normal, h, sz["vocab"]),
    }
    # the routers' biases, layer by layer, each found behind the layers (and
    # the balanced routers) in front of it
    ids = jax.random.randint(jax.random.fold_in(key, 1 << 25),
                             (min(BALANCE_TOKENS, sz["positions"]),), 2,
                             sz["vocab"])
    found = []
    hidden_states(params, sz, ids, balance=found)
    for lp, bias in zip(params["layers"][sz["dense_layers"]:], found):
        lp["router_bias"] = bias
    return params


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
    Cut with ``lax.reduce_precision``, which the compiler has to honour."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def _same(t):
    return t


def _kept(sz):
    """What keeping a cache row does to it: rounds it to the stated
    ``cache_dtype`` (and hands it back as float32)."""
    return {"bfloat16": _bf16, "float32": _same}[sz["cache_dtype"]]


def swiglu(gate_up, limit):
    """``silu(min(gate, limit)) * clip(up, -limit, limit)``."""
    f = gate_up.shape[-1] // 2
    return _silu(jnp.minimum(gate_up[:, :f], limit)) \
        * jnp.clip(gate_up[:, f:], -limit, limit)


def _blocks(f, x):
    """``f`` over ``x`` (an array (s, ...) or a tuple of them) ``BLOCK`` rows
    at a time where ``s`` is a whole number of them."""
    s = jax.tree.leaves(x)[0].shape[0]
    if s <= BLOCK or s % BLOCK:
        return f(x)
    out = jax.lax.map(f, jax.tree.map(
        lambda t: t.reshape(s // BLOCK, BLOCK, *t.shape[1:]), x))
    return jax.tree.map(lambda o: o.reshape(s, *o.shape[2:]), out)


def sinkhorn(m, iters):
    for _ in range(iters):
        m = m / jnp.sum(m, -1, keepdims=True)
        m = m / jnp.sum(m, -2, keepdims=True)
    return m


def hyper_maps(hp, sz, X, cut=_same, single_stream=False):
    """``X`` (s, n, hidden) -> ``(Hpre (s, n), Hpost (s, n), Hres (s, n,
    n))``."""
    s, n, _ = X.shape
    if single_stream:
        return (jnp.full((s, n), 1.0 / n), jnp.ones((s, n)),
                jnp.broadcast_to(jnp.eye(n), (s, n, n)))
    flat = X.reshape(s, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + sz["hc_eps"])
    maps = cut(cut(flat) @ hp["proj"])
    pre = _sigmoid(hp["a_pre"] * maps[:, :n] + hp["b_pre"])
    post = 2.0 * _sigmoid(hp["a_post"] * maps[:, n:2 * n] + hp["b_post"])
    res = sinkhorn(jnp.exp(hp["a_res"] * maps[:, 2 * n:].reshape(s, n, n)
                           + hp["b_res"]), sz["sinkhorn_iters"])
    return pre, post, res


def hyper(hp, sz, X, F, cut=_same, single_stream=False):
    """``X <- Hres X + Hpost^T F(Hpre X)``; ``F`` may return a tuple whose
    first entry is the sub-layer's output."""
    pre, post, res = _blocks(
        lambda X: hyper_maps(hp, sz, X, cut, single_stream), X)
    out = F(jnp.einsum("sn,snh->sh", pre, X))
    y, rest = (out[0], out[1:]) if isinstance(out, tuple) else (out, ())
    X = jnp.einsum("smn,snh->smh", res, X) + post[:, :, None] * y[:, None]
    return (X,) + tuple(rest) if rest else X


def causal_conv(x, weight):
    w = weight.shape[0]
    xp = jnp.pad(x, ((w - 1, 0), (0, 0)))
    return sum(weight[j] * xp[j:j + x.shape[0]] for j in range(w))


def recurrence(q, k, v, log_a, beta, cut=_same):
    """``q``, ``k``, ``v``, ``log_a`` (s, H, d), ``beta`` (s, H): the KDA
    recurrence from a zero state, one token at a time."""
    heads, d = q.shape[1], q.shape[2]

    def step(S, row):
        q, k, v, log_a, beta = row
        S = jnp.exp(log_a)[:, :, None] * S                  # Diag(a) S
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S))
        S = cut(S + k[:, :, None] * u[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q, S)

    return jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, log_a, beta))[1]


HEAD_GROUPS = 4     # a KDA layer's heads go through the recurrence in this
#                     many parts, one after the other: a quarter of the
#                     operands of 64 heads over 12,800 positions at a time


def kda_mix(lp, sz, x, cut=_same, into=_same):
    """What a KDA layer adds to the stream ``x`` (s, hidden) it reads; ``lp``
    float32. The heads are independent up to the output projection, so they
    are taken ``HEAD_GROUPS`` parts at a time (the same numbers)."""
    s, nh, d = x.shape[0], sz["heads"], sz["head_dim"]
    w, r = nh * d, sz["kda_gate_rank"]
    parts = HEAD_GROUPS if nh % HEAD_GROUPS == 0 else 1
    hg = nh // parts                # heads of a part
    u = _rms(lp["norm"]["weight"], x, sz["eps"])
    kernel = lp["in_proj"]["kernel"]
    narrow = _blocks(lambda u: into(u) @ kernel[:, 3 * w:], u)
    a1, g1, b = narrow[:, :r], narrow[:, r:2 * r], narrow[:, 2 * r:]
    cols = lambda t, g, n: jax.lax.dynamic_slice_in_dim(t, g * n, n, t.ndim - 1)

    def part(g):
        def qkv(i):     # this part's channels of q~, k~ or v~
            at = i * w + g * hg * d
            y = _blocks(lambda u: into(u) @ jax.lax.dynamic_slice_in_dim(
                kernel, at, hg * d, 1), u)
            taps = jax.lax.dynamic_slice_in_dim(lp["conv"]["weight"], at,
                                                hg * d, 1)
            return _silu(causal_conv(y, taps)).reshape(s, hg, d)

        q, k, v = _l2norm(qkv(0)) / math.sqrt(d), _l2norm(qkv(1)), qkv(2)
        a = into(a1) @ cols(lp["a_up"]["kernel"], g, hg * d)
        gate = into(g1) @ cols(lp["g_up"]["kernel"], g, hg * d)
        log_a = sz["kda_lower_bound"] * _sigmoid(
            jnp.exp(cols(lp["a_log"], g, hg))[:, None]
            * (a + cols(lp["dt_bias"], g, hg * d)).reshape(s, hg, d))
        beta = _sigmoid(cols(b, g, hg))
        o = recurrence(cut(q), cut(k), cut(v), cut(log_a), cut(beta), cut)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + sz["eps"])
        return (o * lp["o_norm"]["weight"]).reshape(s, hg * d) \
            * _sigmoid(gate)

    o = jax.lax.map(part, jnp.arange(parts))        # (parts, s, hg * d)
    o = jnp.moveaxis(o, 0, 1).reshape(s, w)
    return _blocks(lambda o: into(o) @ lp["out"]["kernel"], o)


def index_rope(sz, x, pos):
    """The indexer's rotary on the first ``index_rope`` channels of ``x`` (s,
    ..., width): pair ``i`` is ``(x[2i], x[2i + 1])`` at ``theta ** (-2i /
    index_rope)``; the rotated pairs stand de-interleaved."""
    d = sz["index_rope"]
    inv_freq = jnp.asarray([sz["index_rope_theta"] ** (-i / d)
                            for i in range(0, d, 2)], jnp.float32)
    theta = pos.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(theta), jnp.sin(theta)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0:d:2], x[..., 1:d:2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., d:]], -1)


def picked_groups(scores, pos, sz):
    """``scores`` (rows, groups) of the queries at ``pos`` (rows,): (rows,
    groups) bool, the ``index_topk / index_pool`` best among the groups whole
    before each query, ties to the lower group; all of them where there are no
    more."""
    p = sz["index_pool"]
    whole = jnp.arange(scores.shape[1])[None, :] < (pos // p)[:, None]
    k = min(sz["index_topk"] // p, scores.shape[1])
    order = jnp.argsort(-jnp.where(whole, scores, -jnp.inf), axis=-1,
                        stable=True)
    # a group's place in that order is where the order's own order puts it
    best = jnp.argsort(order, axis=-1) < k
    return best & whole


def dsa_mix(lp, sz, x, cut=_same, into=_same, dense_attention=False):
    """What the sparse-attention layer adds to the stream ``x`` (s, hidden)
    it reads, and the groups each query picked (s, groups) bool; ``lp``
    float32. The latent and the pooled keys are rounded to the stated
    ``cache_dtype`` in every precision."""
    kept = _kept(sz)
    s, nh, p = x.shape[0], sz["heads"], sz["index_pool"]
    qr, ih, iw = sz["q_rank"], sz["index_heads"], sz["index_width"]
    pos = jnp.arange(s)
    u = _rms(lp["norm"]["weight"], x, sz["eps"])
    a = _blocks(lambda u: into(u) @ lp["a_proj"]["kernel"], u)
    c_q = _rms(lp["q_norm"]["weight"], a[:, :qr], sz["eps"])
    c = cut(kept(_rms(lp["kv_norm"]["weight"], a[:, qr:], sz["eps"])))
    q = _blocks(lambda c_q: into(c_q) @ lp["q_b"]["kernel"], c_q)
    q = cut(q).reshape(s, nh, -1).transpose(1, 0, 2)
    # the indexer
    iq = _blocks(lambda c_q: cut(c_q) @ lp["index_q"]["kernel"], c_q)
    iq = cut(index_rope(sz, iq.reshape(s, ih, iw), pos))
    kw = _blocks(lambda u: cut(u) @ lp["index_kw"]["kernel"], u)
    ik = kw[:, :iw] - jnp.mean(kw[:, :iw], -1, keepdims=True)
    ik = ik * jax.lax.rsqrt(jnp.mean(ik * ik, -1, keepdims=True)
                            + sz["index_norm_eps"])
    ik = index_rope(sz, ik * lp["index_norm"]["weight"]
                    + lp["index_norm"]["bias"], pos)
    w_head = cut(kw[:, iw:] * (ih ** -0.5 * iw ** -0.5))
    ik = jnp.pad(ik, ((0, -s % p), (0, 0)))
    kbar = cut(kept(jnp.mean(ik.reshape(-1, p, iw), 1)))    # (groups, width)

    def select(of):
        iq, w_head, at = of
        scores = jnp.einsum("rh,rhg->rg", w_head, jnp.maximum(
            jnp.einsum("rhd,gd->rhg", iq, kbar), 0.0))
        picked = picked_groups(cut(scores), at, sz)
        own = (pos[None, :] // p == (at // p)[:, None]) \
            & (pos[None, :] <= at[:, None])
        return jnp.repeat(picked, p, axis=1)[:, :s] | own, picked

    allowed, picked = _blocks(select, (iq, w_head, pos))
    if dense_attention:
        allowed = pos[None, :] <= pos[:, None]
    scale = sz["nope"] ** -0.5

    def head(args):             # one head, one block of queries at a time
        q, w_k, w_v = args
        k, v = cut(c @ w_k.T), cut(c @ w_v)

        def block(of):
            q, allowed = of
            scores = cut((q @ k.T) * scale)
            return cut(cut(jax.nn.softmax(
                jnp.where(allowed, scores, -jnp.inf), -1)) @ v)

        if s <= BLOCK or s % BLOCK:
            return block((q, allowed))
        return jax.lax.map(block, (
            q.reshape(-1, BLOCK, q.shape[-1]),
            allowed.reshape(-1, BLOCK, s))).reshape(s, -1)

    ctx = jax.lax.map(head, (q, lp["kv_b_k"], lp["kv_b_v"]))
    ctx = ctx.transpose(1, 0, 2).reshape(s, -1)
    return _blocks(lambda o: into(o) @ lp["out"]["kernel"], ctx), picked


def dense_mlp_of(lp, sz, u, into=_same):
    return _blocks(lambda u: into(swiglu(
        into(u) @ lp["gate_up"]["kernel"], sz["swiglu_limit"]))
        @ lp["down"]["kernel"], u)


def route(lp, sz, u):
    """(chosen (s, k), dense weights (s, router_experts)): float32."""
    scores = _sigmoid(u @ lp["router"]["kernel"].astype(jnp.float32))
    choice = scores + lp["router_bias"]
    chosen = jnp.argsort(-choice, axis=-1,
                         stable=True)[:, :sz["experts_per_token"]]
    picked = jnp.take_along_axis(scores, chosen, -1)
    w = sz["routed_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)
    dense = jnp.sum(jnp.where(
        chosen[:, :, None] == jnp.arange(scores.shape[1]), w[:, :, None],
        0.0), 1)
    return chosen, dense


def balanced_bias(lp, sz, u):
    """The router's bias that balances its load over the rows ``u`` (s,
    hidden), by ``noaux_tc``'s rule: ``BALANCE_STEPS`` times, every expert
    that got fewer than ``s k / experts`` of the rows' choices gains
    ``BALANCE_RATE``, every one that got more loses it. float32
    (router_experts,)."""
    scores = _sigmoid(u @ lp["router"]["kernel"].astype(jnp.float32))
    s, e = scores.shape
    k = sz["experts_per_token"]

    def step(_, bias):
        chosen = jax.lax.top_k(scores + bias, k)[1]
        load = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return bias + BALANCE_RATE * jnp.sign(s * k / e - load)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((e,), jnp.float32))


def experts_of(lp, sz, u, into=_same):
    """The expert sub-layer's function of its normed input rows ``u`` (s,
    hidden): ``(the held experts' part, the shared expert's, chosen (s,
    k))``. ``lp`` as served (bfloat16): one expert at a time is made float32.
    The router reads the rows as they are, whatever ``into`` makes of the
    experts' inputs."""
    outer = _f32({k: lp[k] for k in ("router", "router_bias",
                                     "shared_gate_up", "shared_down")})
    limit = sz["swiglu_limit"]
    chosen, weights = route(outer, sz, u)
    mine = jax.lax.dynamic_slice_in_dim(
        weights, sz["expert_offset"], sz["experts_held"], axis=1)
    u = into(u)

    def of(w_gate_up, w_down, rows):
        return into(swiglu(rows @ w_gate_up.astype(jnp.float32), limit)) \
            @ w_down.astype(jnp.float32)

    def one(total, expert):
        w_gate_up, w_down, w = expert
        s = u.shape[0]
        if s <= BLOCK or s % BLOCK:
            return total + w[:, None] * of(w_gate_up, w_down, u), None
        # an expert works the rows that chose it, BLOCK at a time: those rows
        # first, then as many whole blocks as hold them (a row that did not
        # choose it, in the last of them, weighs 0)
        order = jnp.argsort(w <= 0, stable=True)

        def block(i, total):
            at = jax.lax.dynamic_slice_in_dim(order, i * BLOCK, BLOCK)
            return total.at[at].add(
                w[at][:, None] * of(w_gate_up, w_down, u[at]))

        return jax.lax.fori_loop(0, -(-jnp.sum(w > 0) // BLOCK), block,
                                 total), None

    routed = jax.lax.scan(one, jnp.zeros_like(u),
                          (lp["w_gate_up"], lp["w_down"], mine.T))[0]
    shared = _blocks(lambda u: into(swiglu(
        u @ outer["shared_gate_up"]["kernel"], limit))
        @ outer["shared_down"]["kernel"], u)
    return routed, shared, chosen


_MIXER_KEYS = {KDA: ("norm", "in_proj", "a_up", "g_up", "conv", "a_log",
                     "dt_bias", "o_norm", "out"),
               DSA: ("norm", "a_proj", "q_norm", "kv_norm", "q_b", "kv_b_k",
                     "kv_b_v", "index_q", "index_kw", "index_norm", "out")}


def hidden_states(params, sz: dict, ids, precision="float32", balance=None):
    """(seq,) token ids -> ((seq, hidden): the streams' sum before the final
    norm, the experts each expert layer's router chose (expert layers, seq,
    k), the groups each sparse layer's queries picked (sparse layers, seq,
    groups) bool). ``params`` as served (bfloat16); one layer at a time is made
    float32. With ``balance`` (a list; ``make_weights`` alone) every expert
    layer routes by the bias that balances its load over ``ids``
    (:func:`balanced_bias`), which is appended there."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    cut = into = _bf16 if precision == "bfloat16_activations" else _same
    single = precision == "single_stream"
    x = params["embedding"]["word"]["embedding"][ids].astype(jnp.float32)
    X = jnp.broadcast_to(x[:, None], (x.shape[0], sz["streams"], x.shape[1]))
    chosen, picks = [], []
    for at, lp in enumerate(params["layers"]):
        kind = sz["layer_types"][at]
        mixer = _f32({k: lp[k] for k in _MIXER_KEYS[kind]})
        if kind == KDA:
            X = hyper(lp["hc_mixer"], sz, X,
                      lambda x: kda_mix(mixer, sz, x, cut, into), cut, single)
        else:
            X, picked = hyper(
                lp["hc_mixer"], sz, X, lambda x: dsa_mix(
                    mixer, sz, x, cut, into,
                    dense_attention=precision == "dense_attention"),
                cut, single)
            picks.append(picked)
        w_norm = lp["mlp_norm"]["weight"].astype(jnp.float32)
        if at < sz["dense_layers"]:
            mlp = _f32({k: lp[k] for k in ("gate_up", "down")})
            X = hyper(lp["hc_mlp"], sz, X, lambda x: dense_mlp_of(
                mlp, sz, _rms(w_norm, x, sz["eps"]), into), cut, single)
        else:
            def experts(x):
                u, layer = _rms(w_norm, x, sz["eps"]), lp
                if balance is not None:
                    balance.append(balanced_bias(lp, sz, u))
                    layer = {**lp, "router_bias": balance[-1]}
                routed, shared, picked = experts_of(layer, sz, u, into)
                return routed + shared, picked

            X, picked = hyper(lp["hc_mlp"], sz, X, experts, cut, single)
            chosen.append(picked)
    return jnp.sum(X, 1), jnp.stack(chosen), jnp.stack(picks)


def _head(params, sz: dict, hid, precision):
    hid = _rms(params["final_norm"]["weight"], hid, sz["eps"])
    if precision == "bfloat16_activations":
        hid = _bf16(hid)
    return hid @ params["head"]["kernel"].astype(jnp.float32)


def logits_at(params, sz: dict, ids, positions, precision="float32"):
    """Logits at ``positions`` of ``ids`` over the vocabulary kept."""
    return _head(params, sz, hidden_states(params, sz, ids, precision)[0][
        positions], precision)


_SERVED = {}        # (seed, sizes) -> the one bfloat16 tree of that seed


def served_weights(sz: dict, seed: int):
    at = (seed, tuple(sorted(sz.items())))
    if at not in _SERVED:
        _SERVED.clear()                     # one model fits, not two
        _SERVED[at] = jax.jit(lambda key: make_weights(sz, key))(
            seed_key(seed))
    return _SERVED[at]


def padded_length(sz: dict, n: int) -> int:
    """The positions a sequence of ``n`` tokens is scored over:
    ``judged_positions``, a longer one a whole number of ``BLOCK``, at most
    the cache row's ``positions``."""
    return min(max(-(-n // BLOCK) * BLOCK, sz["judged_positions"]),
               sz["positions"])


_PROGRAMS = {}      # (sizes, precision, padded length) -> the compiled scorer
_DECIDED = {}       # a padded sequence's bytes -> what the forward decided


def scorer_program(sz: dict, params, length: int, precision="float32"):
    """The scorer compiled for sequences padded to ``length``: ``(params, ids
    (length,), served (judged_tokens,), first, n) -> (the gap of each served
    token under the best logit at its position, the best tokens, the experts
    every router chose (expert layers, length, k), the groups the sparse
    layers picked at the judged positions (sparse layers, judged_tokens,
    groups))``. ``params`` are the served weights or their shapes: a compile
    needs no more, and a benchmark makes it beside its other compiles (a
    compile of this forward takes longer than ten of its runs: 24-32 s
    against 1.3-2.7 s at 4,352-8,448 positions, my chip run, PR 47)."""
    at = (tuple(sorted(sz.items())), precision, length)
    if at not in _PROGRAMS:
        def score(params, ids, served, first, n):
            pos = jnp.clip(first - 1 + jnp.arange(served.shape[0]), 0,
                           ids.shape[0] - 1)
            hid, chosen, picks = hidden_states(params, sz, ids, precision)
            logits = _head(params, sz, hid[pos], precision)
            got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
            valid = jnp.arange(served.shape[0]) < n
            return (jnp.where(valid, jnp.max(logits, -1) - got, 0.0),
                    jnp.argmax(logits, -1), chosen, picks[:, pos])

        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        with jax.default_matmul_precision("highest"):
            _PROGRAMS[at] = jax.jit(score).lower(
                params, i32(length), i32(sz["judged_tokens"]), i32(),
                i32()).compile()
    return _PROGRAMS[at]


class Scorer:
    """Scores served tokens against the reference, as
    ``reference/ling3_flash_vl.py``'s does: for a prompt and the tokens served
    after it, the gap by which each served token's logit lies below the
    reference's best at that position. Of a request's served tokens the first
    ``judged_tokens`` are judged. Every sequence is padded to
    :func:`padded_length` positions (causal, so a real position never sees
    the padding after it): one compiled program a length
    (:func:`scorer_program`), and what the forward decided on its way is kept
    for :meth:`decided`."""

    def __init__(self, sz: dict, seed: int, precision: str = "float32"):
        self.sz, self.precision = sz, precision
        self.params = served_weights(sz, seed)

    def _padded(self, seq):
        if len(seq) > self.sz["positions"]:
            raise ValueError(f"{len(seq)} tokens pass the "
                             f"{self.sz['positions']} positions of a cache "
                             "row")
        ids = np.zeros((padded_length(self.sz, len(seq)),), np.int32)
        ids[:len(seq)] = seq
        return ids

    def gaps(self, prompt, served, judged=None):
        """(gaps, this model's own best tokens) at the positions that
        produced the first ``judged_tokens`` of ``served``. The tokens judged
        are the served ones, or ``judged`` (a control: another model's best
        tokens at the same positions of the same teacher-forced sequence)."""
        served = list(served)[:self.sz["judged_tokens"]]
        ids = self._padded(list(prompt) + served)
        out = np.zeros((self.sz["judged_tokens"],), np.int32)
        out[:len(served)] = served if judged is None \
            else judged[:len(served)]
        gaps, top, chosen, picks = scorer_program(
            self.sz, self.params, len(ids), self.precision)(
            self.params, jnp.asarray(ids), jnp.asarray(out),
            jnp.int32(len(prompt)), jnp.int32(len(served)))
        if self.precision == "float32":
            _DECIDED[ids.tobytes()] = (
                np.sort(np.asarray(chosen), axis=-1),
                np.asarray(picks)[:, :len(served)])
        return (np.asarray(gaps)[:len(served)],
                np.asarray(top)[:len(served)])

    def decided(self, prompt, served):
        """What the float32 forward decided over ``prompt`` and the judged
        tokens of ``served``: the experts each expert layer's router chose at
        every position (expert layers, tokens, k), sorted along k, and the
        groups each sparse layer's queries picked at the positions that
        produced the judged tokens (sparse layers, judged tokens, groups)
        bool. From :meth:`gaps`' own run of that sequence where it made
        one."""
        served = list(served)[:self.sz["judged_tokens"]]
        at = self._padded(list(prompt) + served).tobytes()
        if at not in _DECIDED:
            self.gaps(prompt, served)
        chosen, picks = _DECIDED[at]
        return chosen[:, :len(prompt) + len(served)], picks
