"""Plain reference of the ``nemotron_h`` forward pass: every layer is one mixer
under a pre-norm residual, ``x <- x + mixer(RMSNorm(x))``, the kind of each
layer one character of ``hybrid_override_pattern``; a final RMSNorm and an
untied head. Straightforward ``jax.numpy`` in float32 with matrix products at
``highest`` precision; no chunks, no sort, no grouped product, no kernel, no
cache, no paging, no batching. It imports nothing of the program under test.

``M``, Mamba-2 (Dao and Gu, arXiv:2405.21060): ``[z | xBC | dt] = W_in u``;
``xBC <- SiLU(conv_4(xBC) + b)`` (causal, depthwise), split into ``x`` (H, P),
``B``, ``C`` (G, N); ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T,    y_t = S_t C_t + D x_t

one token at a time in a ``lax.scan`` (head ``h`` reads group ``h // (H /
G)``); the output is ``W_out RMSNorm_grouped(y * SiLU(z))`` over groups of
``d_inner / G`` channels.

``*``, attention: 32 query heads over 2 K/V heads (K and V repeated to the
query heads), full causal softmax, no positional embedding.

``E``, latent expert layer: ``s = sigmoid(W_r u)`` in float32; the
``num_experts_per_tok`` experts with the largest ``s + b`` (ties to the lower
index); weights ``routed_scaling_factor * s / (sum of the chosen s + 1e-20)``;
``l = W_dn u``; every HELD expert in turn (a loop, each token's weight for it
zero where it was not chosen) gives ``w W2 relu(W1 l)^2``; the sum goes
through ``W_up``; the shared expert ``W2_s relu(W1_s u)^2`` works on the full
width beside them. The reference is given the same share of the model as the
program: it holds experts ``expert_offset .. + experts_held - 1`` of the
router's ``router_experts`` and adds up their part alone, and its vocabulary
is the slice the configuration keeps. What the absent experts would add is
left out here as there.

What the published config does not say is listed under ``assumed`` in the
configuration file; program and reference follow the same list.

Departures: the projections are stored fused, in the order the program
consumes (``in_proj`` = ``[z | x | B | C | dt]``, ``qkv`` = ``[Q | K | V]``):
with seeded random weights this only names the columns. Layers are stacked by
their place in the pattern's period: ``periods`` is a list with one tree per
character of the period, every leaf leading with ``(repeats,)``.

The weights are served in bfloat16 (norms, ``A_log``, ``dt_bias``, ``D``, the
convolution's and the router's bias in float32), so the seeded weights are
rounded to bfloat16 once, here, and both sides get those values. Layer ``l``
is drawn from ``fold_in(key, l)`` and expert ``e`` (its number in the whole
model) of it from ``fold_in(., e)``, so a chip that holds other experts draws
the same model; the forward upcasts ONE layer at a time (one expert at a time
inside an expert layer), so no float32 copy of the model ever exists;
``Scorer`` instances of one seed share the one bfloat16 tree.

The lower precision the control runs (``Scorer(..., precision=
"bfloat16_state")``), which ``correct`` has to refuse: the Mamba-2 state is
kept in bfloat16 and the scan's arithmetic is done in bfloat16, everything
else float32.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16_state")
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def period_of(pattern: str) -> str:
    """The shortest string whose repeats give ``pattern``."""
    n = len(pattern)
    return next(pattern[:p] for p in range(1, n + 1)
                if n % p == 0 and pattern[:p] * (n // p) == pattern)


def sizes_of(config: dict) -> dict:
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != int(config["num_hidden_layers"]) \
            or set(pattern) - {MAMBA, ATTENTION, EXPERTS}:
        raise ValueError("hybrid_override_pattern is not one of M, *, E for "
                         "each of num_hidden_layers")
    if int(config["mamba_num_heads"]) * int(config["mamba_head_dim"]) \
            != int(config["expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba heads x head_dim is not expand x hidden_size")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("a group limit on the router is not written down "
                         "here")
    published = config.get("published", {})
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "pattern": pattern,
            "layers": len(pattern),
            "mamba_layers": pattern.count(MAMBA),
            "attention_layers": pattern.count(ATTENTION),
            "expert_layers": pattern.count(EXPERTS),
            "mamba_heads": int(config["mamba_num_heads"]),
            "mamba_head_dim": int(config["mamba_head_dim"]),
            "ssm_groups": int(config["n_groups"]),
            "ssm_state": int(config["ssm_state_size"]),
            "conv_kernel": int(config["conv_kernel"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "router_experts": int(published.get("n_routed_experts",
                                                config["n_routed_experts"])),
            "experts_held": int(config["n_routed_experts"]),
            "expert_offset": int(config.get("expert_offset", 0)),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "latent": int(config["moe_latent_size"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["moe_shared_expert_intermediate_size"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "eps": float(config["norm_eps"]),
            # the longest sequence the served cache row holds
            "positions": int(config["serving"]["max_len"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (made outside ``jit``:
    a new seed is no new program)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(sz: dict, key):
    """The weights from ``key`` as they are served, layer ``l`` (in the
    model's order) from ``fold_in(key, l)``: matrices ``N(0, 1/fan_in)``, the
    embedding 0.02, the convolution taps 0.5 and its bias 0, ``A`` uniform in
    1..16 and ``dt`` log-uniform in 0.001..0.1 (the Mamba-2 paper's
    initialisation), ``D`` 1, the router's bias 0, norms 1; rounded to
    bfloat16, one layer (one expert) at a time. Traced: call under
    ``jax.jit`` with the key as an argument."""
    h = sz["hidden"]
    nh = sz["mamba_heads"]
    di = nh * sz["mamba_head_dim"]
    cc = di + 2 * sz["ssm_groups"] * sz["ssm_state"]
    period = period_of(sz["pattern"])

    def drawer(key):
        count = [0]

        def normal(std, *shape):
            count[0] += 1
            return (std * jax.random.normal(
                jax.random.fold_in(key, count[0]), shape, jnp.float32)
            ).astype(jnp.bfloat16)

        def uniform(lo, hi, *shape):
            count[0] += 1
            return jax.random.uniform(jax.random.fold_in(key, count[0]),
                                      shape, jnp.float32, lo, hi)

        return normal, uniform

    def dense(normal, i, o):
        return {"kernel": normal(math.sqrt(1.0 / i), i, o)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def mamba(layer):
        normal, uniform = drawer(jax.random.fold_in(key, layer))
        dt = jnp.exp(uniform(math.log(1e-3), math.log(0.1), nh))
        return {"norm": norm(h), "in_proj": dense(normal, h, di + cc + nh),
                "conv": {"weight": normal(0.5, sz["conv_kernel"], cc),
                         "bias": jnp.zeros((cc,), jnp.float32)},
                "a_log": jnp.log(uniform(1.0, 16.0, nh)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d": jnp.ones((nh,), jnp.float32), "y_norm": norm(di),
                "out": dense(normal, di, h)}

    def attention(layer):
        normal, _ = drawer(jax.random.fold_in(key, layer))
        q = sz["heads"] * sz["head_dim"]
        return {"norm": norm(h),
                "qkv": dense(normal, h, q + 2 * sz["kv_heads"]
                             * sz["head_dim"]),
                "out": dense(normal, q, h)}

    def experts(layer):
        k_layer = jax.random.fold_in(key, layer)
        normal, _ = drawer(k_layer)
        lat, f = sz["latent"], sz["expert_ffn"]

        def expert(e):          # its number in the whole model
            normal, _ = drawer(jax.random.fold_in(
                jax.random.fold_in(k_layer, 1 << 20), e))
            return (normal(math.sqrt(1.0 / lat), lat, f),
                    normal(math.sqrt(1.0 / f), f, lat))

        w1, w2 = jax.lax.map(expert, sz["expert_offset"]
                             + jnp.arange(sz["experts_held"]))
        return {"norm": norm(h),
                "router": dense(normal, h, sz["router_experts"]),
                "router_bias": jnp.zeros((sz["router_experts"],),
                                         jnp.float32),
                "down": dense(normal, h, lat), "w1": w1, "w2": w2,
                "up": dense(normal, lat, h),
                "shared_in": dense(normal, h, sz["shared_ffn"]),
                "shared_out": dense(normal, sz["shared_ffn"], h)}

    make = {MAMBA: mamba, ATTENTION: attention, EXPERTS: experts}
    first = len(period) * jnp.arange(sz["layers"] // len(period))
    normal, _ = drawer(jax.random.fold_in(key, sz["layers"]))
    return {
        "embedding": {"word": {"embedding": normal(0.02, sz["vocab"], h)}},
        "periods": [jax.lax.map(make[kind], first + j)
                    for j, kind in enumerate(period)],
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


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def recurrence(x, delta, a, b, c, state_dtype=jnp.float32):
    """Token by token: ``x`` (s, H, P), ``delta`` (s, H), ``a`` (H,), ``b``
    and ``c`` (s, H, N) (each head's group's row). Returns ``y`` (s, H, P)
    float32. In a lower ``state_dtype`` the state is kept, and every step
    computed, in it."""
    t = state_dtype

    def step(S, row):
        x, delta, b, c = (r.astype(t) for r in row)
        decay = jnp.exp(delta * a.astype(t))
        S = (decay[:, None, None] * S
             + (delta[:, None] * x)[:, :, None] * b[:, None, :]).astype(t)
        return S, jnp.einsum("hpn,hn->hp", S, c).astype(jnp.float32)

    S0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), t)
    return jax.lax.scan(step, S0, (x, delta, b, c))[1]


def mamba_layer(lp, sz, x, state_dtype=jnp.float32):
    s = x.shape[0]
    nh, p = sz["mamba_heads"], sz["mamba_head_dim"]
    g, n = sz["ssm_groups"], sz["ssm_state"]
    di = nh * p
    cc = di + 2 * g * n
    proj = _rms(lp["norm"]["weight"], x, sz["eps"]) @ lp["in_proj"]["kernel"]
    z, xbc, dt = proj[:, :di], proj[:, di:di + cc], proj[:, di + cc:]
    w = lp["conv"]["weight"]                       # (taps, channels)
    taps = w.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    # y_t = sum_j w[j] x_{t - taps + 1 + j}: the last tap is the newest
    xbc = _silu(sum(w[j] * padded[j:j + s] for j in range(taps))
                + lp["conv"]["bias"])
    xs = xbc[:, :di].reshape(s, nh, p)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), nh // g, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), nh // g, axis=1)
    delta = _softplus(dt + lp["dt_bias"])
    y = recurrence(xs, delta, -jnp.exp(lp["a_log"]), b, c, state_dtype)
    y = (y + lp["d"][:, None] * xs).reshape(s, di) * _silu(z)
    y = y.reshape(s, g, di // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + sz["eps"])
    return x + (y.reshape(s, di) * lp["y_norm"]["weight"]) \
        @ lp["out"]["kernel"]


def attention_layer(lp, sz, x):
    s = x.shape[0]
    nh, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    qkv = _rms(lp["norm"]["weight"], x, sz["eps"]) @ lp["qkv"]["kernel"]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):                      # one head at a time: (s, s) scores
        q, k, v = qkv
        scores = jnp.where(causal, q @ k.T / math.sqrt(hd), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v

    def heads(t, repeat):
        t = t.reshape(s, -1, hd).transpose(1, 0, 2)
        return jnp.repeat(t, repeat, axis=0)

    ctx = jax.lax.map(head, (heads(qkv[:, :nh * hd], 1),
                             heads(qkv[:, nh * hd:(nh + kv) * hd], nh // kv),
                             heads(qkv[:, (nh + kv) * hd:], nh // kv)))
    return x + ctx.transpose(1, 0, 2).reshape(s, nh * hd) \
        @ lp["out"]["kernel"]


def route(lp, sz, u):
    """(chosen (s, k), dense weights (s, router_experts)): float32."""
    scores = jax.nn.sigmoid(u @ lp["router"]["kernel"].astype(jnp.float32))
    k = sz["experts_per_token"]
    chosen = jnp.argsort(-(scores + lp["router_bias"]), axis=-1,
                         stable=True)[:, :k]
    picked = jnp.take_along_axis(scores, chosen, -1)
    w = sz["routed_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(w)
    return chosen, dense


def expert_layer(lp, sz, x):
    """``lp`` as served (bfloat16): one expert at a time is made float32.
    Returns ``(x', chosen (s, k))``."""
    outer = _f32({k: v for k, v in lp.items() if k not in ("w1", "w2")})
    u = _rms(outer["norm"]["weight"], x, sz["eps"])
    chosen, weights = route(outer, sz, u)
    latent = u @ outer["down"]["kernel"]
    mine = jax.lax.dynamic_slice_in_dim(
        weights, sz["expert_offset"], sz["experts_held"], axis=1)

    def one(total, expert):
        w1, w2, w = expert
        mid = jnp.square(jnp.maximum(latent @ w1.astype(jnp.float32), 0.0))
        return total + w[:, None] * (mid @ w2.astype(jnp.float32)), None

    routed = jax.lax.scan(one, jnp.zeros_like(latent),
                          (lp["w1"], lp["w2"], mine.T))[0]
    shared = jnp.square(jnp.maximum(
        u @ outer["shared_in"]["kernel"], 0.0)) @ outer["shared_out"]["kernel"]
    return x + routed @ outer["up"]["kernel"] + shared, chosen


def hidden_states(params, sz: dict, ids, precision="float32"):
    """(seq,) token ids -> ((seq, hidden) before the final norm, the experts
    each expert layer's router chose (expert layers, seq, k)). ``params`` as
    served (bfloat16); one layer at a time is made float32."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    state_dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
    x = params["embedding"]["word"]["embedding"][ids].astype(jnp.float32)
    kinds = period_of(sz["pattern"])

    def period(x, pp):
        routes = []
        for kind, lp in zip(kinds, pp):
            if kind == MAMBA:
                x = mamba_layer(_f32(lp), sz, x, state_dtype)
            elif kind == ATTENTION:
                x = attention_layer(_f32(lp), sz, x)
            else:
                x, chosen = expert_layer(lp, sz, x)
                routes.append(chosen)
        return x, jnp.stack(routes) if routes else jnp.zeros((0,), jnp.int32)

    x, routes = jax.lax.scan(period, x, params["periods"])
    return x, routes.reshape(-1, *routes.shape[2:])


def logits_at(params, sz: dict, ids, positions, precision="float32"):
    """Logits at ``positions`` of ``ids`` over the vocabulary kept."""
    hid = hidden_states(params, sz, ids, precision)[0][positions]
    hid = _rms(params["final_norm"]["weight"], hid, sz["eps"])
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
    below the reference's best at that position. One compiled program: every
    sequence is padded to ``positions`` (causal and recurrent, so a real
    position never sees the padding after it)."""

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
        ids = np.zeros((n_pos,), np.int32)
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
