"""Plain reference of the ``exaone_moe`` forward pass (K-EXAONE-236B-A23B):
attention layers of two kinds by the published pattern (``layer_types``): a
SLIDING layer's query ``i`` sees key ``j`` iff ``0 <= i - j < window``, a FULL
layer's every ``j <= i``; grouped-query attention (64 query heads over 8 K/V
heads of 128 as published); a dense SwiGLU in the leading ``dense`` layers of
``mlp_layer_types`` and a sparse expert layer after them; a final RMSNorm and
an untied head. Straightforward ``jax.numpy`` in float32 with matrix products
at ``highest`` precision: every layer keeps every position, the window is an
explicit band mask over the whole ``(queries, s)`` score matrix; no cache, no
ring, no paging, no sort, no grouped product, no kernel, no batching. It
imports nothing of the program under test.

*The one rounding the deployment states* is the reference's too: the
configuration's ``serving.cache_dtype`` says in what precision a layer's keys
and values are KEPT (bfloat16), so every layer's K and V rows are rounded to
it once, after the per-head norm and rotary, before anything attends to them.
Everything else is float32. Without it the distance between a sound program
and this file is the cache's rounding and nothing else, and a program that
computed everything in bfloat16 could not be told from one that computes as
the configuration states (PERF.md, section 6, PR 40).

*What the published config does not say* is the family's convention, as the
configuration file's ``assumed`` block states it (``sizes_of`` refuses a file
that states another): each sub-layer's RMSNorm on its OUTPUT, ``x <- x +
RMSNorm(f(x))``, none in front; an RMSNorm over each head's values of q and
of k after their projections, before rotary; rotary on the sliding layers
only; the window's ``sliding_window`` positions include the token itself.
Rotary is the default one over the whole head: pair ``i`` is ``(x[i], x[i +
64])``, turning at ``theta^(-2i/128)``.

*Expert layer*: the rule of ``reference/deepseek_v3.py`` with one group
(float32 sigmoid scores, a choice-only bias, the 8 largest, weights ``2.5 *
sc / sum``), IMPORTED from that file (``route``) with its small helpers; every
HELD expert in turn (a loop, each token's weight for it zero where it was not
chosen), the shared expert beside them. The reference is given the same share
of the model as the program: experts ``expert_offset .. + experts_held - 1``
of the router's ``router_experts``, and the vocabulary slice the configuration
keeps. The multi-token-prediction module is left out
(``num_nextn_predict_layers`` under ``reduced``).

The weights are served in bfloat16 (norms and the router's bias in float32),
rounded once, here; layer ``l`` is drawn from ``fold_in(key, l)`` and expert
``e`` (its number in the whole model) of it from ``fold_in(., e)``. The
forward upcasts ONE layer at a time (one expert at a time inside an expert
layer), takes the MLPs ``BLOCK`` rows at a time and attends one head and one
block of ``BLOCK`` queries at a time, so that 8192 + 3072 positions fit
beside the served weights.

The lower precisions the controls run, which ``correct`` has to refuse:
``"bfloat16_activations"`` (what the configuration states as float32 is
bfloat16: ONE bfloat16 term into every product, keys, values and the
attention's arithmetic in bfloat16; sums, norms, the residual stream and the
router's product stay float32) and ``"full_window"`` (float32, but every
layer attends causally with no band: what a program that forgot the window,
or kept rows the window had left, would compute).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness

_ds = harness.load_module("reference", "deepseek_v3")
seed_key, route = _ds.seed_key, _ds.route
_f32, _rms, _same, _swiglu = _ds._f32, _ds._rms, _ds._same, _ds._swiglu
ROUTER_BIAS_STD = _ds.ROUTER_BIAS_STD

PRECISIONS = ("float32", "bfloat16_activations", "full_window")
SLIDING = "sliding_attention"
# the conventions written down here, as the configuration file states them
ASSUMED = {"sublayer_norm": "output", "qk_norm": True,
           "rope_layers": "sliding", "window_counts_self": True}


def sizes_of(config: dict) -> dict:
    published = config.get("published", {})
    layers = int(config["num_hidden_layers"])
    kinds = tuple(config["layer_types"])
    mlps = tuple(config["mlp_layer_types"])
    dense = sum(1 for m in mlps if m == "dense")
    if len(kinds) != layers or len(mlps) != layers \
            or mlps != ("dense",) * dense + ("sparse",) * (layers - dense) \
            or config["scoring_func"] != "sigmoid" \
            or not config["norm_topk_prob"] \
            or config["rope_parameters"]["rope_type"] != "default" \
            or {k: config["assumed"][k] for k in ASSUMED} != ASSUMED \
            or any((w == 0) != (t != SLIDING) or w not in (
                0, int(config["sliding_window"]))
                for w, t in zip(config["sliding_windows"], kinds)):
        raise ValueError("only the published layer, routing and rotary rules "
                         "are written down here")
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "layers": layers, "dense_layers": dense,
            "expert_layers": layers - dense,
            "layer_types": kinds,
            "full_layers": sum(t != SLIDING for t in kinds),
            "window_layers": sum(t == SLIDING for t in kinds),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "sliding_window": int(config["sliding_window"]),
            "window": int(config["sliding_window"]),
            "dense_ffn": int(config["intermediate_size"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["num_shared_experts"])
            * int(config["moe_intermediate_size"]),
            "router_experts": int(published.get("num_experts",
                                                config["num_experts"])),
            "experts_held": int(config["num_experts"]),
            "expert_offset": int(config.get("expert_offset", 0)),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "n_group": int(config["n_group"]),
            "topk_group": int(config["topk_group"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_parameters"]["rope_theta"]),
            # what the served caches hold of a token in a layer (K and V
            # apart), and the pages of a slot's cycle in a window layer
            "row_width": int(config["num_key_value_heads"])
            * int(config["head_dim"]),
            "page_size": int(config["serving"]["page_size"]),
            "ring_pages": int(config["serving"]["ring_pages"]),
            # the precision a layer's K and V rows are kept in
            "cache_dtype": str(config["serving"]["cache_dtype"]),
            # the longest sequence the served cache row holds
            "positions": int(config["serving"]["max_len"])}


def make_weights(sz: dict, key):
    """The weights from ``key`` as they are served, layer ``l`` (in the
    model's order) from ``fold_in(key, l)``: matrices ``N(0, 1/fan_in)``, the
    embedding 0.02, the router's bias ``N(0, ROUTER_BIAS_STD)`` in float32,
    norms 1; rounded to bfloat16, one layer (one expert) at a time. Traced:
    call under ``jax.jit`` with the key as an argument."""
    h, hd = sz["hidden"], sz["head_dim"]
    q_width = sz["heads"] * hd

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
        return {"norm": norm(h), "q_norm": norm(hd), "k_norm": norm(hd),
                "qkv": dense(normal, h, q_width + 2 * sz["row_width"]),
                "out": dense(normal, q_width, h)}

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

BLOCK = 1024        # rows of an MLP pass, queries of an attention pass


def _blocks(f, x):
    """``f`` over ``x`` (s, ...) ``BLOCK`` rows at a time where ``s`` is a
    whole number of them."""
    s = x.shape[0]
    if s <= BLOCK or s % BLOCK:
        return f(x)
    out = jax.lax.map(f, x.reshape(s // BLOCK, BLOCK, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def _sublayer(w, sz, x, f):
    """``x + RMSNorm(f(x))``."""
    return x + _rms(w, f(x), sz["eps"])


def rope(sz, x, pos):
    """``x`` (s, heads, d) at positions ``pos`` (s,): pair ``i`` is ``(x[i],
    x[i + d / 2])``."""
    d = x.shape[-1]
    inv_freq = (sz["rope_theta"] ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)
    theta = pos.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(theta)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(theta)] * 2, -1)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-b, a], -1) * sin


def _bf16(t):
    """Float32 ``t`` rounded to bfloat16's 8 bits of mantissa, still float32.
    Cut with ``lax.reduce_precision``, which the compiler has to honour: a
    round trip through ``astype`` it folds away on the TPU (it allows itself
    "excess precision"), and then neither the cache's rounding nor the
    lower-precision control's is in the program that runs (PERF.md, section
    6, PR 40)."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def _kept(sz):
    """What keeping a K or V row does to it: rounds it to the stated
    ``cache_dtype`` (and hands it back as float32)."""
    return {"bfloat16": _bf16, "float32": _same}[sz["cache_dtype"]]


def attention_layer(lp, sz, x, sliding, cut=_same, into=_same, banded=True):
    """``lp`` float32; ``sliding``: the layer's kind; ``banded``: a sliding
    layer's band is applied (the ``full_window`` control leaves it out and
    nothing else). K and V rows are rounded to the stated ``cache_dtype`` in
    every precision. The controls' arithmetic: ``cut`` rounds queries, keys,
    values and everything inside the attention, ``into`` the inputs of the
    projections around it."""
    windowed = sliding and banded
    kept = _kept(sz)
    s, nh, nkv, hd = x.shape[0], sz["heads"], sz["kv_heads"], sz["head_dim"]
    pos = jnp.arange(s)

    def attend(u):
        qkv = into(u) @ lp["qkv"]["kernel"]
        q = qkv[:, :nh * hd].reshape(s, nh, hd)
        k = qkv[:, nh * hd:(nh + nkv) * hd].reshape(s, nkv, hd)
        v = qkv[:, (nh + nkv) * hd:].reshape(s, nkv, hd)
        q = _rms(lp["q_norm"]["weight"], q, sz["eps"])
        k = _rms(lp["k_norm"]["weight"], k, sz["eps"])
        if sliding:
            q, k = rope(sz, q, pos), rope(sz, k, pos)
        k, v = cut(kept(k)).transpose(1, 0, 2), cut(kept(v)).transpose(1, 0, 2)
        q = cut(q).transpose(1, 0, 2).reshape(nkv, nh // nkv, s, hd)

        def group(args):            # one K/V head, its query heads in turn
            q_heads, k, v = args

            def head(q):            # one block of queries: (BLOCK, s) scores
                def block(q_at):
                    q, at = q_at
                    seen = at[:, None] >= pos[None, :]
                    if windowed:
                        seen &= at[:, None] - pos[None, :] < sz["window"]
                    scores = cut(q @ k.T * hd ** -0.5)
                    p = cut(jax.nn.softmax(
                        jnp.where(seen, scores, -jnp.inf), -1))
                    return cut(p @ v)

                if s <= BLOCK or s % BLOCK:
                    return block((q, pos))
                return jax.lax.map(block, (
                    q.reshape(-1, BLOCK, hd), pos.reshape(-1, BLOCK))
                ).reshape(s, hd)

            return jax.lax.map(head, q_heads)

        ctx = jax.lax.map(group, (q, k, v)).reshape(nh, s, hd)
        return into(ctx.transpose(1, 0, 2).reshape(s, -1)) \
            @ lp["out"]["kernel"]

    return _sublayer(lp["norm"]["weight"], sz, x, attend)


def dense_mlp(lp, sz, x, into=_same):
    def mlp(u):
        return _blocks(lambda u: into(_swiglu(into(u)
                                              @ lp["gate_up"]["kernel"]))
                       @ lp["down"]["kernel"], u)

    return _sublayer(lp["mlp_norm"]["weight"], sz, x, mlp)


def experts_of(lp, sz, u, into=_same):
    """The expert sub-layer's function of its input rows ``u`` (s, hidden):
    ``(the held experts' part, the shared expert's, chosen (s, k))``. ``lp``
    as served (bfloat16): one expert at a time is made float32. The router
    reads the rows as they are, whatever ``into`` makes of the experts'
    inputs."""
    outer = _f32({k: v for k, v in lp.items()
                  if k not in ("attn", "w_gate_up", "w_down")})
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
    shared = into(_swiglu(u @ outer["shared_gate_up"]["kernel"])) \
        @ outer["shared_down"]["kernel"]
    return routed, shared, chosen


def expert_mlp(lp, sz, x, into=_same):
    """Returns ``(x', chosen (s, k))``."""
    chosen = []

    def mlp(u):
        routed, shared, picked = experts_of(lp, sz, u, into)
        chosen.append(picked)
        return routed + shared

    x = _sublayer(lp["mlp_norm"]["weight"].astype(jnp.float32), sz, x, mlp)
    return x, chosen[0]


def hidden_states(params, sz: dict, ids, precision="float32"):
    """(seq,) token ids -> ((seq, hidden) before the final norm, the experts
    each expert layer's router chose (expert layers, seq, k)). ``params`` as
    served (bfloat16); one layer at a time is made float32."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    low = precision == "bfloat16_activations"
    cut = into = _bf16 if low else _same
    banded = precision != "full_window"
    x = params["embedding"]["word"]["embedding"][ids].astype(jnp.float32)
    chosen = []
    for layer in range(sz["layers"]):
        sliding = sz["layer_types"][layer] == SLIDING
        at = layer - sz["dense_layers"]
        lp = params["dense"][layer] if at < 0 else jax.tree.map(
            lambda w: w[at], params["moe"])
        x = attention_layer(_f32(lp["attn"]), sz, x, sliding, cut, into,
                            banded)
        if at < 0:
            x = dense_mlp(_f32({k: v for k, v in lp.items() if k != "attn"}),
                          sz, x, into)
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
