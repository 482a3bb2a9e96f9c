"""Plain reference of the ``olmo_hybrid`` forward pass: periods of three Gated
DeltaNet layers (Yang, Kautz, Hatamizadeh, arXiv:2412.06464) and one layer of
full causal softmax attention; RMSNorm on each sub-layer's OUTPUT before the
residual add (``h = x + norm(mixer(x))``, ``h = h + norm(mlp(h))``); SwiGLU;
no biases; an untied head. Straightforward ``jax.numpy`` in float32 with
matrix products at ``highest`` precision; the recurrence

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t

runs one token at a time in a ``lax.scan``: no chunks, no kernel, no cache, no
paging, no batching. It imports nothing of the program under test.

What the published config does not say is set by the Olmo 2 / 3 convention
and listed under ``assumed`` in the configuration file; program and reference
follow the same list: the norm on the sub-layer's output; q and k of the full
layers normed over their whole projected width; ``rope_theta`` null read as
no rotary embedding; no convolution bias; ``l2norm`` with 1e-6 under the
root; the recurrent state in float32.

Departures: the projections are stored fused, in the order the program's
engine consumes (``in_proj`` = ``[q | k | v | gate]``, ``ab_proj`` = ``[a |
b]``, ``qkv`` = ``[Q | K | V]``, ``gate_up`` = ``[gate | up]``): with seeded
random weights this only names the columns. Layers are stacked by their
place in the period: ``periods.linear`` is a list of three trees and
``periods.full`` one, every leaf leading with ``(periods,)``.

The weights are served in bfloat16 (norms, ``A_log`` and ``dt_bias`` in
float32), so the seeded weights are rounded to bfloat16 once, here, and both
sides get those values. At the published widths the float32 copy of the cut
model is 16.4 GB, more than the chip: layer ``l`` is drawn from
``fold_in(key, l)`` and rounded before the next is drawn (``lax.map``), and
the forward upcasts ONE layer at a time inside its scan over the layers, so
no float32 copy of the model ever exists; ``Scorer`` instances of one seed
share the one bfloat16 tree.

The lower precision the control runs (``Scorer(..., precision=
"bfloat16_state")``), which ``correct`` has to refuse: the recurrent state is
kept in bfloat16 and the scan's arithmetic is done in bfloat16 (its inputs
q, k, v, alpha and beta rounded to it), everything else float32.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16_state")
_L2_EPS = 1e-6


def sizes_of(config: dict) -> dict:
    types = config["layer_types"]
    n_lin = types.index("full_attention")
    period = ["linear_attention"] * n_lin + ["full_attention"]
    if len(types) != int(config["num_hidden_layers"]) \
            or types != period * (len(types) // len(period)):
        raise ValueError("layer_types is not whole periods of "
                         f"{period} over num_hidden_layers")
    heads = int(config["num_attention_heads"])
    lin_heads = int(config["linear_num_value_heads"])
    if int(config["num_key_value_heads"]) != heads \
            or int(config["linear_num_key_heads"]) != lin_heads:
        raise ValueError("fewer K/V than query heads is not written down "
                         "here")
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "layers": len(types),
            "periods": len(types) // len(period),
            "linear_per_period": n_lin,
            "linear_layers": n_lin * (len(types) // len(period)),
            "full_layers": len(types) // len(period),
            "heads": heads,
            "head_dim": int(config["hidden_size"]) // heads,
            "ffn": int(config["intermediate_size"]),
            "linear_heads": lin_heads,
            "linear_key_dim": int(config["linear_key_head_dim"]),
            "linear_value_dim": int(config["linear_value_head_dim"]),
            "conv_kernel": int(config["linear_conv_kernel_dim"]),
            "beta_scale": 2.0 if config["linear_allow_neg_eigval"] else 1.0,
            "eps": float(config["rms_norm_eps"]),
            # the longest sequence the served cache row holds
            "positions": int(config["serving"]["max_len"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (made outside ``jit``:
    a new seed is no new program)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(sz: dict, key):
    """The weights from ``key`` as they are served, layer ``l`` (in the
    model's order) from ``fold_in(key, l)``: matrices ``N(0, 1/fan_in)``
    (``w_a``, ``w_b`` a tenth of that, so that ``A_log`` and ``dt_bias`` set
    the heads' time constants), the embedding 0.02, the convolution taps 0.5,
    ``A`` uniform in 0..16 and ``dt`` log-uniform in 0.001..0.1 (the paper's
    initialisation), norms 1; rounded to bfloat16, one layer at a time.
    Traced: call under ``jax.jit`` with the key as an argument."""
    h, f = sz["hidden"], sz["ffn"]
    nh, dk, dv = sz["linear_heads"], sz["linear_key_dim"], \
        sz["linear_value_dim"]
    chan = nh * (2 * dk + dv)
    per = sz["linear_per_period"] + 1

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

    def dense(normal, i, o, gain=1.0):
        return {"kernel": normal(gain * math.sqrt(1.0 / i), i, o)}

    def norm(width):
        return {"weight": jnp.ones((width,), jnp.float32)}

    def mlp(normal):
        return {"norm2": norm(h), "gate_up": dense(normal, h, 2 * f),
                "down": dense(normal, f, h)}

    def linear_layer(layer):
        normal, uniform = drawer(jax.random.fold_in(key, layer))
        dt = jnp.exp(uniform(math.log(1e-3), math.log(0.1), nh))
        return {"in_proj": dense(normal, h, chan + nh * dv),
                "ab_proj": dense(normal, h, 2 * nh, gain=0.1),
                "conv": {"weight": normal(0.5, sz["conv_kernel"], chan)},
                "a_log": jnp.log(uniform(0.0, 16.0, nh)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": norm(dv), "out": dense(normal, nh * dv, h),
                "norm1": norm(h), **mlp(normal)}

    def full_layer(p):
        normal, _ = drawer(jax.random.fold_in(key, p * per + per - 1))
        return {"qkv": dense(normal, h, 3 * h), "q_norm": norm(h),
                "k_norm": norm(h), "out": dense(normal, h, h),
                "norm1": norm(h), **mlp(normal)}

    first = per * jnp.arange(sz["periods"])     # each period's first layer
    normal, _ = drawer(jax.random.fold_in(key, sz["layers"]))
    return {
        "embedding": {"word": {"embedding": normal(0.02, sz["vocab"], h)}},
        "periods": {
            "linear": [jax.lax.map(linear_layer, first + j)
                       for j in range(sz["linear_per_period"])],
            "full": jax.lax.map(full_layer, jnp.arange(sz["periods"]))},
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


def _mlp(lp, x, eps):
    gate, up = jnp.split(x @ lp["gate_up"]["kernel"], 2, axis=-1)
    return _rms(lp["norm2"]["weight"],
                (_silu(gate) * up) @ lp["down"]["kernel"], eps)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def recurrence(q, k, v, alpha, beta, state_dtype=jnp.float32):
    """Token by token: ``q``, ``k`` (s, H, d_k), ``v`` (s, H, d_v),
    ``alpha``, ``beta`` (s, H). Returns ``o`` (s, H, d_v) float32. In a lower
    ``state_dtype`` the state is kept, and every step computed, in it."""
    t = state_dtype

    def step(S, row):
        q, k, v, a, b = (r.astype(t) for r in row)
        S = a[:, None, None] * S
        # (I - b k k^T) S + b k v^T = S + k (b (v - S^T k))^T
        r = b[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = (S + k[:, :, None] * r[:, None, :]).astype(t)
        return S, jnp.einsum("hkv,hk->hv", S, q).astype(jnp.float32)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), t)
    return jax.lax.scan(step, S0, (q, k, v, alpha, beta))[1]


def linear_layer(lp, sz, x, state_dtype):
    s = x.shape[0]
    nh, dk, dv = sz["linear_heads"], sz["linear_key_dim"], \
        sz["linear_value_dim"]
    chan = nh * (2 * dk + dv)
    proj = x @ lp["in_proj"]["kernel"]
    w = lp["conv"]["weight"]                       # (taps, channels)
    taps = w.shape[0]
    padded = jnp.pad(proj[:, :chan], ((taps - 1, 0), (0, 0)))
    # y_t = sum_j w[j] x_{t - taps + 1 + j}: the last tap is the newest
    y = _silu(sum(w[j] * padded[j:j + s] for j in range(taps)))
    q = _l2norm(y[:, :nh * dk].reshape(s, nh, dk)) / math.sqrt(dk)
    k = _l2norm(y[:, nh * dk:2 * nh * dk].reshape(s, nh, dk))
    v = y[:, 2 * nh * dk:].reshape(s, nh, dv)
    a, b = jnp.split(x @ lp["ab_proj"]["kernel"], 2, axis=-1)
    alpha = jnp.exp(-jnp.exp(lp["a_log"]) * _softplus(a + lp["dt_bias"]))
    beta = sz["beta_scale"] / (1.0 + jnp.exp(-b))
    o = recurrence(q, k, v, alpha, beta, state_dtype)
    o = _rms(lp["o_norm"]["weight"], o, sz["eps"]).reshape(s, nh * dv)
    y = (o * _silu(proj[:, chan:])) @ lp["out"]["kernel"]
    x = x + _rms(lp["norm1"]["weight"], y, sz["eps"])
    return x + _mlp(lp, x, sz["eps"])


def full_layer(lp, sz, x):
    s = x.shape[0]
    nh, hd = sz["heads"], sz["head_dim"]
    q, k, v = jnp.split(x @ lp["qkv"]["kernel"], 3, axis=-1)
    q = _rms(lp["q_norm"]["weight"], q, sz["eps"])
    k = _rms(lp["k_norm"]["weight"], k, sz["eps"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):                      # one head at a time: (s, s) scores
        q, k, v = qkv
        scores = jnp.where(causal, q @ k.T / math.sqrt(hd), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v

    def heads(t):
        return t.reshape(s, nh, hd).transpose(1, 0, 2)

    ctx = jax.lax.map(head, (heads(q), heads(k), heads(v)))
    y = ctx.transpose(1, 0, 2).reshape(s, nh * hd) @ lp["out"]["kernel"]
    x = x + _rms(lp["norm1"]["weight"], y, sz["eps"])
    return x + _mlp(lp, x, sz["eps"])


def hidden_states(params, sz: dict, ids, precision="float32"):
    """(seq,) token ids -> (seq, hidden) before the final norm. ``params``
    as served (bfloat16); one layer at a time is made float32."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    state_dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
    x = params["embedding"]["word"]["embedding"][ids].astype(jnp.float32)

    def period(x, pp):
        for lp in pp["linear"]:
            x = linear_layer(_f32(lp), sz, x, state_dtype)
        return full_layer(_f32(pp["full"]), sz, x), None

    return jax.lax.scan(period, x, params["periods"])[0]


def logits_at(params, sz: dict, ids, positions, precision="float32"):
    """Logits at ``positions`` of ``ids`` over the whole vocabulary."""
    hid = hidden_states(params, sz, ids, precision)[positions]
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

    def gaps(self, prompt, served, judged=None):
        """(gaps, this model's own best tokens) at the positions that
        produced ``served``. The tokens judged are the served ones, or
        ``judged`` (the control: another model's best tokens at the same
        positions of the same teacher-forced sequence)."""
        n_pos = self.sz["positions"]
        seq = list(prompt) + list(served)
        if len(seq) > n_pos:
            raise ValueError(f"{len(seq)} tokens pass the {n_pos} positions "
                             "of a cache row")
        ids = np.zeros((n_pos,), np.int32)
        ids[:len(seq)] = seq
        out = np.zeros((n_pos,), np.int32)
        out[:len(served)] = served if judged is None else judged
        with jax.default_matmul_precision("highest"):
            gaps, top = self._score(self.params, jnp.asarray(ids),
                                    jnp.asarray(out),
                                    jnp.int32(len(prompt)),
                                    jnp.int32(len(served)))
        return (np.asarray(gaps)[:len(served)],
                np.asarray(top)[:len(served)])
