"""Plain reference of the GPT-2 forward pass (Radford et al. 2019): pre-LN
decoder, learned positions, tanh GELU (``gelu_new``), causal attention, the
output head tied to the token embedding. Straightforward ``jax.numpy`` in
float32 with matrix products at ``highest`` precision; no cache, no paging,
no batching, no kernel; it imports nothing of the program under test.

Departure: the fused ``c_attn`` kernel is read head-major (``[head0: q k v |
head1: q k v | ...]``) where the published checkpoint stores ``[Q | K | V]``:
with seeded random weights this only names the columns, and it is the
layout the program's engine consumes, because the same seeded weights are
handed to both. The vocabulary is padded to ``padded_vocab_size`` rows (an
``assumed`` size); prompts only use the published 50257, but the served
tokens range over all the rows.

The weights are served in bfloat16 (LayerNorm parameters in float32), so the
seeded weights are rounded to bfloat16 once, here, and both sides get those
values: the program as bfloat16, the reference as float32.

Two lower precisions, for the control that ``correct`` has to refuse
(``Scorer(..., precision=...)``):

``"fp8"``   the step below bfloat16: every matrix (per output channel) and
            every matrix product's input activations (per token) scaled to
            the range of float8 e4m3 and rounded to it; sums stay float32.
``"int8w"`` weight-only int8 (per output channel, symmetric absmax), the
            program's own ``quant`` tier. Read beside the other: on the chip
            it lands about 2x the bfloat16 program's gap, too near to be
            refused by a served-token statistic (PERF.md, section 7).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def sizes_of(config: dict) -> dict:
    return {"vocab": int(config["vocab_size"]),
            "padded_vocab": int(config["assumed"]["padded_vocab_size"]),
            "hidden": int(config["n_embd"]),
            "layers": int(config["n_layer"]),
            "heads": int(config["n_head"]),
            "ffn": 4 * int(config["n_embd"]),
            "positions": int(config["n_positions"]),
            "eps": float(config["layer_norm_epsilon"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62. Made outside ``jit``:
    the key is an argument of the programs that use it, so that a new seed
    is not a new program to compile."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(sz: dict, key):
    """The weights from ``key`` (``seed_key(seed)``) as they are served:
    matrices and biases rounded to bfloat16, LayerNorm parameters float32;
    layers stacked on a leading axis. Traced: call under ``jax.jit`` with the
    key as an argument."""
    h, f, n = sz["hidden"], sz["ffn"], sz["layers"]
    count = [0]

    def normal(std, *shape):
        count[0] += 1
        return (std * jax.random.normal(jax.random.fold_in(key, count[0]),
                                        shape, jnp.float32)
                ).astype(jnp.bfloat16)

    def dense(i, o):
        return {"kernel": normal(math.sqrt(1.0 / i), n, i, o),
                "bias": jnp.zeros((n, o), jnp.bfloat16)}

    def ln(*lead):
        return {"weight": jnp.ones(lead + (h,), jnp.float32),
                "bias": jnp.zeros(lead + (h,), jnp.float32)}

    return {
        "embedding": {
            "word": {"embedding": normal(0.02, sz["padded_vocab"], h)},
            "position": {"embedding": normal(0.02, sz["positions"], h)}},
        "layers": {"ln1": ln(n), "qkv": dense(h, 3 * h), "out": dense(h, h),
                   "ln2": ln(n), "fc1": dense(h, f), "fc2": dense(f, h)},
        "final_ln": ln(),
    }


PRECISIONS = ("float32", "int8w", "fp8")


def _int8(w, axis):
    """Round to 8 bits along ``axis`` (symmetric, absmax)."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    return jnp.round(w / jnp.where(scale == 0, 1.0, scale)) * scale


def _fp8(x, axis):
    """Scale to float8 e4m3's range along ``axis`` and round to it."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def reference_weights(sz: dict, key, precision: str = "float32"):
    """The served values as float32; in a lower ``precision`` every matrix
    rounded per output channel (the embedding per token row)."""
    tree = jax.tree.map(lambda x: x.astype(jnp.float32),
                        make_weights(sz, key))
    if precision not in PRECISIONS:
        raise ValueError(precision)
    if precision != "float32":
        rnd = _int8 if precision == "int8w" else _fp8
        for name in ("qkv", "out", "fc1", "fc2"):
            kernel = tree["layers"][name]["kernel"]
            tree["layers"][name]["kernel"] = rnd(kernel, -2)
        word = tree["embedding"]["word"]
        word["embedding"] = rnd(word["embedding"], -1)
    return tree


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _ln(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["weight"] + p["bias"]


def hidden_states(params, sz: dict, ids, act=lambda x: x):
    """(seq,) token ids -> (seq, hidden) after the final LayerNorm. ``act``
    rounds a matrix product's input activations (the fp8 control)."""
    s = ids.shape[0]
    nh = sz["heads"]
    hd = sz["hidden"] // nh
    x = params["embedding"]["word"]["embedding"][ids] \
        + params["embedding"]["position"]["embedding"][:s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        q_k_v = act(_ln(lp["ln1"], x, sz["eps"])) @ lp["qkv"]["kernel"] \
            + lp["qkv"]["bias"]
        qkv = q_k_v.reshape(s, nh, 3, hd)
        q, k, v = (qkv[:, :, j].transpose(1, 0, 2) for j in range(3))
        scores = jnp.einsum("nqd,nkd->nqk", q, k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        ctx = jnp.einsum("nqk,nkd->nqd", probs, v)
        ctx = ctx.transpose(1, 0, 2).reshape(s, sz["hidden"])
        x = x + act(ctx) @ lp["out"]["kernel"] + lp["out"]["bias"]
        y = _gelu(act(_ln(lp["ln2"], x, sz["eps"])) @ lp["fc1"]["kernel"]
                  + lp["fc1"]["bias"])
        return x + act(y) @ lp["fc2"]["kernel"] + lp["fc2"]["bias"], None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _ln(params["final_ln"], x, sz["eps"])


def logits_at(params, sz: dict, ids, positions, precision="float32"):
    """Logits at ``positions`` of ``ids`` over every row of the embedding,
    the padding rows too: the program's head ranges over all of them and does
    serve their ids (about one token in a thousand with random weights;
    PERF.md, Open questions), so the reference has to be able to judge
    them."""
    act = (lambda x: _fp8(x, -1)) if precision == "fp8" else (lambda x: x)
    hid = act(hidden_states(params, sz, ids, act)[positions])
    return hid @ params["embedding"]["word"]["embedding"].T


class Scorer:
    """Scores served tokens against the reference: for a prompt and the
    tokens served after it, the gap by which each served token's logit lies
    below the reference's best at that position. One compiled program: every
    sequence is padded to ``positions`` (causal, so the padding is never
    seen by a real position)."""

    def __init__(self, sz: dict, seed: int, precision: str = "float32"):
        self.sz = sz
        with jax.default_matmul_precision("highest"):
            self.params = jax.jit(
                lambda key: reference_weights(sz, key, precision))(
                seed_key(seed))

        def score(params, ids, served, first, n):
            # logits at the positions that predicted the served tokens:
            # first-1 .. first+n-2, padded to a fixed count
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
            raise ValueError(f"{len(seq)} tokens pass the model's {n_pos} "
                             "positions")
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
