"""Plain reference of BERT masked-LM pre-training: forward, loss, gradients
and AdamW in straightforward ``jax.numpy``, float32, matrix products at
``highest`` precision. No kernel, no mixed precision, no loss scale, no
donation tricks; it imports nothing of the program under test.

It follows Devlin et al. 2018 (post-LN encoder, learned positions, tied
decoder with a bias, MLM head = dense + GELU + LayerNorm) and Loshchilov &
Hutter's decoupled weight decay as Apex's ``FusedAdam(adam_w_mode=True)``
states it. Departures, each because the program under test does the same
and the comparison is to measure precision, not these:

- GELU is the tanh form (the published ``hidden_act: "gelu"`` is the erf
  form; listed under Open questions in PERF.md);
- no dropout (the program applies none without a dropout key);
- every position is predicted (label = input id, full-length mask), which is
  the traffic this benchmark feeds, not BERT's 15% masking;
- the weight tree has the layout the program's ``apply_bert`` consumes,
  because the same seeded weights are handed to both.

``precision="bfloat16"`` computes everything (weights, statistics, loss,
Adam state) in bfloat16: the control that ``correct`` has to refuse.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02          # initializer_range of the published config


def sizes_of(config: dict) -> dict:
    return {"vocab": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "ffn": int(config["intermediate_size"]),
            "positions": int(config["max_position_embeddings"]),
            "types": int(config["type_vocab_size"]),
            "eps": float(config["layer_norm_eps"])}


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62. Made outside ``jit``:
    the key is an argument of the programs that use it, so that a new seed
    is not a new program to compile."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(sz: dict, key):
    """The float32 weights from ``key`` (``seed_key(seed)``), traced: call
    under ``jax.jit`` with the key as an argument."""
    h, f = sz["hidden"], sz["ffn"]
    count = [0]

    def normal(*shape):
        count[0] += 1
        return INIT_STD * jax.random.truncated_normal(
            jax.random.fold_in(key, count[0]), -2.0, 2.0, shape, jnp.float32)

    def dense(i, o):
        return {"kernel": normal(i, o), "bias": jnp.zeros((o,), jnp.float32)}

    def ln():
        return {"weight": jnp.ones((h,), jnp.float32),
                "bias": jnp.zeros((h,), jnp.float32)}

    return {
        "embeddings": {"word": {"embedding": normal(sz["vocab"], h)},
                       "position": {"embedding": normal(sz["positions"], h)},
                       "token_type": {"embedding": normal(sz["types"], h)},
                       "layernorm": ln()},
        "encoder": [{"attention": {"qkv": dense(h, 3 * h),
                                   "out": dense(h, h), "layernorm": ln()},
                     "mlp": {"fc1": dense(h, f), "fc2": dense(f, h),
                             "layernorm": ln()}}
                    for _ in range(sz["layers"])],
        "mlm_head": {"transform": dense(h, h), "layernorm": ln(),
                     "bias": jnp.zeros((sz["vocab"],), jnp.float32)},
        "pooler": dense(h, h),
    }


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _ln(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps)) * p["weight"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def mlm_logits(params, sz: dict, ids, mask):
    """(rows, seq) ids and attention mask -> (rows, seq, vocab) logits."""
    b, s = ids.shape
    nh = sz["heads"]
    hd = sz["hidden"] // nh
    emb = params["embeddings"]
    x = emb["word"]["embedding"][ids] \
        + emb["position"]["embedding"][:s][None] \
        + emb["token_type"]["embedding"][0][None, None]
    x = _ln(emb["layernorm"], x, sz["eps"])
    bias = jnp.where(mask[:, None, None, :] != 0, 0.0,
                     -jnp.inf).astype(x.dtype)
    for layer in params["encoder"]:
        a = layer["attention"]
        qkv = _dense(a["qkv"], x).reshape(b, s, 3, nh, hd)
        q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / math.sqrt(hd) + bias
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bnqk,bnkd->bnqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, sz["hidden"])
        x = _ln(a["layernorm"], x + _dense(a["out"], ctx), sz["eps"])
        m = layer["mlp"]
        x = _ln(m["layernorm"],
                x + _dense(m["fc2"], _gelu(_dense(m["fc1"], x))), sz["eps"])
    head = params["mlm_head"]
    t = _ln(head["layernorm"], _gelu(_dense(head["transform"], x)),
            sz["eps"])
    return t @ emb["word"]["embedding"].T + head["bias"]


def loss_sum(params, sz: dict, ids, mask):
    """Sum over predicted positions of the cross entropy (label = id)."""
    logits = mlm_logits(params, sz, ids, mask)
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    picked = jnp.take_along_axis(logp, ids[..., None], -1)[..., 0]
    return -(picked * mask.astype(logp.dtype)).sum()


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_names(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def train(sz: dict, seed: int, batches, optimizer: dict, row_block: int,
          precision: str = "float32", devices=None) -> dict:
    """Follow the first ``len(batches)`` steps from the seeded weights.

    ``batches`` are host ``(ids, mask)`` pairs; each is taken ``row_block``
    rows at a time and the gradients summed, so that a batch the program
    holds in bfloat16 fits here in float32. Returns the step losses, the
    per-leaf norms of the first step's gradient and of the parameters'
    change over all the steps (numpy, in ``jax.tree.leaves`` order).

    With several ``devices`` (a cell on four chips, whose batch is four
    times as large) each block of rows is spread over them and the weights
    are copied to each: the same plain code, partitioned by the compiler, so
    that the check takes no longer than on one chip."""
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    lr, wd = float(optimizer["lr"]), float(optimizer["weight_decay"])
    b1, b2 = (float(x) for x in optimizer["betas"])
    eps = float(optimizer["eps"])

    key = seed_key(seed)

    @jax.jit
    def init(key):
        return jax.tree.map(lambda x: x.astype(dt), make_weights(sz, key))

    @jax.jit
    def block(params, ids, mask, denom):
        value, grads = jax.value_and_grad(
            lambda p: loss_sum(p, sz, ids, mask) / denom.astype(dt))(params)
        return value, grads

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))

    @jax.jit
    def adam(params, m, v, grads, t):
        def upd(p, m, v, g):
            m = (b1 * m + (1 - b1) * g).astype(dt)
            v = (b2 * v + (1 - b2) * g * g).astype(dt)
            u = (m / (1 - b1 ** t).astype(dt)) / (
                jnp.sqrt(v / (1 - b2 ** t).astype(dt)) + eps) + wd * p
            return (p - lr * u).astype(dt), m, v
        out = jax.tree.map(upd, params, m, v, grads)
        pick = lambda i: jax.tree.map(lambda p, o: o[i], params, out)
        return pick(0), pick(1), pick(2)

    norms = jax.jit(_leaf_norms)
    change = jax.jit(lambda p, key: _leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        p, jax.tree.map(lambda x: x.astype(dt), make_weights(sz, key)))))

    spread = lambda x: x
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("rows",))
        init = jax.jit(init.__wrapped__,
                       out_shardings=NamedSharding(mesh, P()))
        spread = lambda x: jax.device_put(x, NamedSharding(mesh, P("rows")))
        row_block *= len(devices)

    with jax.default_matmul_precision("highest"):
        params = init(key)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for t, (ids, mask) in enumerate(batches, start=1):
            denom = jnp.asarray(float(np.sum(mask)), jnp.float32)
            total, grads = 0.0, None
            for r in range(0, ids.shape[0], row_block):
                value, g = block(params, spread(ids[r:r + row_block]),
                                 spread(mask[r:r + row_block]), denom)
                grads = g if grads is None else add(grads, g)
                total = total + value.astype(jnp.float32)
            if grad_norms is None:
                grad_norms = np.asarray(norms(grads))
            params, m, v = adam(params, m, v, grads,
                                jnp.asarray(float(t), jnp.float32))
            losses.append(float(total))
        update_norms = np.asarray(change(params, key))
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "leaves": leaf_names(params)}
