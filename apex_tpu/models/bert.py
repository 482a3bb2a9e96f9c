"""BERT encoder (flagship / north-star model).

The reference has no in-tree BERT; its test GPT/BERT live in
``apex/transformer/testing/standalone_bert.py`` and the north-star workload
is BERT-Large pretrain with amp O2 + FusedAdam + FusedLayerNorm. This is a
functional BERT built on the package's own accelerants:

- ``apex_tpu.normalization.fused_layer_norm_affine`` for every LayerNorm;
- attention through ``apex_tpu.transformer.functional``'s fused kernels
  (``flash_attention_packed``: no score array reaches HBM at s128 / s256);
- params are a nested dict so the AMP O2 cast (`keep_batchnorm_fp32` treats
  "layernorm" paths as norms) and TP sharding specs apply mechanically.

Layout: activations are (batch, seq, hidden); attention is
(batch, heads, seq, seq) — MXU-friendly, all dims static.
"""

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from apex_tpu.amp.autocast import cast_args
from apex_tpu.models import layers as L
from apex_tpu.normalization import fused_layer_norm_affine
from apex_tpu.utils.profiler import region


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    hidden_dropout: float = 0.1     # applied only when rng given
    attention_dropout: float = 0.1
    # fused attention (ref: apex/contrib multihead_attn / fmha): at a
    # sequence of 128 or 256 the whole-sequence fmha kernel pair on the
    # packed projection, above 256 the tiled flash kernels, else XLA;
    # False falls back to materialized scores + fused softmax kernel
    fused_attention: bool = True
    # jax.checkpoint each encoder layer: one hidden state per layer of
    # live memory plus recompute — unlocks per-chip batch 32 for
    # BERT-Large amp O2 on v5e (b=32 OOMs without it). Ref analogue:
    # tensor_parallel/random.py::CheckpointFunction discipline.
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def bert_large() -> BertConfig:
    return BertConfig()


def bert_base() -> BertConfig:
    return BertConfig(hidden_size=768, num_layers=12, num_heads=12,
                      intermediate_size=3072)


def bert_tiny() -> BertConfig:  # for tests / dryruns
    return BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                      num_heads=4, intermediate_size=256,
                      max_position_embeddings=128)


def init_bert(key: jax.Array, cfg: BertConfig,
              dtype=jnp.float32) -> Dict[str, Any]:
    keys = iter(jax.random.split(key, 6 + 8 * cfg.num_layers))
    h, i = cfg.hidden_size, cfg.intermediate_size
    params: Dict[str, Any] = {
        "embeddings": {
            "word": L.init_embedding(next(keys), cfg.vocab_size, h, dtype),
            "position": L.init_embedding(
                next(keys), cfg.max_position_embeddings, h, dtype),
            "token_type": L.init_embedding(
                next(keys), cfg.type_vocab_size, h, dtype),
            "layernorm": {"weight": jnp.ones((h,), jnp.float32),
                          "bias": jnp.zeros((h,), jnp.float32)},
        },
        "encoder": [],
        "mlm_head": {
            "transform": L.init_dense(next(keys), h, h, dtype=dtype),
            "layernorm": {"weight": jnp.ones((h,), jnp.float32),
                          "bias": jnp.zeros((h,), jnp.float32)},
            # decoder ties to the word embedding; only a bias is stored
            "bias": jnp.zeros((cfg.vocab_size,), dtype),
        },
        "pooler": L.init_dense(next(keys), h, h, dtype=dtype),
    }
    for _ in range(cfg.num_layers):
        layer = {
            "attention": {
                "qkv": L.init_dense(next(keys), h, 3 * h, dtype=dtype),
                "out": L.init_dense(next(keys), h, h, dtype=dtype),
                "layernorm": {"weight": jnp.ones((h,), jnp.float32),
                              "bias": jnp.zeros((h,), jnp.float32)},
            },
            "mlp": {
                "fc1": L.init_dense(next(keys), h, i, dtype=dtype),
                "fc2": L.init_dense(next(keys), i, h, dtype=dtype),
                "layernorm": {"weight": jnp.ones((h,), jnp.float32),
                              "bias": jnp.zeros((h,), jnp.float32)},
            },
        }
        params["encoder"].append(layer)
    return params


def _ln(p, x, eps):
    return fused_layer_norm_affine(x, p["weight"], p["bias"],
                                   x.shape[-1], eps).astype(x.dtype)


def _attention(p, cfg: BertConfig, x, mask, dropout_rng=None):
    from apex_tpu.transformer.functional import (
        flash_attention_packed, scaled_masked_softmax)

    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    qkv = L.dense(p["qkv"], x).reshape(b, s, 3, nh, hd)
    if cfg.fused_attention:
        # the projection's output as it lies: at a sequence of 128 or 256
        # the fmha kernels index q, k and v in it and write the context as
        # the output projection reads it; any other shape is transposed
        # and takes flash_attention's own path
        return L.dense(p["out"], flash_attention_packed(
            qkv, mask, softmax_scale=1.0 / math.sqrt(hd),
            dropout_rate=cfg.attention_dropout, dropout_rng=dropout_rng))
    q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
    scores = jnp.einsum("bnqd,bnkd->bnqk", *cast_args("einsum", q, k))
    if mask is not None:
        # mask: (b, s) with 1 = attend; the fused kernel masks nonzero
        inv = (1 - mask)[:, None, None, :]
    else:
        inv = jnp.zeros((b, 1, 1, s), jnp.int32)
    probs = scaled_masked_softmax(scores, inv, 1.0 / math.sqrt(hd))
    if dropout_rng is not None and cfg.attention_dropout > 0:
        keep = jax.random.bernoulli(dropout_rng, 1 - cfg.attention_dropout,
                                    probs.shape)
        probs = probs * keep / (1 - cfg.attention_dropout)
    ctx = jnp.einsum("bnqk,bnkd->bnqd", *cast_args("einsum", probs, v))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    return L.dense(p["out"], ctx)


def _maybe_dropout(x, rate, rng):
    if rng is None or rate <= 0:
        return x
    keep = jax.random.bernoulli(rng, 1 - rate, x.shape)
    return x * keep / (1 - rate)


def apply_bert(params: Dict[str, Any], cfg: BertConfig,
               input_ids: jax.Array,
               attention_mask: Optional[jax.Array] = None,
               token_type_ids: Optional[jax.Array] = None,
               *, dropout_rng: Optional[jax.Array] = None,
               compute_dtype=None) -> Dict[str, jax.Array]:
    """Returns {"hidden": (b,s,h), "mlm_logits": (b,s,vocab),
    "pooled": (b,h)}."""
    b, s = input_ids.shape
    emb = params["embeddings"]
    with region("embed"):
        x = L.embedding(emb["word"], input_ids, compute_dtype)
        x = x + L.embedding(emb["position"], jnp.arange(s),
                            compute_dtype)[None]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = x + L.embedding(emb["token_type"], token_type_ids, compute_dtype)
        x = _ln(emb["layernorm"], x, cfg.layer_norm_eps)

    rngs = (jax.random.split(dropout_rng, 2 * cfg.num_layers + 1)
            if dropout_rng is not None else [None] * (2 * cfg.num_layers + 1))
    with region("embed"):   # apart from the lookups: the key split stays put
        x = _maybe_dropout(x, cfg.hidden_dropout, rngs[0])

    def encoder_layer(layer, x, rng_a, rng_h):
        with region("attention"):
            att = _attention(layer["attention"], cfg, x, attention_mask,
                             rng_a)
            att = _maybe_dropout(att, cfg.hidden_dropout, rng_h)
            x = _ln(layer["attention"]["layernorm"], x + att,
                    cfg.layer_norm_eps)
        with region("mlp"):
            mlp = L.dense(layer["mlp"]["fc2"],
                          jax.nn.gelu(L.dense(layer["mlp"]["fc1"], x)))
            x = _ln(layer["mlp"]["layernorm"], x + mlp, cfg.layer_norm_eps)
        return x

    if cfg.remat:
        encoder_layer = jax.checkpoint(encoder_layer,
                                       static_argnums=())
    for li, layer in enumerate(params["encoder"]):
        with jax.named_scope(f"layer{li}"):
            x = encoder_layer(layer, x, rngs[2 * li + 1],
                              rngs[2 * li + 2])

    head = params["mlm_head"]
    with region("head"):
        t = jax.nn.gelu(L.dense(head["transform"], x))
        t = _ln(head["layernorm"], t, cfg.layer_norm_eps)
        word_table = emb["word"]["embedding"].astype(t.dtype)
        # rows flattened BEFORE the product, contracted on the table's
        # hidden axis: the logits are born (b*s, vocab) row-major, which is
        # what the loss kernel reads; as a (b, s, vocab) product XLA lays
        # them out vocab-major and copies the gigabyte in front of the kernel
        flat = jax.lax.dot_general(t.reshape(b * s, -1), word_table,
                                   (((1,), (1,)), ((), ())))
        mlm_logits = (flat.astype(jnp.float32)
                      + head["bias"].astype(jnp.float32)).reshape(b, s, -1)
        pooled = jnp.tanh(L.dense(params["pooler"], x[:, 0]))
    return {"hidden": x, "mlm_logits": mlm_logits, "pooled": pooled}


def mlm_loss(logits: jax.Array, labels: jax.Array,
             label_mask: jax.Array) -> jax.Array:
    """Masked-LM cross entropy in fp32; ``label_mask`` (1 = predict)
    selects positions. Routed through the fused xentropy kernel (ref:
    ``apex/contrib/xentropy``) so the (b, s, vocab) log-softmax is never
    materialized."""
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    b, s, v = logits.shape
    with region("loss"):
        flat_labels = jnp.where(label_mask != 0, labels, -1).reshape(b * s)
        losses = softmax_cross_entropy_loss(logits.reshape(b * s, v),
                                            flat_labels)
        m = label_mask.astype(jnp.float32)
        return losses.sum() / jnp.maximum(m.sum(), 1.0)
