"""PR 42 diagnostic: where does decode part from the prompt path on the chip?

1. The two KDA kernels and the MLA decode kernel at the cell's widths (32
   heads of 128; a row of 640) against plain jax.numpy.
2. The model at the published widths with few slots: prefill, then decode
   teacher-forced, logits against the prompt path over the whole sequence.

    python3 scripts/pr42/decode_vs_prompt.py [--cpu]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if "--cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models import bailing_hybrid as bh
from apex_tpu.serving import PagedDecodeEngine
from apex_tpu.transformer.functional import gated_delta as gd
from apex_tpu.transformer.functional.mla_attention import (
    mla_decode_attention, mla_decode_reference)

small = "--cpu" in sys.argv
H, D = (4, 16) if small else (32, 128)
print(jax.devices()[0])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


# -- 1a. the KDA step against jnp --------------------------------------------
ks = jax.random.split(jax.random.PRNGKey(0), 8)
B, L = 8, 3
q = jax.random.normal(ks[0], (B, H, D)) / np.sqrt(D)
k = jax.random.normal(ks[1], (B, H, D))
k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
v = jax.random.normal(ks[2], (B, H, D))
la = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, D)))
be = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
st = jax.random.normal(ks[5], (L, B, H, D, D))
act = jnp.arange(B) % 3 != 1
with jax.default_matmul_precision("highest"):
    S = jnp.exp(la)[..., None] * st[1]
    u = be[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, S))
    S = S + k[..., None] * u[:, :, None, :]
    want_o = jnp.einsum("bhk,bhkv->bhv", q, S)
o, st2 = jax.jit(gd.gated_delta_step)(q, k, v, la, be, st, jnp.int32(1), act)
on = np.asarray(act)
print("kda_step o", rel(o[on], want_o[on]), "state", rel(st2[1][on], S[on]),
      "idle kept", bool((np.asarray(st2[1])[~on] == np.asarray(st[1])[~on]).all()),
      "idle zero", bool((np.asarray(o)[~on] == 0).all()),
      "other layers", bool((np.asarray(st2[0]) == np.asarray(st[0])).all()
                           and (np.asarray(st2[2]) == np.asarray(st[2])).all()))

# -- 1b. the chunked KDA against stepping -------------------------------------
s = 256
qs = jax.random.normal(ks[0], (H, s, D)) / np.sqrt(D)
kk = jax.random.normal(ks[1], (H, s, D))
kk = kk / jnp.linalg.norm(kk, axis=-1, keepdims=True)
vs = jax.random.normal(ks[2], (H, s, D))
las = -5 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (H, s, D)))
bes = jax.nn.sigmoid(jax.random.normal(ks[4], (H, s)))
oc, sc = jax.jit(gd.gated_delta_chunked)(qs, kk, vs, las, bes)


def scan_ref(qs, kk, vs, las, bes):
    def step(S, row):
        q, k, v, la, be = row
        S = jnp.exp(la)[..., None] * S
        u = be[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S))
        S = S + k[..., None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S)
    mv = lambda t: jnp.moveaxis(t, 1, 0)
    S, o = jax.lax.scan(step, jnp.zeros((H, D, D)),
                        (mv(qs), mv(kk), mv(vs), mv(las), mv(bes)))
    return jnp.moveaxis(o, 0, 1), S


with jax.default_matmul_precision("highest"):
    os_, ss = jax.jit(scan_ref)(qs, kk, vs, las, bes)
print("kda_chunk o", rel(oc, os_), "state", rel(sc, ss))

# -- 1c. the MLA decode kernel at H heads -------------------------------------
W, VW, page = (128, 32, 4) if small else (640, 512, 16)
slots, pages_per = 4, 40
pool = (0.3 * jax.random.normal(ks[6], (1, 2 + slots * pages_per, page, W))
        ).astype(jnp.bfloat16)
bt = 2 + jnp.arange(slots * pages_per, dtype=jnp.int32).reshape(slots, -1)
pos = jnp.asarray([0, 37, 300, pages_per * page - 1], jnp.int32)
pos = jnp.minimum(pos, pages_per * page - 1)
qm = 0.2 * jax.random.normal(ks[7], (slots, H, W))
new = 0.3 * jax.random.normal(ks[5], (slots, W))
got = jax.jit(lambda *a: mla_decode_attention(*a, value_width=VW))(
    qm, new, pool, bt, pos, jnp.int32(0))
want = mla_decode_reference(qm, new, pool, bt, pos, jnp.int32(0),
                            value_width=VW)
print("mla_decode", [rel(got[i], want[i]) for i in range(slots)])

# -- 2. the model: prefill, then decode, against the prompt path --------------
if small:
    cfg = bh.bailing_hybrid_tiny()
    P, T, max_len, buckets, pg = 40, 24, 128, (64, 128), 4
else:
    cfg = bh.BailingHybridConfig(
        vocab_size=19648, layer_types=bh.layer_types_of(1, 7, 6),
        first_k_dense=1, experts_held=8)
    P, T, max_len, buckets, pg = 700, 48, 2048, (1024,), 16
params = jax.jit(lambda key: bh.init(key, cfg, jnp.bfloat16))(
    jax.random.PRNGKey(1))
rng = np.random.RandomState(0)
ids = rng.randint(2, cfg.vocab_size, P + T)
for cache_dtype in (jnp.bfloat16, jnp.float32):
    eng = PagedDecodeEngine(
        params, cfg, num_slots=4, max_len=max_len,
        num_pages=PagedDecodeEngine.full_pool_pages(4, max_len, pg),
        page_size=pg, cache_dtype=cache_dtype, prefix_sharing=False,
        buckets=buckets)
    slot = 2
    rows = [np.asarray(eng.prefill(slot, [int(t) for t in ids[:P]]))[0]]
    active = jnp.arange(4) == slot
    for i in range(T):
        eng.prepare_decode({slot: P + i})
        toks = jnp.zeros((4,), jnp.int32).at[slot].set(int(ids[P + i]))
        rows.append(np.asarray(eng.decode(toks, active))[slot])
    got = np.stack(rows)

    @jax.jit
    def prompt_path(params, ids):
        x = bh.prefill_layers(params, cfg, bh.embed(params, ids),
                              jnp.ones(ids.shape, jnp.int32), cache_dtype)[0]
        return bh.logits_of(params, cfg, x)

    pad = -(P + T) % 64
    full = np.asarray(prompt_path(params, jnp.asarray(
        np.concatenate([ids, np.zeros((pad,), ids.dtype)]))))[P - 1:P + T]
    d = got - full
    print(jnp.dtype(cache_dtype).name, "logit std", float(full.std()),
          "rms by step", [round(float(np.sqrt((d[i] ** 2).mean())), 5)
                          for i in (0, 1, 2, 4, 8, 16, T)],
          "best differs", int((got.argmax(-1) != full.argmax(-1)).sum()),
          "of", T + 1)
    del eng
