"""On the chip: the sparse layer's decode pieces at the cell's shapes, each
against its plain form and each timed alone (index kernel, exact top-k, the
gather of the picked rows by single rows and by runs of 4, the MLA kernel over
the gathered buffer). ``--cpu`` runs tiny shapes here. 2 chip-minutes."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.transformer.functional import sparse_index as si
from apex_tpu.transformer.functional.mla_attention import (
    mla_decode_attention, mla_decode_reference,
)

cpu = "--cpu" in sys.argv
B, H, W, PAGE, P = (4, 2, 16, 8, 4) if cpu else (64, 32, 128, 16, 4)
MAXP = 16 if cpu else 1024
TOP = 4 if cpu else 512
HEADS, LW = (4, 128) if cpu else (64, 512)
dt = jnp.float32 if cpu else jnp.bfloat16
rng = np.random.RandomState(0)
pages = 2 + B * MAXP
rows = jnp.asarray(rng.randn(1, pages, PAGE // P, W), dt)
pool = jnp.asarray(rng.randn(1, pages, PAGE, LW) * 0.3, dt)
bt = jnp.asarray(2 + rng.permutation(B * MAXP).reshape(B, MAXP), jnp.int32)
pos = jnp.asarray(rng.randint(MAXP * PAGE // 4, MAXP * PAGE * 7 // 8, B),
                  jnp.int32)
groups = pos // P
q = jnp.asarray(rng.randn(B, H, W), jnp.float32)
w = jnp.asarray(rng.randn(B, H), jnp.float32) * (H * W) ** -0.5


def timed(name, f, *args, n=20):
    out = jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    print(f"{name}: {1e3 * (time.perf_counter() - t) / n:.3f} ms", flush=True)
    return out


index = jax.jit(lambda *a: si.index_scores(*a, jnp.int32(0)))
got = timed("apex_dsa_index_fwd", index, q, w, rows, bt, groups)
want = jax.jit(lambda *a: si.index_scores_reference(*a, 0))(
    q, w, rows, bt, groups)
live = np.asarray(want) > -1e30
err = np.abs(np.asarray(got) - np.asarray(want))[live].max()
print("index kernel against plain XLA: max err", err, "scores' scale",
      np.abs(np.asarray(want)[live]).max(), "masked alike",
      bool(((np.asarray(got) > -1e30) == live).all()), flush=True)
pick = jax.jit(lambda s, g: si.pick_groups(s, g, TOP))
picked, count = timed("top_k", pick, got, groups)
same = np.mean([len(set(a[:c]) & set(b[:c])) / max(c, 1) for a, b, c in zip(
    np.asarray(picked), np.asarray(pick(want, groups)[0]), np.asarray(count))])
print("picks in common with the plain scores' picks", same, flush=True)
gather = jax.jit(lambda *a: si.gather_picked(pool, 0, *a, P))
buf, table, length = timed("gather by rows", gather, bt, picked, count, pos)


def by_runs(bt, picked, count, pos):
    k = picked.shape[1]
    j = jnp.arange(k + 1)[None, :]
    g = jnp.where(j < count[:, None], jnp.pad(picked, ((0, 0), (0, 1))),
                  jnp.where(j == count[:, None], (pos // P)[:, None], 0))
    a_page = PAGE // P
    page = jnp.take_along_axis(bt, g // a_page, 1)
    first = (page * PAGE + g % a_page * P).reshape(-1)
    flat = pool.reshape(-1, LW)
    return jax.vmap(lambda s: lax.dynamic_slice(flat, (s, 0), (P, LW)))(first)


runs = timed("gather by runs of 4", jax.jit(by_runs), bt, picked, count, pos)
print("runs equal rows", bool(jnp.array_equal(
    runs.reshape(B, -1, LW)[:, :(TOP + 1) * P],
    buf.reshape(B, -1, LW)[:, :(TOP + 1) * P])), flush=True)
ql = jnp.asarray(rng.randn(B, HEADS, LW) * 0.05, jnp.float32)
new = jnp.asarray(rng.randn(B, LW) * 0.3, jnp.float32)
attend = jax.jit(lambda *a: mla_decode_attention(*a, jnp.int32(0),
                                                 value_width=LW))
o = timed("apex_mla_decode_fwd over the buffer", attend, ql, new, buf, table,
          length)
o_ref = jax.jit(lambda *a: mla_decode_reference(*a, 0, value_width=LW))(
    ql, new, buf, table, length)
print("attention against plain XLA: max err",
      float(jnp.abs(o - o_ref).max()), "rows attended", np.asarray(length)[:8],
      flush=True)
