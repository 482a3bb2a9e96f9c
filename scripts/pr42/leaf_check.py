"""PR 42 diagnostic at a serving cell's full size: which leaf of the cache
does a decode step leave wrong? Slot A is prefilled with P tokens and decodes
token P; slot B is prefilled with the same P + 1 tokens. State, tails and the
pool's row at position P of A have to equal B's. For any cell whose model
keeps recurrent state (this PR's by default).

    python3 scripts/pr42/leaf_check.py [--cpu] [--cell <name>]
"""
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
cpu = "--cpu" in sys.argv
if cpu:
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax.numpy as jnp
import numpy as np

from benchmark import harness

harness.enable_compile_cache()
name = sys.argv[sys.argv.index("--cell") + 1] if "--cell" in sys.argv \
    else "ling3_flash_vl.many_stream_reasoning"
cell = harness.Cell(name)
config, _ = harness.views(cell, cpu)
ref, runner = cell.reference(), cell.runner()
engine, *_ = runner.build(types.SimpleNamespace(seed=123456789), config, ref)
n, page = engine.num_slots, engine.page_size
P = 41 if cpu else min(1500, max(engine.buckets) - 2)
A, B = 0, n - 1
seq = [int(t) for t in np.random.RandomState(0).randint(2, 500, P + 2)]
engine.prefill(A, seq[:P])
engine.prefill(B, seq[:P + 1])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return round(float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30)), 6)


def leaves(slot, pos):
    c = engine.cache
    pg = engine._slot_pages[slot][pos // page]
    return (np.asarray(c.state[:, slot]), np.asarray(c.conv[:, slot]),
            np.asarray(c.k[:, pg, pos % page].astype(jnp.float32)),
            int(c.lengths[slot]))


worst = 0.0
for step in (0, 1):
    active = np.zeros((n,), bool)
    active[A] = True
    toks = np.zeros((n,), np.int32)
    toks[A] = seq[P + step]
    engine.prepare_decode({A: P + step})
    engine.decode(jnp.asarray(toks), jnp.asarray(active))
    if step == 1:
        engine.free_slot(B)
        engine.prefill(B, seq[:P + 2])
    a, b = leaves(A, P + step), leaves(B, P + step)
    print(name, "slots", n, "P", P, "after decode step", step + 1,
          "lengths", a[3], b[3])
    print("  state by layer",
          [rel(a[0][l], b[0][l]) for l in range(len(a[0]))])
    print("  tails by layer",
          [rel(a[1][l], b[1][l]) for l in range(len(a[1]))])
    print("  tail rows of layer 0",
          [rel(a[1][0][r], b[1][0][r]) for r in range(a[1].shape[1])])
    print("  pool row (k)", rel(a[2], b[2]), flush=True)
    worst = max([worst, rel(a[2], b[2])]
                + [rel(a[i][l], b[i][l]) for i in (0, 1)
                   for l in range(len(a[i]))])
print("worst leaf", worst, "sound" if worst < 1e-2 else "WRONG")
sys.exit(0 if worst < 1e-2 else 1)
