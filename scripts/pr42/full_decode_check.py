"""PR 42 diagnostic at the cell's FULL size (256 slots, 64 experts held, the
configuration file's engine): prefill prompts of 1,500 and 3,000 tokens into
slots 0, 100 and 255, decode T tokens teacher-forced with those slots active,
and compare every step's logits with the program's own prompt path over the
whole sequence and with the plain reference.

    python3 scripts/pr42/full_decode_check.py [--cpu]   (rehearsal sizes)
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
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models import bailing_hybrid as bh
from benchmark import harness

harness.enable_compile_cache()
cell = harness.Cell("ling3_flash_vl.many_stream_reasoning")
config, _ = harness.views(cell, cpu)
ref = cell.reference()
runner = cell.runner()
ctx = types.SimpleNamespace(seed=123456789)
engine, sched, deliveries, sz = runner.build(ctx, config, ref)
cfg, params = engine.cfg, engine.params
print(jax.devices()[0], "slots", engine.num_slots, "buckets", engine.buckets)
T = 8 if cpu else 24
lengths = (30, 70) if cpu else (1500, 3000)
slots = [0, engine.num_slots // 2, engine.num_slots - 1]
rng = np.random.RandomState(0)
seqs = {s: rng.randint(2, sz["vocab"], lengths[i % 2] + T)
        for i, s in enumerate(slots)}
P = {s: len(seqs[s]) - T for s in slots}
rows = {s: [np.asarray(engine.prefill(s, [int(t) for t in seqs[s][:P[s]]]))[0]]
        for s in slots}
active = np.zeros((engine.num_slots,), bool)
active[slots] = True
for i in range(T):
    engine.prepare_decode({s: P[s] + i for s in slots})
    toks = np.zeros((engine.num_slots,), np.int32)
    for s in slots:
        toks[s] = seqs[s][P[s] + i]
    logits = np.asarray(engine.decode(jnp.asarray(toks), jnp.asarray(active)))
    for s in slots:
        rows[s].append(logits[s])


@jax.jit
def prompt_path(params, ids):
    x = bh.prefill_layers(params, cfg, bh.embed(params, ids),
                          jnp.ones(ids.shape, jnp.int32), jnp.bfloat16)[0]
    return bh.logits_of(params, cfg, x)


for s in slots:
    got = np.stack(rows[s])
    n = len(seqs[s])
    pad = -n % 64
    ids = np.concatenate([seqs[s], np.zeros((pad,), seqs[s].dtype)])
    full = np.asarray(prompt_path(params, jnp.asarray(ids)))[P[s] - 1:n]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, i: ref.logits_at(
            p, sz, i, jnp.arange(P[s] - 1, n)))(params, jnp.asarray(ids)))
    for name, other in (("prompt path", full), ("reference", want)):
        d = got - other
        print("slot", s, "prompt", P[s], name, "rms by step",
              [round(float(np.sqrt((d[i] ** 2).mean())), 5)
               for i in (0, 1, 2, 4, 8, T)],
              "best differs", int((got.argmax(-1) != other.argmax(-1)).sum()),
              "of", T + 1, flush=True)
    d = full - want
    print("slot", s, "prompt path against reference rms",
          round(float(np.sqrt((d ** 2).mean())), 5), flush=True)
