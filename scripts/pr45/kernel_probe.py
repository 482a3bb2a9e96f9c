"""On the chip: ``apex_mla_decode_fwd`` alone at the two cells' shapes: the
parent's kernel (``--parent``, a ``git archive`` of the parent commit), the
kernel of ``--tree`` (default: the checkout this is run from) and that one
under other ring and block sizes (module constants set from here: the kernel
takes no option); ``--also=<name>:<file>`` times another kernel file beside
them. Every path is relative to the directory this is run from, the root of
a checkout, and one that leads out of it is refused: nothing outside the
checkout is read or run. One JSON line a variant: milliseconds a call (the
mean over ``CALLS`` calls behind one ``block_until_ready``), the largest
distance from the parent's output over every slot, and from the float32 XLA
form over the first slots. ``--cpu`` runs tiny shapes here to rehearse the
script; ``--sanity`` times the parent and the tree's kernel only. Exit code
1: the tree's kernel is not the parent's to rounding; 3: it is not 5% faster
than the parent's at every shape (``pairs.sh`` then stops: an hour of pairs
would measure nothing worth handing in)."""

import functools
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

CPU = "--cpu" in sys.argv
CHECKOUT = os.path.realpath(os.getcwd())
KERNEL = os.path.join("apex_tpu", "transformer", "functional",
                      "mla_attention.py")


def option(name, default=None):
    return next((a[len(name) + 3:] for a in sys.argv
                 if a.startswith(f"--{name}=")), default)


def inside(path):
    """``path`` (relative to the checkout) resolved, or exit: it may not
    lead out of the checkout."""
    full = os.path.realpath(os.path.join(CHECKOUT, path))
    if full != CHECKOUT and not full.startswith(CHECKOUT + os.sep):
        sys.exit(f"{path}: outside the checkout {CHECKOUT}")
    return full


sys.path.insert(0, inside(option("tree", ".")))
from apex_tpu.transformer.functional import mla_attention as mine  # noqa: E402

OUT = os.path.join(CHECKOUT, "chiprun_out", "p45")
CALLS = 3 if CPU else 40
PAGE, WIDTH, VALUE = 16, 640, 512

# (slots, heads, pages a slot in the table, layers, lengths from .. to)
SHAPES = {
    "deepseek_128h": (64, 128, 400, 5, 1100, 4013),
    "ling_32h": (256, 32, 512, 1, 1100, 3700),
}
if CPU:
    SHAPES = {"tiny_8h": (6, 8, 40, 2, 3, 600)}


def load(name, path):
    """The kernel module in file ``path`` (inside the checkout), or None
    where there is none."""
    path = inside(path)
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inputs(slots, heads, table, layers, lo, hi, seed=45):
    rng = np.random.RandomState(seed)
    lengths = rng.randint(lo, hi, size=slots).astype(np.int32)
    lengths[slots // 2] = 0                 # one slot that reads nothing
    n_pages = 2 + slots * table
    key = jax.random.PRNGKey(seed)
    pool = jax.random.normal(key, (layers, n_pages, PAGE, WIDTH),
                             jnp.bfloat16)
    tables = np.zeros((slots, table), np.int32)
    free = rng.permutation(np.arange(2, n_pages))
    at = 0
    for i, n in enumerate(lengths):
        k = -(-int(n) // PAGE)
        tables[i, :k] = free[at:at + k]
        at += k
    q = jnp.asarray(rng.normal(size=(slots, heads, WIDTH)) * 0.05,
                    jnp.float32)
    new = jnp.asarray(rng.normal(size=(slots, WIDTH)), jnp.float32)
    return q, new, pool, jnp.asarray(tables), jnp.asarray(lengths)


def timed(fn, args):
    out = fn(*args).block_until_ready()
    for _ in range(2):
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(CALLS):
        last = fn(*args)
    last.block_until_ready()
    return out, (time.perf_counter() - t0) / CALLS * 1e3


def main():
    os.makedirs(OUT, exist_ok=True)
    parent = load("parent_mla", os.path.join(
        option("parent", os.path.join(".chip_tree", "parent")), KERNEL))
    variants = [("parent", parent, {})] if parent else []
    variants.append(("mine", mine, {}))
    if "--sanity" in sys.argv:
        return run(variants)
    for also in (a[7:] for a in sys.argv if a.startswith("--also=")):
        name, path = also.split(":", 1)
        variants.append((name, load(name, path), {}))
    low = mine._RING_BLOCKS[0]
    variants += [
        ("ring_of_4", mine, {"_RING_BLOCKS": (low, 4)}),
        ("ring_of_6", mine, {"_RING_BLOCKS": (low, 6)}),
        ("ring_of_12", mine, {"_RING_BLOCKS": (low, 12),
                              "_RING_BYTES": 1 << 23}),
        ("ring_of_6_blocks_of_512", mine, {"_BLOCK_POSITIONS": 512,
                                           "_RING_BLOCKS": (low, 6),
                                           "_RING_BYTES": 1 << 23}),
    ]
    run(variants)


def run(variants):
    """Times each ``(name, module, constants)``; exits 1 where this tree's
    kernel is not the parent's to rounding, 3 (at the end) where it is not
    5% faster."""
    tag = next((a[6:] for a in sys.argv if a.startswith("--tag=")), "probe")
    slow = False
    with open(os.path.join(OUT, f"kernel_{tag}.jsonl"), "w") as log:
        for shape, dims in SHAPES.items():
            slots, heads, table, layers = dims[:4]
            q, new, pool, bt, pos = inputs(*dims)
            layer = jnp.int32(layers - 1)
            few = min(4, slots)
            want = mine.mla_decode_reference(
                q[:few], new[:few], pool, bt[:few], pos[:few], layers - 1,
                value_width=VALUE)
            base, ms_of = None, {}
            for name, module, consts in variants:
                saved = {k: getattr(module, k) for k in consts}
                for k, v in consts.items():
                    setattr(module, k, v)
                try:
                    fn = jax.jit(functools.partial(
                        module.mla_decode_attention, value_width=VALUE))
                    t0 = time.perf_counter()
                    got, ms = timed(fn, (q, new, pool, bt, pos, layer))
                    line = {"shape": shape, "variant": name, "heads": heads,
                            "slots": slots, "positions": int(pos.sum()),
                            "ms_a_call": ms,
                            "ns_a_position": ms * 1e6 / int(pos.sum()),
                            "first_call_and_timing_s":
                                time.perf_counter() - t0,
                            "err_xla": float(jnp.abs(got[:few] - want).max())}
                    ms_of[name] = ms
                    if base is None:
                        base = got
                    line["err_first_variant"] = float(
                        jnp.abs(got - base).max())
                except Exception as e:  # noqa: BLE001 - a variant the compiler refuses
                    line = {"shape": shape, "variant": name,
                            "error": repr(e)[:400]}
                finally:
                    for k, v in saved.items():
                        setattr(module, k, v)
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
                if name == "mine" and not (
                        line.get("err_first_variant", 1.0) < 1e-4
                        and line["err_xla"] < 1e-4):
                    sys.exit(1)
            slow |= ms_of.get("mine", 0.0) > 0.95 * ms_of.get(
                "parent", float("inf"))
            del pool
    if slow and not CPU:
        sys.exit(3)


if __name__ == "__main__":
    main()
