"""Chipless: what ``apex_mla_decode_fwd`` costs a server's START, which no
compile cache keeps: tracing and lowering (Pallas -> Mosaic included) of the
DeepSeek and Ling decode programs at their cells' sizes, and compiling them
for a v5e that is described, not attached (cache off). Run from the root of
the checkout to be measured (``PYTHONPATH=. python3 <this file>``): it
imports whatever ``apex_tpu`` stands there, so the parent is measured by
running the same file from an unpacked ``git archive`` of it.

One JSON line a program and repeat: seconds to lower, seconds to compile,
the bytes of the lowered text and, for each of the kernel's serialized
Mosaic modules in it, its bytes and its matrix products (``tpu.matmul``: the
number ``tests/L0/test_aot_v5e.py`` holds to two bodies' worth).
"""

import base64
import functools
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from apex_tpu.utils import platform

jax.config.update("jax_enable_compilation_cache", False)
platform._platform = lambda: "tpu"      # kernels lower for Mosaic

REPEATS = int(sys.argv[1]) if len(sys.argv) > 1 else 3
COMPILE = "--no-compile" not in sys.argv


def mosaic_modules(text, name="apex_mla_decode_fwd"):
    """``(bytes of the serialized module, its tpu.matmul operations)`` of
    each Mosaic call of kernel ``name`` in a lowered program's text."""
    from jaxlib.mlir import ir

    out = []
    for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', text):
        raw = base64.b64decode(body)
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(raw).operation.get_asm()
        if name in asm:
            out.append((len(raw), asm.count("tpu.matmul")))
    return out


def programs(dev):
    from apex_tpu.models import bailing_hybrid as bh
    from apex_tpu.models import deepseek
    from apex_tpu.serving.cache import init_hybrid_cache, init_latent_cache

    sharding = SingleDeviceSharding(dev)
    on = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    page = 16
    cfg = deepseek.DeepseekConfig(vocab_size=16160, num_layers=5,
                                  first_k_dense=1, experts_held=16)
    yield "deepseek_decode", cfg, (
        on(jax.eval_shape(lambda k: deepseek.init(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))),
        on(jax.eval_shape(functools.partial(
            init_latent_cache, cfg, 64, 6400, 64 * (6400 // page) + 2, page,
            jnp.bfloat16))),
        sds((64,), jnp.int32), sds((64,), jnp.bool_))
    cfg = bh.BailingHybridConfig(
        vocab_size=19648, layer_types=bh.layer_types_of(1, 7, 6),
        first_k_dense=1, experts_held=64)
    yield "ling_decode", cfg, (
        on(jax.eval_shape(lambda k: bh.init(k, cfg, jnp.bfloat16),
                          jax.random.PRNGKey(0))),
        on(jax.eval_shape(functools.partial(
            init_hybrid_cache, cfg, 256, 8192, 256 * (8192 // page) + 2,
            page, jnp.bfloat16))),
        sds((256,), jnp.int32), sds((256,), jnp.bool_))


def main():
    from apex_tpu.serving.decode import make_model_decode_fn

    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    for name, cfg, args in programs(dev):
        for repeat in range(REPEATS):
            jax.clear_caches()
            fn = make_model_decode_fn(cfg)
            t0, c0 = time.perf_counter(), time.process_time()
            lowered = fn.lower(*args)
            t1, c1 = time.perf_counter(), time.process_time()
            text = lowered.as_text()
            # this sandbox shares its cores: the process's own seconds wander
            # less than the clock's
            line = {"program": name, "repeat": repeat,
                    "lower_s": round(t1 - t0, 3),
                    "lower_cpu_s": round(c1 - c0, 3), "text_bytes": len(text),
                    "mosaic_modules": mosaic_modules(text)}
            if COMPILE:
                t2 = time.perf_counter()
                compiled = lowered.compile()
                line["compile_s"] = round(time.perf_counter() - t2, 3)
                line["code_bytes"] = compiled.memory_analysis(
                    ).generated_code_size_in_bytes
                line["mla_calls"] = len(re.findall(
                    r"%apex_mla_decode_fwd(\.\d+)? = ", compiled.as_text()))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
