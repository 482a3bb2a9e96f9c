"""sha256 of the lowered text of the tiny configurations' serving programs.

One line a program: every family's ``jit_prefill`` (largest bucket) and
``jit_decode`` over a float32 and a bfloat16 pool, and the GPT family's
verify, tree-verify and chunk-prefill programs. Run from the root of a
checkout (``shacmp.sh`` runs it in two and compares): a change that claims
to leave the served programs alone must print the parent's list."""
import dataclasses
import hashlib
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

from apex_tpu.models import (
    bailing_hybrid, deepseek, exaone_moe, gpt, hybrid, nemotron_h,
)
from apex_tpu.serving import PagedDecodeEngine

SLOTS, MAX_LEN, PAGES, PAGE, BUCKETS, SPEC_K, CHUNK = 3, 512, 100, 16, \
    [64, 512], 3, 64


def families():
    k = jax.random.PRNGKey(0)
    f32 = dict(cache_dtype=jnp.float32)
    c = dataclasses.replace(gpt.gpt_tiny(), use_rope=True, hidden_dropout=0.0)
    yield "gpt", gpt.init_gpt(k, c), c, dict(spec_k=SPEC_K, tree_spec=True)
    c = hybrid.hybrid_tiny()
    yield "hybrid", hybrid.init_hybrid(k, c), c, dict(prefix_sharing=False)
    c = nemotron_h.nemotron_h_tiny()
    yield "nemotron", nemotron_h.init(k, c), c, dict(prefix_sharing=False)
    c = deepseek.deepseek_tiny()
    yield "deepseek", deepseek.init(k, c), c, f32
    c = exaone_moe.exaone_moe_tiny()
    yield "exaone", exaone_moe.init(k, c), c, f32
    c = bailing_hybrid.bailing_hybrid_tiny()
    yield "ling", bailing_hybrid.init(k, c), c, dict(prefix_sharing=False)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def spec_programs(eng):
    """The GPT engine's verify, tree-verify and chunk-prefill programs,
    traced at the shapes the engine calls them with."""
    k1, pages = SPEC_K + 1, eng.max_pages
    return {
        "verify": eng._verify.trace(eng.params, eng.cache, i32(SLOTS, k1)),
        "tree_verify": eng._tree_verify.trace(
            eng.params, eng.cache, i32(SLOTS, k1), i32(SLOTS, k1),
            jax.ShapeDtypeStruct((SLOTS, k1, k1), jnp.bool_)),
        f"chunk_prefill_{CHUNK}": eng._chunk_prefill.trace(
            eng.params, eng.cache, i32(1, CHUNK), i32(CHUNK), i32(), i32(),
            i32(CHUNK // PAGE), i32(pages), i32(pages)),
    }


for name, params, cfg, kw in families():
    dtypes = [jnp.bfloat16, jnp.float32] if name == "gpt" \
        else [jnp.float32, jnp.bfloat16]
    for dt in dtypes:
        eng = PagedDecodeEngine(
            params, cfg, num_slots=SLOTS, max_len=MAX_LEN, num_pages=PAGES,
            page_size=PAGE, buckets=BUCKETS, **{**kw, "cache_dtype": dt})
        programs = eng.trace_programs()
        if name == "gpt":
            programs.update(spec_programs(eng))
        for pname, traced in programs.items():
            text = traced.lower().as_text()
            print(name, jnp.dtype(dt).name, pname,
                  hashlib.sha256(text.encode()).hexdigest()[:16])
