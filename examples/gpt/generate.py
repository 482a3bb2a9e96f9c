#!/usr/bin/env python
"""KV-cached GPT generation CLI — drives ``apex_tpu.serving`` end to
end: bf16 inference params (``amp`` O2 model cast), a preallocated
donated page pool, bucketed prefill, and continuous batching over a
fixed slot set with greedy or temperature/top-k sampling.

Synthetic weights + prompts (the in-tree models are test-scale); run on
the CPU rig with e.g.::

    python examples/gpt/generate.py --num-requests 8 --num-slots 4 \\
        --max-new-tokens 24 --temperature 0.8 --top-k 50

or pass explicit prompts as comma-separated token ids::

    python examples/gpt/generate.py --prompt 5,7,11 --prompt 42,1,2,3
"""

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

from apex_tpu import amp  # noqa: E402
from apex_tpu.models.gpt import GPTConfig, init_gpt  # noqa: E402
from apex_tpu.serving import (  # noqa: E402
    ContinuousBatchingScheduler, PagedDecodeEngine, Request,
)
from apex_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

PAGE_SIZE = 16

def parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    m = p.add_argument_group("model")
    m.add_argument("--vocab-size", type=int, default=512)
    m.add_argument("--hidden-size", type=int, default=64)
    m.add_argument("--num-layers", type=int, default=4)
    m.add_argument("--num-heads", type=int, default=8)
    m.add_argument("--ffn-hidden-size", type=int, default=128)
    m.add_argument("--use-rope", action="store_true")
    m.add_argument("--fp32", action="store_true",
                   help="skip the O2 bf16 model cast (and use an fp32 "
                        "KV cache)")
    s = p.add_argument_group("serving")
    s.add_argument("--num-slots", type=int, default=4)
    s.add_argument("--max-len", type=int, default=128,
                   help=f"cache rows a slot: whole pages of {PAGE_SIZE}")
    s.add_argument("--top-k", type=int, default=0)
    r = p.add_argument_group("requests")
    r.add_argument("--prompt", action="append", default=None,
                   help="comma-separated token ids; repeatable. Default: "
                        "--num-requests random prompts")
    r.add_argument("--num-requests", type=int, default=8)
    r.add_argument("--max-new-tokens", type=int, default=16)
    r.add_argument("--temperature", type=float, default=0.0)
    r.add_argument("--eos-id", type=int, default=1)
    r.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    enable_compile_cache()
    ns = parse_args()
    cfg = GPTConfig(
        vocab_size=ns.vocab_size, hidden_size=ns.hidden_size,
        num_layers=ns.num_layers, num_heads=ns.num_heads,
        ffn_hidden_size=ns.ffn_hidden_size,
        max_position_embeddings=ns.max_len, use_rope=ns.use_rope,
        hidden_dropout=0.0)
    params = init_gpt(jax.random.PRNGKey(ns.seed), cfg)
    if not ns.fp32:
        # O2 inference cast: bf16 params (norms stay fp32) — halves
        # weight HBM; the KV cache follows the same dtype choice
        params = amp.initialize("O2", verbosity=0).cast_model(params)
    cache_dtype = jnp.float32 if ns.fp32 else jnp.bfloat16

    # a full pool: every slot can hold max_len rows, nothing is preempted
    engine = PagedDecodeEngine(
        params, cfg, num_slots=ns.num_slots, max_len=ns.max_len,
        num_pages=PagedDecodeEngine.full_pool_pages(
            ns.num_slots, ns.max_len, PAGE_SIZE),
        page_size=PAGE_SIZE, cache_dtype=cache_dtype, top_k=ns.top_k)
    sched = ContinuousBatchingScheduler(engine, eos_id=ns.eos_id)

    if ns.prompt:
        prompts = [tuple(int(t) for t in s.split(",")) for s in ns.prompt]
    else:
        rng = np.random.RandomState(ns.seed)
        prompts = [
            tuple(int(t) for t in rng.randint(
                2, cfg.vocab_size, size=rng.randint(4, ns.max_len // 2)))
            for _ in range(ns.num_requests)]

    for i, prompt in enumerate(prompts):
        sched.submit(Request(prompt=prompt,
                             max_new_tokens=ns.max_new_tokens,
                             temperature=ns.temperature,
                             seed=ns.seed + i))

    t0 = time.perf_counter()
    outputs = sched.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(o) for o in outputs)
    for i, (prompt, out) in enumerate(zip(prompts, outputs)):
        print(f"[{i}] prompt({len(prompt)})={list(prompt)[:8]}... "
              f"-> {out}")
    print(f"generated {n_tok} tokens across {len(outputs)} requests "
          f"in {dt:.2f}s ({n_tok / dt:.1f} tok/s, includes compile)")


if __name__ == "__main__":
    main()
