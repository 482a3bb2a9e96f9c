#!/usr/bin/env python3
"""Where does the DECODE path part from the PROMPT path? (PR 40, review
round: the prompt path agrees with the reference on every route, the served
tokens still lie 0.00085 from it.) The cell's own engine at full size: one
prompt prefilled into two slots, then ``steps`` tokens decoded teacher-forced
in both; the same sequence through the prompt path (``prefill_layers``) in
one pass. Printed: the decode logits against the prompt path's (RMS, flips
of the best token, by block of 128 steps), slot against slot (any difference
at all says a slot's result depends on its neighbours), and layer by layer
the K and V rows decode WROTE into the pools against the rows the prompt
path computes for the same positions (the first layer that differs by more
than a rounding's worth has its cause in the layer before it).

    python3 scripts/pr40/decode_vs_prompt.py [--cpu] [--seed n] [--steps n]
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--seed", type=int, default=2200000033)
    ap.add_argument("--steps", type=int, default=768)
    args = ap.parse_args()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import exaone_moe
    from apex_tpu.serving.cache import ring_page
    from benchmark import harness

    harness.enable_compile_cache()
    cell = harness.Cell("k_exaone_236b_a23b.long_context_reasoning")
    config, _ = harness.views(cell, args.cpu)
    ref, runner = cell.reference(), cell.runner()
    eng, _, _, sz = runner.build(types.SimpleNamespace(seed=args.seed),
                                 config, ref)
    cfg, params = eng.cfg, eng.params
    n0 = min(eng.buckets)
    steps = min(args.steps, eng.max_len - n0 - 1)
    total = n0 + steps
    ids = np.random.RandomState(args.seed % 2 ** 31).randint(
        2, sz["vocab"], total).astype(np.int32)
    a, b = 0, eng.num_slots - 1 if args.cpu else 37
    for slot in (a, b):
        eng.prefill(slot, ids[:n0])
    active = jnp.zeros((eng.num_slots,), bool).at[jnp.array([a, b])].set(True)
    rows = []
    for t in range(n0, total):
        assert eng.prepare_decode({a: t, b: t}) == []
        tokens = jnp.zeros((eng.num_slots,), jnp.int32).at[
            jnp.array([a, b])].set(int(ids[t]))
        rows.append(np.asarray(eng.decode(tokens, active))[[a, b]])
    decoded = np.stack(rows)                    # (steps, 2, vocab)
    eng.sync_table()
    cache, page = eng.cache, eng.page_size
    at = np.arange(n0, total)                   # the positions decode wrote
    pages = np.asarray(eng._table)[a][at // page]
    host = lambda t: np.asarray(t).astype(np.float32)
    wrote = {"full": [host(t[0][pages, at % page])
                      for t in (cache.k, cache.v)]}
    last = at[-min(cfg.window, steps):]         # what the cycle still holds
    ring = np.asarray(ring_page(a, last // page, cache.ring))
    for layer in range(cfg.window_layers):
        wrote[f"sliding{layer}"] = [
            host(t[layer][ring, last % page]) for t in (cache.wk, cache.wv)]

    padded = -(-total // 1024) * 1024 if not args.cpu else total

    @jax.jit
    def prompt_path(params, ids, mask):
        x, full, sliding, _ = exaone_moe.prefill_layers(
            params, cfg, exaone_moe.embed(params, ids), mask, cache.k.dtype)
        # the rows as the pools' dtype holds them: made float32 on the HOST
        # (on the chip the compiler folds a round trip through astype away)
        return (exaone_moe.logits_of(params, cfg, x[n0:total]),
                [t[0, n0:total] for t in full],
                [[t[layer, last[0]:total] for t in sliding]
                 for layer in range(cfg.window_layers)])

    seq = np.zeros((padded,), np.int32)
    seq[:total] = ids
    # decode step t reads token t and gives the logits of position t
    logits, full, sliding = jax.tree.map(
        lambda t: np.asarray(t).astype(np.float32), jax.device_get(
            prompt_path(params, jnp.asarray(seq), jnp.asarray(
                (np.arange(padded) < total).astype(np.int32)))))
    out = {"prompt": n0, "steps": steps, "slots": [a, b]}
    # does a round trip through astype round at all in a compiled program?
    x = jax.random.normal(jax.random.PRNGKey(0), (1 << 16,), jnp.float32)
    trip = jax.jit(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32) * 2)
    cut = jax.jit(lambda x: jax.lax.reduce_precision(x, 8, 7) * 2)
    out["astype_round_trip_changes_share"] = float(
        (np.asarray(trip(x)) != 2 * np.asarray(x)).mean())
    out["reduce_precision_changes_share"] = float(
        (np.asarray(cut(x)) != 2 * np.asarray(x)).mean())
    err = decoded[:, 0] - logits
    out["logits_rms"] = float(np.sqrt((err ** 2).mean()))
    out["logits_rms_by_128_steps"] = [
        round(float(np.sqrt((err[i:i + 128] ** 2).mean())), 5)
        for i in range(0, steps, 128)]
    out["logits_std"] = float(logits.std())
    out["best_token_differs_at"] = int(
        (decoded[:, 0].argmax(-1) != logits.argmax(-1)).sum())
    out["slot_against_slot_elements_unequal"] = int(
        (decoded[:, 0] != decoded[:, 1]).sum())
    out["slot_against_slot_max"] = float(
        np.abs(decoded[:, 0] - decoded[:, 1]).max())

    def rows_apart(got, want):
        scale = float(np.sqrt((want ** 2).mean()))
        return {"unequal_share": round(float((got != want).mean()), 5),
                "rms_over_rms": float(np.sqrt(((got - want) ** 2).mean())
                                      / scale)}

    out["rows_decode_wrote_against_prompt_path"] = {
        "full": [rows_apart(g, w) for g, w in zip(wrote["full"], full)],
        **{f"sliding{layer}": [rows_apart(g, w) for g, w in zip(
            wrote[f"sliding{layer}"], sliding[layer])]
           for layer in range(cfg.window_layers)}}
    out["layer_of_pool_row"] = {
        "sliding": [i for i in range(cfg.num_layers) if cfg.windowed(i)],
        "full": [i for i in range(cfg.num_layers) if not cfg.windowed(i)]}
    print(json.dumps(out, indent=1))
    os.makedirs(os.path.join(ROOT, "chiprun_out", "p40r"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "p40r",
                           "decode_vs_prompt.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
