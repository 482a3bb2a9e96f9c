"""Serving headline contract: KV-cached incremental decode must match
the full-sequence forward to fp32 tolerance at identical positions —
plus the supporting invariants (pad-independence of bucketed prefill,
cache-donation bit-identity, cache dtype behavior)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt import apply_gpt_unsharded, gpt_tiny, init_gpt
from apex_tpu.serving import init_cache, make_decode_fn, make_prefill_fn
from apex_tpu.serving.cache import KVCache

S_TOTAL, PROMPT, S_MAX = 20, 8, 32


def _cfg(use_rope):
    return dataclasses.replace(gpt_tiny(), use_rope=use_rope,
                               hidden_dropout=0.0)


def _full_logits(params, cfg, seq):
    hidden = apply_gpt_unsharded(params, cfg, seq)
    table = params["embedding"]["word"]["embedding"]
    return jnp.dot(hidden, table.T).astype(jnp.float32)


def _teacher_forced(params, cfg, seq, cache_dtype=jnp.float32,
                    num_slots=2):
    """prefill(seq[:PROMPT]) then decode feeding the TRUE next tokens;
    returns logits rows aligned with positions PROMPT-1 .. S_TOTAL-1."""
    prefill = make_prefill_fn(cfg)
    decode = make_decode_fn(cfg)
    cache = init_cache(cfg, num_slots, S_MAX, cache_dtype)
    cache, logits = prefill(params, cache, seq[:, :PROMPT],
                            jnp.ones((PROMPT,), jnp.int32),
                            jnp.int32(0))
    rows = [logits[0]]
    pad_tokens = jnp.zeros((num_slots - 1,), jnp.int32)
    active = jnp.asarray([True] + [False] * (num_slots - 1))
    for t in range(PROMPT, seq.shape[1]):
        tokens = jnp.concatenate(
            [jnp.asarray([int(seq[0, t])], jnp.int32), pad_tokens])
        cache, logits = decode(params, cache, tokens, active)
        rows.append(logits[0])
    return jnp.stack(rows)


@pytest.mark.parametrize("use_rope", [True, False],
                         ids=["rope", "learned_pos"])
def test_decode_matches_full_forward(use_rope):
    cfg = _cfg(use_rope)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = jax.random.randint(jax.random.PRNGKey(1), (1, S_TOTAL), 0,
                             cfg.vocab_size)
    want = _full_logits(params, cfg, seq)[0, PROMPT - 1:]
    got = _teacher_forced(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_prefill_pad_tail_never_attended():
    """Bucket padding regression: prefill of the same prompt padded with
    two different garbage tails must produce identical logits AND an
    identical cache — pad K/V can never leak into attention, now or
    through later in-place cache writes."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prefill = make_prefill_fn(cfg)
    prompt = np.asarray([[5, 7, 11, 13, 17]], np.int32)  # ragged: 5
    bucket = 16
    mask = (np.arange(bucket) < prompt.shape[1]).astype(np.int32)

    def run(pad_value):
        ids = np.full((1, bucket), pad_value, np.int32)
        ids[:, : prompt.shape[1]] = prompt
        cache = init_cache(cfg, 1, S_MAX, jnp.float32)
        return prefill(params, cache, jnp.asarray(ids),
                       jnp.asarray(mask), jnp.int32(0))

    cache_a, logits_a = run(0)
    cache_b, logits_b = run(499)
    np.testing.assert_array_equal(np.asarray(logits_a),
                                  np.asarray(logits_b))
    for a, b in zip(cache_a, cache_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the decode continuation is identical too
    decode = make_decode_fn(cfg)
    _, la = decode(params, cache_a, jnp.asarray([3], jnp.int32),
                   jnp.asarray([True]))
    _, lb = decode(params, cache_b, jnp.asarray([3], jnp.int32),
                   jnp.asarray([True]))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_ragged_batch_parity():
    """Prompts of different lengths, each bucketed with a pad tail, all
    decoding concurrently in one cache — every slot must still match
    its own full-sequence forward."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prefill = make_prefill_fn(cfg)
    decode = make_decode_fn(cfg)
    lens = [3, 8, 13]
    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, cfg.vocab_size, size=(1, n + 4)).astype(
        np.int32) for n in lens]
    cache = init_cache(cfg, len(lens), S_MAX, jnp.float32)
    for i, (n, seq) in enumerate(zip(lens, seqs)):
        bucket = 16
        ids = np.zeros((1, bucket), np.int32)
        ids[:, :n] = seq[:, :n]
        mask = (np.arange(bucket) < n).astype(np.int32)
        cache, logits = prefill(params, cache, jnp.asarray(ids),
                                jnp.asarray(mask), jnp.int32(i))
        want = _full_logits(params, cfg, jnp.asarray(seq[:, :n]))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(want[0, -1]),
                                   rtol=1e-4, atol=1e-4)
    # four teacher-forced decode steps with ALL slots active at their
    # own (ragged) positions
    for t in range(4):
        tokens = jnp.asarray([int(s[0, n + t]) for n, s in
                              zip(lens, seqs)], jnp.int32)
        cache, logits = decode(params, cache, tokens,
                               jnp.ones((len(lens),), bool))
        for i, (n, seq) in enumerate(zip(lens, seqs)):
            want = _full_logits(params, cfg,
                                jnp.asarray(seq[:, : n + t + 1]))
            np.testing.assert_allclose(np.asarray(logits[i]),
                                       np.asarray(want[0, -1]),
                                       rtol=1e-4, atol=1e-4)


def test_cache_donation_bit_identity():
    """The donated jitted decode must produce bit-identical caches and
    logits to a fresh-cache run of the same steps — donation is a
    buffer-reuse optimization, never a numerics change."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prefill = make_prefill_fn(cfg)
    decode = make_decode_fn(cfg)

    def run_steps():
        cache = init_cache(cfg, 1, S_MAX, jnp.bfloat16)
        cache, _ = prefill(params, cache,
                           jnp.asarray([[2, 3, 5, 7]], jnp.int32),
                           jnp.ones((4,), jnp.int32), jnp.int32(0))
        outs = []
        for tok in (11, 13, 17):
            cache, logits = decode(params, cache,
                                   jnp.asarray([tok], jnp.int32),
                                   jnp.asarray([True]))
            outs.append(np.asarray(logits))
        return cache, outs

    cache_a, outs_a = run_steps()
    cache_b, outs_b = run_steps()
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(cache_a, cache_b):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert cache_a.k.dtype == jnp.bfloat16
    assert int(cache_a.lengths[0]) == 7  # 4 prompt + 3 decoded


def test_init_cache_validates():
    cfg = _cfg(False)
    with pytest.raises(ValueError, match="position table"):
        init_cache(cfg, 1, cfg.max_position_embeddings + 1)
    with pytest.raises(ValueError, match="positive"):
        init_cache(cfg, 0, 8)
    c = init_cache(cfg, 2, 16)
    assert isinstance(c, KVCache) and c.k.dtype == jnp.bfloat16
    assert c.k.shape == (cfg.num_layers, 2, cfg.num_heads, 16,
                         cfg.head_dim)


# -- paged cache ------------------------------------------------------------

def _paged_teacher_forced(params, cfg, seq, free_order=None):
    """Paged analogue of :func:`_teacher_forced`: prefill + decode via
    :class:`PagedDecodeEngine` (page_size 8, so the 8-token prompt ends
    exactly at a page boundary only for the default PROMPT — boundary
    allocation and in-page appends both get exercised)."""
    from apex_tpu.serving import PagedDecodeEngine

    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=jnp.float32, buckets=(8, 16, 32),
                            free_order=free_order)
    logits = eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    rows = [logits[0]]
    for t in range(PROMPT, seq.shape[1]):
        assert eng.prepare_decode({0: t}) == []
        logits = eng.decode(
            jnp.asarray([int(seq[0, t]), 0], jnp.int32),
            jnp.asarray([True, False]))
        rows.append(logits[0])
    return jnp.stack(rows)


@pytest.mark.parametrize("use_rope", [True, False],
                         ids=["rope", "learned_pos"])
def test_paged_decode_matches_full_forward(use_rope):
    """The serving headline contract holds through the page
    indirection: paged incremental decode == full-sequence forward to
    fp32 tolerance at identical positions."""
    cfg = _cfg(use_rope)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = jax.random.randint(jax.random.PRNGKey(1), (1, S_TOTAL), 0,
                             cfg.vocab_size)
    want = _full_logits(params, cfg, seq)[0, PROMPT - 1:]
    got = _paged_teacher_forced(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_paged_decode_bit_identical_across_page_placements():
    """Physical page placement is an allocator detail: the same request
    decoded through permuted free-list orders must produce
    BIT-IDENTICAL logits at every step (masked scores are exactly
    zeroed in the softmax, so unmapped/garbage pages contribute exactly
    0.0 — tolerance would hide a real leak)."""
    from apex_tpu.serving.cache import RESERVED_PAGES

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = jax.random.randint(jax.random.PRNGKey(1), (1, S_TOTAL), 0,
                             cfg.vocab_size)
    usable = list(range(RESERVED_PAGES, 14))
    rng = np.random.RandomState(3)
    orders = [None, list(reversed(usable)),
              list(rng.permutation(usable))]
    runs = [np.asarray(_paged_teacher_forced(params, cfg, seq, order))
            for order in orders]
    for other in runs[1:]:
        np.testing.assert_array_equal(runs[0], other)


def test_paged_dense_logits_agree():
    """Paged and dense decode run the same math over the same rows —
    they must agree to tight fp32 tolerance at every step (not bitwise:
    the attention reductions are differently shaped programs)."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = jax.random.randint(jax.random.PRNGKey(1), (1, S_TOTAL), 0,
                             cfg.vocab_size)
    dense = np.asarray(_teacher_forced(params, cfg, seq))
    paged = np.asarray(_paged_teacher_forced(params, cfg, seq))
    np.testing.assert_allclose(paged, dense, rtol=1e-5, atol=1e-5)


# -- speculative verify -----------------------------------------------------

def _seq(cfg, n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0,
                              cfg.vocab_size)


@pytest.mark.parametrize("k", [1, 3])
def test_verify_matches_full_forward(k):
    """The k+1-position verify forward is exact: row j equals the full
    forward's logits after reading seq[: PROMPT + j + 1] — the verify
    step is a prefill-shaped continuation, not an approximation."""
    from apex_tpu.serving import make_verify_fn

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    prefill = make_prefill_fn(cfg)
    verify = make_verify_fn(cfg)
    cache = init_cache(cfg, 2, S_MAX, jnp.float32)
    cache, _ = prefill(params, cache, seq[:, :PROMPT],
                       jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
    # column 0 = the pending token, columns 1.. = drafts; slot 1 idle
    # (its rows 0..k take garbage writes the masks never admit)
    tokens = jnp.concatenate(
        [seq[:, PROMPT:], jnp.zeros((1, k + 1), jnp.int32)], axis=0)
    cache, logits = verify(params, cache, tokens)
    want = _full_logits(params, cfg, seq)[0, PROMPT:]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # lengths are committed by the HOST after the accept walk, never by
    # the verify step itself
    assert int(cache.lengths[0]) == PROMPT


@pytest.mark.parametrize("k", [1, 3])
def test_paged_verify_matches_full_forward(k):
    """Same exactness through the page indirection (page_size 8 with
    PROMPT 8: the verify window starts ON a page boundary, so
    prepare_decode's n_new-row allocation is load-bearing)."""
    from apex_tpu.serving import PagedDecodeEngine

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=jnp.float32,
                            buckets=(8, 16, 32), spec_k=k)
    eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    assert eng.prepare_decode({0: PROMPT}, n_new=k + 1) == []
    tokens = jnp.concatenate(
        [seq[:, PROMPT:], jnp.zeros((1, k + 1), jnp.int32)], axis=0)
    logits = eng.verify(tokens)
    want = _full_logits(params, cfg, seq)[0, PROMPT:]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("paged", [False, True],
                         ids=["dense", "paged"])
def test_verify_rejected_rows_not_observable(paged):
    """The rollback contract, bitwise: two runs whose first verify step
    carried DIFFERENT garbage draft tails (all rejected — only the
    pending token commits) must produce a bit-identical next verify
    step AND a bit-identical next plain-decode step. Rejected rows are
    written, but every later mask either re-writes them first (verify:
    the new window covers the stale range) or never admits them (plain:
    scores masked at fp32 -inf before softmax) — tolerance here would
    hide a real leak."""
    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 2)

    def run(garbage):
        if paged:
            from apex_tpu.serving import PagedDecodeEngine
            eng = PagedDecodeEngine(params, cfg, num_slots=1,
                                    max_len=S_MAX, num_pages=14,
                                    page_size=8, cache_dtype=jnp.float32,
                                    buckets=(8, 16, 32), spec_k=k)
            eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
            eng.prepare_decode({0: PROMPT}, n_new=k + 1)
            bad = jnp.concatenate(
                [seq[:, PROMPT:PROMPT + 1],
                 jnp.full((1, k), garbage, jnp.int32)], axis=1)
            eng.verify(bad)
            eng.commit([1])  # accept only the pending token
            eng.prepare_decode({0: PROMPT + 1}, n_new=k + 1)
            l_verify = eng.verify(seq[:, PROMPT + 1:PROMPT + k + 2])
            eng.commit([1])
            eng.prepare_decode({0: PROMPT + 2})
            l_plain = eng.decode(seq[:, PROMPT + 2],
                                 jnp.asarray([True]))
            return np.asarray(l_verify), np.asarray(l_plain)
        from apex_tpu.serving import make_verify_fn
        prefill = make_prefill_fn(cfg)
        verify = make_verify_fn(cfg)
        decode = make_decode_fn(cfg)
        cache = init_cache(cfg, 1, S_MAX, jnp.float32)
        cache, _ = prefill(params, cache, seq[:, :PROMPT],
                           jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
        bad = jnp.concatenate(
            [seq[:, PROMPT:PROMPT + 1],
             jnp.full((1, k), garbage, jnp.int32)], axis=1)
        cache, _ = verify(params, cache, bad)
        cache = cache._replace(lengths=cache.lengths + 1)
        cache, l_verify = verify(params, cache,
                                 seq[:, PROMPT + 1:PROMPT + k + 2])
        cache = cache._replace(lengths=cache.lengths + 1)
        cache, l_plain = decode(params, cache, seq[:, PROMPT + 2],
                                jnp.asarray([True]))
        return np.asarray(l_verify), np.asarray(l_plain)

    va, pa = run(3)
    vb, pb = run(499)
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(pa, pb)


def test_verify_agrees_with_plain_decode_steps():
    """Feeding the verify window one token at a time through plain
    decode must land on the same logits to tight fp32 tolerance (not
    bitwise: the two are differently shaped reductions — the stream
    bit-identity contract lives at the sampled-token level, see
    test_scheduler.py)."""
    from apex_tpu.serving import make_verify_fn

    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    plain = np.asarray(_teacher_forced(params, cfg, seq))[1:]

    prefill = make_prefill_fn(cfg)
    verify = make_verify_fn(cfg)
    cache = init_cache(cfg, 2, S_MAX, jnp.float32)
    cache, _ = prefill(params, cache, seq[:, :PROMPT],
                       jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
    tokens = jnp.concatenate(
        [seq[:, PROMPT:], jnp.zeros((1, k + 1), jnp.int32)], axis=0)
    _, logits = verify(params, cache, tokens)
    np.testing.assert_allclose(np.asarray(logits[0]), plain,
                               rtol=1e-5, atol=1e-5)


def test_tp_verify_matches_unsharded():
    """tp=2 speculative verify (dense + paged): logits match the
    unsharded verify step to fp32 tolerance and the greedy accept walk
    commits the identical token prefix — the TP mesh composes with
    speculation unchanged."""
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import (
        PagedDecodeEngine, make_tp_paged_verify_fn, make_tp_verify_fn,
        make_verify_fn,
    )
    from apex_tpu.transformer import parallel_state as ps

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    k = 2
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    tokens = jnp.concatenate(
        [seq[:, PROMPT:], jnp.zeros((1, k + 1), jnp.int32)], axis=0)
    ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    model = GPTModel(cfg, tp_size=2)

    # dense: one prefilled cache, cloned through both verify paths
    prefill = make_prefill_fn(cfg)
    cache = init_cache(cfg, 2, S_MAX, jnp.float32)
    cache, _ = prefill(params, cache, seq[:, :PROMPT],
                       jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
    clone = jax.tree.map(jnp.copy, cache)
    _, want = make_verify_fn(cfg)(params, cache, tokens)
    _, got = make_tp_verify_fn(model)(params, clone, tokens)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got[0], -1)),
                                  np.asarray(jnp.argmax(want[0], -1)))

    # paged: engine-built cache (block tables + pool), same contract
    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=jnp.float32,
                            buckets=(8, 16, 32), spec_k=k)
    eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    eng.prepare_decode({0: PROMPT}, n_new=k + 1)
    eng.sync_table()    # the clone is launched by hand: upload first
    clone = jax.tree.map(jnp.copy, eng.cache)
    want = eng.verify(tokens)
    _, got = make_tp_paged_verify_fn(model)(params, clone, tokens)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got[0], -1)),
                                  np.asarray(jnp.argmax(want[0], -1)))


# -- tree verify ------------------------------------------------------------
#
# One forward over a DRAFT TREE: grid node j writes its K/V at physical
# row pos + j but attends at logical position pos + depth[j], seeing
# committed history plus exactly its ancestor set (anc[:, j]). A linear
# chain is the k1-wide special case and must reproduce the existing
# verify step bit-for-bit; branch nodes must each match the full
# forward over their OWN root-to-leaf path.

def _chain_tree(k1):
    """depth = arange, anc[src, q] = src <= q: the linear chain
    (``anc[i, j]`` means column i visible to QUERY column j, so the
    chain is upper-triangular in (src, query) order)."""
    depth = jnp.arange(k1, dtype=jnp.int32)[None, :]
    anc = jnp.triu(jnp.ones((k1, k1), bool))[None]
    return depth, anc


def test_tree_verify_linear_chain_bit_identical_to_verify():
    """With a chain ancestor matrix the tree verify IS the linear
    verify — same program shape, same writes, bit-identical logits
    and cache. Tolerance would hide a mask bug."""
    from apex_tpu.serving import make_tree_verify_fn, make_verify_fn

    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    prefill = make_prefill_fn(cfg)
    cache = init_cache(cfg, 2, S_MAX, jnp.float32)
    cache, _ = prefill(params, cache, seq[:, :PROMPT],
                       jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
    clone = jax.tree.map(jnp.copy, cache)
    tokens = jnp.concatenate(
        [seq[:, PROMPT:], jnp.zeros((1, k + 1), jnp.int32)], axis=0)
    cache_a, want = make_verify_fn(cfg)(params, cache, tokens)
    depth, anc = _chain_tree(k + 1)
    depth = jnp.broadcast_to(depth, (2, k + 1))
    anc = jnp.broadcast_to(anc, (2, k + 1, k + 1))
    cache_b, got = make_tree_verify_fn(cfg)(params, clone, tokens,
                                            depth, anc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for a, b in zip(cache_a, cache_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tree_verify_branches_match_full_forward():
    """A two-branch tree: root R with children A and B, A with child C.
    Each node's logits row must equal the full forward over prompt +
    its OWN ancestor path — sibling branches never contaminate each
    other even though their K/V rows coexist in the window."""
    from apex_tpu.serving import make_tree_verify_fn, tree_arrays

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + 1)
    prefill = make_prefill_fn(cfg)
    cache = init_cache(cfg, 1, S_MAX, jnp.float32)
    cache, _ = prefill(params, cache, seq[:, :PROMPT],
                       jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
    root = int(seq[0, PROMPT])
    a_tok, b_tok, c_tok = 101, 202, 303
    toks, depth, anc, valid, parents, start = tree_arrays(
        [[root]], [([a_tok, b_tok, c_tok], [-1, -1, 0])], k1=4)
    assert list(parents[0]) == [-1, 0, 0, 1]
    _, logits = make_tree_verify_fn(cfg)(
        params, cache, jnp.asarray(toks), jnp.asarray(depth),
        jnp.asarray(anc))
    logits = np.asarray(logits[0])
    # column j of the grid == last row of the full forward over the
    # prompt + j's root-to-node path
    paths = {0: [root], 1: [root, a_tok], 2: [root, b_tok],
             3: [root, a_tok, c_tok]}
    for col, path in paths.items():
        full = jnp.concatenate(
            [seq[:, :PROMPT], jnp.asarray([path], jnp.int32)], axis=1)
        want = np.asarray(_full_logits(params, cfg, full)[0, -1])
        np.testing.assert_allclose(logits[col], want,
                                   rtol=1e-4, atol=1e-4)
    # and the sibling branches really did diverge
    assert (np.argmax(logits[1]) != np.argmax(logits[2])
            or not np.allclose(logits[1], logits[2]))


def test_paged_tree_verify_matches_dense():
    """The tree mask composes with the page indirection: paged tree
    verify agrees with the dense tree verify to tight fp32 tolerance
    (differently shaped reductions — argmax must agree exactly)."""
    from apex_tpu.serving import (
        PagedDecodeEngine, make_tree_verify_fn,
    )

    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + 1)
    root = int(seq[0, PROMPT])
    from apex_tpu.serving import tree_arrays
    toks, depth, anc, _, _, _ = tree_arrays(
        [[root]], [([101, 202, 303], [-1, -1, 0])], k1=4)

    prefill = make_prefill_fn(cfg)
    cache = init_cache(cfg, 1, S_MAX, jnp.float32)
    cache, _ = prefill(params, cache, seq[:, :PROMPT],
                       jnp.ones((PROMPT,), jnp.int32), jnp.int32(0))
    _, want = make_tree_verify_fn(cfg)(
        params, cache, jnp.asarray(toks), jnp.asarray(depth),
        jnp.asarray(anc))

    eng = PagedDecodeEngine(params, cfg, num_slots=1, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=jnp.float32, buckets=(8, 16, 32),
                            spec_k=3, tree_spec=True)
    eng.prefill(0, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    eng.prepare_decode({0: PROMPT}, n_new=4)
    got = eng.tree_verify(jnp.asarray(toks), jnp.asarray(depth),
                          jnp.asarray(anc))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(got[0], -1)),
        np.asarray(jnp.argmax(want[0], -1)))


def test_tp_tree_verify_matches_unsharded():
    """tp=2 tree verify (dense + paged): the tree descriptors are
    replicated host decisions, heads shard over ``model`` — logits
    match the unsharded tree verify to fp32 tolerance with exact
    argmax agreement, mirroring test_tp_verify_matches_unsharded."""
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import (
        PagedDecodeEngine, make_tp_paged_tree_verify_fn,
        make_tp_tree_verify_fn, make_tree_verify_fn, tree_arrays,
    )
    from apex_tpu.transformer import parallel_state as ps

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + 1)
    root = int(seq[0, PROMPT])
    toks, depth, anc, _, _, _ = tree_arrays(
        [[root], [root]], [([101, 202, 303], [-1, -1, 0]),
                           ([11, 22, 33], [-1, 0, 1])], k1=4)
    toks, depth, anc = (jnp.asarray(toks), jnp.asarray(depth),
                        jnp.asarray(anc))
    ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    model = GPTModel(cfg, tp_size=2)

    prefill = make_prefill_fn(cfg)
    cache = init_cache(cfg, 2, S_MAX, jnp.float32)
    for slot in (0, 1):
        cache, _ = prefill(params, cache, seq[:, :PROMPT],
                           jnp.ones((PROMPT,), jnp.int32),
                           jnp.int32(slot))
    clone = jax.tree.map(jnp.copy, cache)
    _, want = make_tree_verify_fn(cfg)(params, cache, toks, depth, anc)
    _, got = make_tp_tree_verify_fn(model)(params, clone, toks, depth,
                                           anc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))

    eng = PagedDecodeEngine(params, cfg, num_slots=2, max_len=S_MAX,
                            num_pages=14, page_size=8,
                            cache_dtype=jnp.float32, buckets=(8, 16, 32),
                            spec_k=3, tree_spec=True)
    for slot in (0, 1):
        eng.prefill(slot, [int(t) for t in np.asarray(seq[0, :PROMPT])])
    eng.prepare_decode({0: PROMPT, 1: PROMPT}, n_new=4)
    eng.sync_table()    # the clone is launched by hand: upload first
    clone = jax.tree.map(jnp.copy, eng.cache)
    want = eng.tree_verify(toks, depth, anc)
    _, got = make_tp_paged_tree_verify_fn(model)(params, clone, toks,
                                                 depth, anc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


def test_init_paged_cache_validates():
    from apex_tpu.serving import init_paged_cache
    from apex_tpu.serving.cache import (
        PagedKVCache, RESERVED_PAGES, SCRATCH_PAGE,
    )

    cfg = _cfg(False)
    with pytest.raises(ValueError, match="position table"):
        init_paged_cache(cfg, 1, cfg.max_position_embeddings + 1, 6, 16)
    with pytest.raises(ValueError, match="positive"):
        init_paged_cache(cfg, 0, 8, 6, 4)
    with pytest.raises(ValueError, match="reserved"):
        init_paged_cache(cfg, 1, 8, RESERVED_PAGES, 4)
    c = init_paged_cache(cfg, 2, 16, 6, 4)
    assert isinstance(c, PagedKVCache) and c.k.dtype == jnp.bfloat16
    assert c.k.shape == (cfg.num_layers, 6, 4,
                         cfg.num_heads * cfg.head_dim)
    assert c.block_tables.shape == (2, 4)  # ceil(16 / 4) per slot
    assert int(c.block_tables.min()) == SCRATCH_PAGE  # parked on scratch
    assert int(c.block_tables.max()) == SCRATCH_PAGE
