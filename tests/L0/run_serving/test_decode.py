"""Serving headline contract: KV-cached incremental decode must match
the full-sequence forward to fp32 tolerance at identical positions —
plus the supporting invariants (pad-independence of bucketed prefill,
cache-donation bit-identity, cache dtype behavior, the verify step's
rollback). The oracle is the full forward (``full_forward``); every
program here goes through the page pool."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from full_forward import full_logits, teacher_forced

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (
    PagedDecodeEngine, init_paged_cache, make_paged_decode_fn,
    make_paged_prefill_fn, tree_arrays,
)
from apex_tpu.serving.cache import (
    NULL_PAGE, RESERVED_PAGES, SCRATCH_PAGE, PagedKVCache,
)

S_TOTAL, PROMPT, S_MAX = 20, 8, 32
TOL = dict(rtol=1e-4, atol=1e-4)


def _cfg(use_rope):
    return dataclasses.replace(gpt_tiny(), use_rope=use_rope,
                               hidden_dropout=0.0)


def _engine(params, cfg, num_slots=2, page_size=8, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return PagedDecodeEngine(params, cfg, num_slots=num_slots,
                             max_len=S_MAX, num_pages=14 * 8 // page_size,
                             page_size=page_size, buckets=(8, 16, 32),
                             **kw)


def _ids(row):
    return [int(t) for t in np.asarray(row)]


def test_prefill_pad_tail_never_attended():
    """Bucket padding regression: prefill of the same prompt padded with
    two different garbage tails must produce identical logits AND an
    identical cache — pad K/V can never leak into attention, now or
    through later in-place cache writes. (The engine pads with zeros on
    the host, so the program is driven by hand.)"""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prefill = make_paged_prefill_fn(cfg)
    prompt = np.asarray([[5, 7, 11, 13, 17]], np.int32)  # ragged: 5
    bucket, page = 16, 8
    mask = (np.arange(bucket) < prompt.shape[1]).astype(np.int32)
    # the prompt's one page, the pad's page redirected to scratch
    write = jnp.asarray([RESERVED_PAGES, SCRATCH_PAGE], jnp.int32)
    row = jnp.asarray([RESERVED_PAGES] + [NULL_PAGE] * 3, jnp.int32)

    def run(pad_value):
        ids = np.full((1, bucket), pad_value, np.int32)
        ids[:, : prompt.shape[1]] = prompt
        cache = init_paged_cache(cfg, 1, S_MAX, 6, page, jnp.float32)
        return prefill(params, cache, jnp.asarray(ids), jnp.asarray(mask),
                       jnp.int32(0), write, row)

    cache_a, logits_a = run(0)
    cache_b, logits_b = run(499)
    np.testing.assert_array_equal(np.asarray(logits_a),
                                  np.asarray(logits_b))
    for a, b in zip(cache_a, cache_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the decode continuation is identical too
    decode = make_paged_decode_fn(cfg)
    _, la = decode(params, cache_a, jnp.asarray([3], jnp.int32),
                   jnp.asarray([True]))
    _, lb = decode(params, cache_b, jnp.asarray([3], jnp.int32),
                   jnp.asarray([True]))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _ragged(cfg, lens, extra, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=n + extra).astype(np.int32)
            for n in lens]


def test_ragged_batch_parity():
    """Prompts of different lengths, each bucketed with a pad tail, all
    decoding concurrently in one pool — every slot must still match
    its own full-sequence forward, across the page boundaries its
    positions cross (pages of 4: rows 4, 8 and 16)."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    lens = [3, 8, 13]
    seqs = _ragged(cfg, lens, 4)
    eng = _engine(params, cfg, num_slots=3, page_size=4)
    for i, (n, seq) in enumerate(zip(lens, seqs)):
        logits = eng.prefill(i, _ids(seq[:n]))
        np.testing.assert_allclose(
            np.asarray(logits[0]),
            teacher_forced(params, cfg, seq, [n - 1])[0], **TOL)
    # four teacher-forced decode steps with ALL slots active at their
    # own (ragged) positions
    for t in range(4):
        assert eng.prepare_decode(
            {i: n + t for i, n in enumerate(lens)}) == []
        logits = eng.decode(
            jnp.asarray([int(s[n + t]) for n, s in zip(lens, seqs)],
                        jnp.int32), jnp.ones((len(lens),), bool))
        for i, (n, seq) in enumerate(zip(lens, seqs)):
            np.testing.assert_allclose(
                np.asarray(logits[i]),
                teacher_forced(params, cfg, seq, [n + t])[0], **TOL)


def test_cache_donation_bit_identity():
    """The donated jitted decode must produce bit-identical caches and
    logits to a fresh-cache run of the same steps — donation is a
    buffer-reuse optimization, never a numerics change."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)

    def run_steps():
        eng = _engine(params, cfg, num_slots=1, cache_dtype=jnp.bfloat16)
        eng.prefill(0, [2, 3, 5, 7])
        outs = []
        for pos, tok in enumerate((11, 13, 17), start=4):
            eng.prepare_decode({0: pos})
            outs.append(np.asarray(eng.decode(
                jnp.asarray([tok], jnp.int32), jnp.asarray([True]))))
        return eng.cache, outs

    cache_a, outs_a = run_steps()
    cache_b, outs_b = run_steps()
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(cache_a, cache_b):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert cache_a.k.dtype == jnp.bfloat16
    assert int(cache_a.lengths[0]) == 7  # 4 prompt + 3 decoded


# -- decode through the page indirection -------------------------------------

def _paged_teacher_forced(params, cfg, seq, free_order=None):
    """prefill(seq[:PROMPT]) then decode feeding the TRUE next tokens;
    returns logits rows aligned with positions PROMPT-1 .. S_TOTAL-1
    (page_size 8, so the 8-token prompt ends exactly at a page boundary
    only for the default PROMPT — boundary allocation and in-page
    appends both get exercised)."""
    eng = _engine(params, cfg, free_order=free_order)
    logits = eng.prefill(0, _ids(seq[0, :PROMPT]))
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
    want = full_logits(params, cfg, seq)[0, PROMPT - 1:]
    got = _paged_teacher_forced(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_paged_decode_bit_identical_across_page_placements():
    """Physical page placement is an allocator detail: the same request
    decoded through permuted free-list orders must produce
    BIT-IDENTICAL logits at every step (masked scores are exactly
    zeroed in the softmax, so unmapped/garbage pages contribute exactly
    0.0 — tolerance would hide a real leak)."""
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


# -- speculative verify -----------------------------------------------------

def _seq(cfg, n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0,
                              cfg.vocab_size)


def _verify_tokens(seq, k):
    """column 0 = the pending token, columns 1.. = drafts; slot 1 idle
    (its rows 0..k take garbage writes the masks never admit)."""
    return jnp.concatenate(
        [seq[:, PROMPT:], jnp.zeros((1, k + 1), jnp.int32)], axis=0)


@pytest.mark.parametrize("k", [1, 3])
def test_paged_verify_matches_full_forward(k):
    """The k+1-position verify forward is exact: row j equals the full
    forward's logits after reading seq[: PROMPT + j + 1] — the verify
    step is a prefill-shaped continuation, not an approximation
    (page_size 8 with PROMPT 8: the verify window starts ON a page
    boundary, so prepare_decode's n_new-row allocation is
    load-bearing)."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    eng = _engine(params, cfg, spec_k=k)
    eng.prefill(0, _ids(seq[0, :PROMPT]))
    assert eng.prepare_decode({0: PROMPT}, n_new=k + 1) == []
    logits = eng.verify(_verify_tokens(seq, k))
    want = full_logits(params, cfg, seq)[0, PROMPT:]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               **TOL)
    # lengths are committed by the HOST after the accept walk, never by
    # the verify step itself
    assert int(eng.cache.lengths[0]) == PROMPT


@pytest.mark.parametrize("use_rope", [True, False],
                         ids=["rope", "learned_pos"])
def test_verify_at_ragged_positions_across_page_boundaries(use_rope):
    """Three slots verify at once from lengths 3, 6 and 13 over pages
    of 4: every window (rows 3..6, 6..9, 13..16) starts inside one page
    and ends in the next, which the step has to have been given
    (``prepare_decode(n_new=k1)``). Row j of every slot is its own full
    forward's at that position."""
    k1, lens = 4, [3, 6, 13]
    cfg = _cfg(use_rope)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seqs = _ragged(cfg, lens, k1, seed=2)
    eng = _engine(params, cfg, num_slots=3, page_size=4, spec_k=k1 - 1)
    for i, (n, seq) in enumerate(zip(lens, seqs)):
        eng.prefill(i, _ids(seq[:n]))
    assert eng.prepare_decode(dict(enumerate(lens)), n_new=k1) == []
    logits = np.asarray(eng.verify(jnp.asarray(
        np.stack([seq[n:] for n, seq in zip(lens, seqs)]))))
    for i, (n, seq) in enumerate(zip(lens, seqs)):
        np.testing.assert_allclose(
            logits[i], teacher_forced(params, cfg, seq, range(n, n + k1)),
            **TOL)


@pytest.mark.parametrize("page_size", [8, 4],
                         ids=["on_boundary", "crossing"])
def test_verify_rejected_rows_not_observable(page_size):
    """The rollback contract, bitwise: two runs whose first verify step
    carried DIFFERENT garbage draft tails (all rejected — only the
    pending token commits) must produce a bit-identical next verify
    step AND a bit-identical next plain-decode step. Rejected rows are
    written, but every later mask either re-writes them first (verify:
    the new window covers the stale range) or never admits them (plain:
    masked in the scores, zeroed in the values) — tolerance here would
    hide a real leak. With pages of 8 the windows open a page, with
    pages of 4 they cross into one."""
    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 2)
    start = PROMPT - (page_size == 4) * 2       # 8, or 6: mid-page

    def run(garbage):
        eng = _engine(params, cfg, num_slots=1, page_size=page_size,
                      spec_k=k)
        eng.prefill(0, _ids(seq[0, :start]))
        eng.prepare_decode({0: start}, n_new=k + 1)
        bad = jnp.concatenate(
            [seq[:, start:start + 1],
             jnp.full((1, k), garbage, jnp.int32)], axis=1)
        eng.verify(bad)
        eng.commit([1])  # accept only the pending token
        eng.prepare_decode({0: start + 1}, n_new=k + 1)
        l_verify = eng.verify(seq[:, start + 1:start + k + 2])
        eng.commit([1])
        eng.prepare_decode({0: start + 2})
        l_plain = eng.decode(seq[:, start + 2], jnp.asarray([True]))
        return np.asarray(l_verify), np.asarray(l_plain)

    va, pa = run(3)
    vb, pb = run(499)
    np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(pa, pb)


def test_verify_agrees_with_plain_decode_steps():
    """Feeding the verify window one token at a time through plain
    decode must land on the same logits to tight fp32 tolerance (not
    bitwise: the two are differently shaped reductions, decode's the
    paged-attention kernel — the stream bit-identity contract lives at
    the sampled-token level, see test_scheduler.py)."""
    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    plain = np.asarray(_paged_teacher_forced(params, cfg, seq))[1:]

    eng = _engine(params, cfg, spec_k=k)
    eng.prefill(0, _ids(seq[0, :PROMPT]))
    eng.prepare_decode({0: PROMPT}, n_new=k + 1)
    logits = eng.verify(_verify_tokens(seq, k))
    np.testing.assert_allclose(np.asarray(logits[0]), plain,
                               rtol=1e-5, atol=1e-5)


def test_tp_verify_matches_unsharded():
    """tp=2 speculative verify: logits match the unsharded verify step
    to fp32 tolerance and the greedy accept walk commits the identical
    token prefix — the TP mesh composes with speculation unchanged."""
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import make_tp_paged_verify_fn
    from apex_tpu.transformer import parallel_state as ps

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    k = 2
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    tokens = _verify_tokens(seq, k)
    ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    model = GPTModel(cfg, tp_size=2)

    # an engine-built cache (block tables + pool) through both paths
    eng = _engine(params, cfg, spec_k=k)
    eng.prefill(0, _ids(seq[0, :PROMPT]))
    eng.prepare_decode({0: PROMPT}, n_new=k + 1)
    eng.sync_table()    # the clone is launched by hand: upload first
    clone = jax.tree.map(jnp.copy, eng.cache)
    want = eng.verify(tokens)
    _, got = make_tp_paged_verify_fn(model)(params, clone, tokens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               **TOL)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got[0], -1)),
                                  np.asarray(jnp.argmax(want[0], -1)))
    np.testing.assert_allclose(
        np.asarray(got[0]),
        np.asarray(full_logits(params, cfg, seq)[0, PROMPT:]), **TOL)


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
    k = 3
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + k + 1)
    tokens = _verify_tokens(seq, k)
    depth, anc = _chain_tree(k + 1)

    def engine():
        eng = _engine(params, cfg, spec_k=k, tree_spec=True)
        eng.prefill(0, _ids(seq[0, :PROMPT]))
        eng.prepare_decode({0: PROMPT}, n_new=k + 1)
        return eng

    a, b = engine(), engine()
    want = a.verify(tokens)
    got = b.tree_verify(tokens, jnp.broadcast_to(depth, (2, k + 1)),
                        jnp.broadcast_to(anc, (2, k + 1, k + 1)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for x, y in zip(a.cache, b.cache):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


#: root R with children A and B, A with child C; and a chain of three
_TREES = [([101, 202, 303], [-1, -1, 0]), ([11, 22, 33], [-1, 0, 1])]


def _paths(root, tree):
    """column of the grid -> its root-to-node token path."""
    toks, parents = tree
    paths = {0: [root]}
    for j, (t, p) in enumerate(zip(toks, parents), start=1):
        paths[j] = paths[p + 1] + [t]
    return paths


def test_tree_verify_branches_match_full_forward():
    """A two-branch tree: root R with children A and B, A with child C.
    Each node's logits row must equal the full forward over prompt +
    its OWN ancestor path — sibling branches never contaminate each
    other even though their K/V rows coexist in the window."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + 1)
    root = int(seq[0, PROMPT])
    toks, depth, anc, valid, parents, start = tree_arrays(
        [[root]], [_TREES[0]], k1=4)
    assert list(parents[0]) == [-1, 0, 0, 1]
    eng = _engine(params, cfg, num_slots=1, spec_k=3, tree_spec=True)
    eng.prefill(0, _ids(seq[0, :PROMPT]))
    eng.prepare_decode({0: PROMPT}, n_new=4)
    logits = np.asarray(eng.tree_verify(
        jnp.asarray(toks), jnp.asarray(depth), jnp.asarray(anc))[0])
    # column j of the grid == last row of the full forward over the
    # prompt + j's root-to-node path
    for col, path in _paths(root, _TREES[0]).items():
        full = _ids(seq[0, :PROMPT]) + path
        np.testing.assert_allclose(
            logits[col],
            teacher_forced(params, cfg, full, [len(full) - 1])[0], **TOL)
    # and the sibling branches really did diverge
    assert (np.argmax(logits[1]) != np.argmax(logits[2])
            or not np.allclose(logits[1], logits[2]))


def test_tree_verify_at_ragged_positions_across_page_boundaries():
    """The tree mask composes with the page indirection: two slots, a
    branching tree from length 3 and a chain from length 6 over pages
    of 4, so both grids' rows (3..6, 6..9) cross into a further page.
    Every node equals its own path's full forward."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    lens = [3, 6]
    seqs = _ragged(cfg, lens, 1, seed=4)
    roots = [int(seq[n]) for n, seq in zip(lens, seqs)]
    toks, depth, anc, _, _, _ = tree_arrays(
        [[r] for r in roots], _TREES, k1=4)
    eng = _engine(params, cfg, page_size=4, spec_k=3, tree_spec=True)
    for i, (n, seq) in enumerate(zip(lens, seqs)):
        eng.prefill(i, _ids(seq[:n]))
    assert eng.prepare_decode(dict(enumerate(lens)), n_new=4) == []
    logits = np.asarray(eng.tree_verify(
        jnp.asarray(toks), jnp.asarray(depth), jnp.asarray(anc)))
    for i, (n, seq) in enumerate(zip(lens, seqs)):
        for col, path in _paths(roots[i], _TREES[i]).items():
            full = _ids(seq[:n]) + path
            np.testing.assert_allclose(
                logits[i, col],
                teacher_forced(params, cfg, full, [len(full) - 1])[0],
                **TOL)


def test_tp_tree_verify_matches_unsharded():
    """tp=2 tree verify: the tree descriptors are replicated host
    decisions, heads shard over ``model`` — logits match the unsharded
    tree verify to fp32 tolerance with exact argmax agreement,
    mirroring test_tp_verify_matches_unsharded."""
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import make_tp_paged_tree_verify_fn
    from apex_tpu.transformer import parallel_state as ps

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    seq = _seq(cfg, PROMPT + 1)
    root = int(seq[0, PROMPT])
    toks, depth, anc, _, _, _ = tree_arrays([[root], [root]], _TREES, k1=4)
    toks, depth, anc = (jnp.asarray(toks), jnp.asarray(depth),
                        jnp.asarray(anc))
    ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    model = GPTModel(cfg, tp_size=2)

    eng = _engine(params, cfg, spec_k=3, tree_spec=True)
    for slot in (0, 1):
        eng.prefill(slot, _ids(seq[0, :PROMPT]))
    eng.prepare_decode({0: PROMPT, 1: PROMPT}, n_new=4)
    eng.sync_table()    # the clone is launched by hand: upload first
    clone = jax.tree.map(jnp.copy, eng.cache)
    want = eng.tree_verify(toks, depth, anc)
    _, got = make_tp_paged_tree_verify_fn(model)(params, clone, toks,
                                                 depth, anc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(got, -1)),
                                  np.asarray(jnp.argmax(want, -1)))


# -- chunked prefill ----------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 8])
def test_chunk_prefill_rows_match_full_forward(chunk):
    """Every chunk of a 13-token prompt (pages of 4; the last chunk
    padded) returns the full forward's row at the chunk's last real
    token: a chunk attends the chunks before it through the pool at its
    absolute positions, whichever pages they crossed."""
    cfg = _cfg(True)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    prompt = _ragged(cfg, [13], 0, seed=5)[0]
    want = teacher_forced(params, cfg, prompt, range(len(prompt)))
    eng = _engine(params, cfg, num_slots=1, page_size=4)
    state = eng.begin_chunk_prefill(0, _ids(prompt))
    pos = int(state["start"])
    assert pos == 0     # nothing cached: no page is skipped
    while pos < len(prompt):
        part = _ids(prompt[pos:pos + chunk])
        final = pos + chunk >= len(prompt)
        logits = eng.chunk_prefill(0, part, pos, state, chunk, final)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   want[pos + len(part) - 1], **TOL)
        pos += chunk
    eng.finish_chunk_prefill(0, state)
    eng.check_invariants()
    assert int(eng.cache.lengths[0]) == len(prompt)


# -- what the cache and the engine refuse -------------------------------------

def test_init_paged_cache_validates():
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
