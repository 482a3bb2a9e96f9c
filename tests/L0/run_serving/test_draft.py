"""ngram_draft: the host-side prompt-lookup drafter (pure function of
the token history — exact-value tests, no device work), plus the
tree-speculation grid packer ``tree_arrays``, the grid accept walk
``tree_speculative_accept``, and the lockstep ``DraftModel``."""

from apex_tpu.serving import ngram_draft


def test_repeating_pattern_continues():
    # suffix [1, 2] last occurred at index 0; the continuation is
    # [3, 1, 2] — the draft that makes a period-3 loop free to decode
    assert ngram_draft([1, 2, 3, 1, 2], 3) == [3, 1, 2]


def test_longest_suffix_wins():
    # the trigram suffix [1, 2, 3] recurs (continuation 9) and so does
    # the bigram [2, 3] (a later occurrence continues with 5); longer
    # evidence must win over recency at a shorter length
    hist = [1, 2, 3, 9, 2, 3, 5, 1, 2, 3]
    assert ngram_draft(hist, 1) == [9]


def test_recency_breaks_ties_within_a_length():
    # [2, 3] occurs twice with different continuations; the MOST RECENT
    # earlier occurrence (-> 5) is the draft, not the first (-> 9)
    hist = [2, 3, 9, 2, 3, 5, 2, 3]
    assert ngram_draft(hist, 1, max_ngram=2) == [5]


def test_terminal_self_match_excluded():
    # every suffix of [1, 2, 3] occurs only once (at the end): a
    # drafter that matched the suffix against itself would return
    # garbage here instead of the honest empty draft
    assert ngram_draft([1, 2, 3], 3) == []


def test_no_recurrence_returns_empty():
    assert ngram_draft([1, 2, 3, 4, 5, 6], 4) == []


def test_short_and_empty_history():
    assert ngram_draft([], 3) == []
    assert ngram_draft([7], 3) == []  # nothing before the 1-gram suffix


def test_draft_truncated_at_history_end():
    # the match sits one token from the end: only one continuation
    # token exists, and the drafter must return the short draft rather
    # than pad or over-read
    assert ngram_draft([5, 9, 5], 4) == [9, 5]


def test_k_bounds():
    hist = [1, 2, 3, 1, 2]
    assert ngram_draft(hist, 0) == []
    assert ngram_draft(hist, -1) == []
    assert ngram_draft(hist, 2) == [3, 1]


def test_ngram_window_bounds():
    hist = [1, 2, 3, 1, 2]
    assert ngram_draft(hist, 3, max_ngram=0) == []
    assert ngram_draft(hist, 3, min_ngram=0) == []
    # min_ngram above any recurring length -> empty
    assert ngram_draft([9, 1, 2, 3, 1, 2, 3], 2, min_ngram=3,
                       max_ngram=3) == [1, 2]


# -- tree_arrays: the verify-grid packer -------------------------------------

def test_tree_arrays_packs_forced_chain_and_tree():
    import numpy as np

    from apex_tpu.serving import tree_arrays

    # slot 0: forced chain [9, 8] (f=2, root col 1), tree = root child A
    #         with children B (chain) — cols 2, 3
    # slot 1: forced [5] only (plain re-send, no tree)
    toks, depth, anc, valid, parents, start = tree_arrays(
        [[9, 8], [5]], [([4, 6], [-1, 0]), None], k1=4)
    assert toks.tolist() == [[9, 8, 4, 6], [5, 0, 0, 0]]
    assert depth.tolist() == [[0, 1, 2, 3], [0, 0, 0, 0]]
    assert valid.tolist() == [[False, False, True, True],
                              [False, False, False, False]]
    assert parents.tolist() == [[-1, 0, 1, 2], [-1, -1, -1, -1]]
    assert start.tolist() == [1, 0]
    # ancestor sets: col 3 sees the whole chain, pads see only self
    assert anc[0, :, 3].tolist() == [True, True, True, True]
    assert anc[0, :, 0].tolist() == [True, False, False, False]
    assert anc[1, :, 1].tolist() == [False, True, False, False]
    # branching: two children of the same root get disjoint subtrees
    t2, d2, a2, v2, p2, s2 = tree_arrays(
        [[7]], [([1, 2, 3], [-1, -1, 0])], k1=4)
    assert p2.tolist() == [[-1, 0, 0, 1]]
    assert d2.tolist() == [[0, 1, 1, 2]]
    assert not a2[0, 2, 3] and a2[0, 1, 3]  # C under A, not under B


def test_tree_arrays_validates():
    import pytest

    from apex_tpu.serving import tree_arrays

    with pytest.raises(ValueError, match="pending"):
        tree_arrays([[]], [None], k1=2)
    with pytest.raises(ValueError, match="exceeds grid"):
        tree_arrays([[1, 2]], [([3, 4, 5], [-1, 0, 1])], k1=4)
    with pytest.raises(ValueError, match="earlier node"):
        tree_arrays([[1]], [([3, 4], [-1, 5])], k1=4)


# -- tree_speculative_accept: the grid walk ----------------------------------

def test_tree_accept_walks_matching_branch():
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.serving import tree_arrays, tree_speculative_accept

    # grid: root=7 (col 0), children A=4 (col 1), B=5 (col 2), A's
    # child C=6 (col 3)
    toks, depth, anc, valid, parents, start = tree_arrays(
        [[7]], [([4, 5, 6], [-1, -1, 0])], k1=4)
    V = 16

    def grid(samples_by_col):
        g = np.zeros((1, 4), np.int32)
        for col, s in samples_by_col.items():
            g[0, col] = s
        return jnp.asarray(g)

    args = (jnp.asarray(toks), jnp.asarray(parents), jnp.asarray(valid),
            jnp.asarray(start))
    # root samples B (5) -> hop to col 2; col 2 samples something with
    # no matching child -> stop. Commits: root sample + B's sample.
    cnt, path = tree_speculative_accept(grid({0: 5, 2: 9}), *args)
    assert cnt.tolist() == [2]
    assert path[0, :2].tolist() == [0, 2]
    # root samples A (4) -> col 1; col 1 samples C (6) -> col 3; stop
    cnt, path = tree_speculative_accept(grid({0: 4, 1: 6, 3: 11}), *args)
    assert cnt.tolist() == [3]
    assert path[0, :3].tolist() == [0, 1, 3]
    # root samples neither child -> only the root's sample commits
    cnt, path = tree_speculative_accept(grid({0: 9}), *args)
    assert cnt.tolist() == [1]
    assert path[0, :1].tolist() == [0]


# -- DraftModel: lockstep greedy drafting ------------------------------------

def _draft_setup():
    import dataclasses

    import jax

    from apex_tpu.models.gpt import gpt_tiny, init_gpt
    from apex_tpu.serving import DraftModel

    cfg = dataclasses.replace(gpt_tiny(), use_rope=True,
                              hidden_dropout=0.0)
    params = init_gpt(jax.random.PRNGKey(0), cfg)
    return cfg, params, DraftModel(params, cfg, num_slots=2, max_len=32)


def _greedy_reference(params, cfg, history, k):
    """k greedy continuations of ``history`` via the model's own full
    forward — what DraftModel must reproduce through its incremental
    cache."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import apply_gpt_unsharded

    toks = list(history)
    out = []
    for _ in range(k):
        h = apply_gpt_unsharded(params, cfg,
                                jnp.asarray([toks], jnp.int32))
        table = params["embedding"]["word"]["embedding"]
        logits = jnp.dot(h[0, -1], table.T)
        nxt = int(jnp.argmax(logits))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_draft_model_matches_greedy_reference():
    cfg, params, dm = _draft_setup()
    h0 = [5, 9, 3, 7]
    h1 = [11, 13, 2]
    chains = dm.draft([h0, h1], [3, 2])
    assert chains[0] == _greedy_reference(params, cfg, h0, 3)
    assert chains[1] == _greedy_reference(params, cfg, h1, 2)


def test_draft_model_resyncs_after_rejection():
    """After a partial accept the target's history DIVERGES from what
    the draft cache saw; the next draft call must roll back to the
    common prefix and still match the from-scratch greedy reference."""
    cfg, params, dm = _draft_setup()
    h = [5, 9, 3, 7]
    first = dm.draft([h, None], [3, 0])[0]
    # target accepted one draft token then resampled a different one
    h2 = h + [first[0], (first[1] + 1) % cfg.vocab_size]
    second = dm.draft([h2, None], [3, 0])[0]
    assert second == _greedy_reference(params, cfg, h2, 3)


def test_draft_model_free_slot_clears_state():
    cfg, params, dm = _draft_setup()
    a = dm.draft([[5, 9, 3], None], [2, 0])[0]
    dm.free_slot(0)
    # a different request in the recycled slot must not inherit rows
    b = dm.draft([[7, 11], None], [2, 0])[0]
    assert b == _greedy_reference(params, cfg, [7, 11], 2)
    dm.free_slot(0)
    assert dm.draft([[5, 9, 3], None], [2, 0])[0] == a


def test_draft_model_tree_adds_second_best_root():
    """draft_tree spends its k-node budget as a greedy chain of k - 1
    plus the second-best first token as an alternative root child —
    two DISTINCT children of the walk root."""
    cfg, params, dm = _draft_setup()
    toks, parents = dm.draft_tree([[5, 9, 3, 7], None], [3, 0])[0]
    assert len(toks) == 3
    assert toks[:2] == _greedy_reference(params, cfg, [5, 9, 3, 7], 2)
    assert parents == [-1, 0, -1]  # chain + the alternative root
    assert toks[2] != toks[0]  # genuinely second-best, not a duplicate


# -- the drafter's private cache: one pool, an identity table ----------------

def _identity_table(dm):
    import numpy as np

    from apex_tpu.serving.cache import RESERVED_PAGES

    pages = dm.cache.block_tables.shape[1]
    return RESERVED_PAGES + np.arange(dm.num_slots * pages).reshape(
        dm.num_slots, pages)


def test_draft_model_table_is_the_identity_and_never_changes():
    """No ``PagePool``, nothing shared, no host table traffic: logical
    page j of slot i is physical page RESERVED + i * P + j when the
    drafter is built and after drafts, a roll-back and a freed slot; no
    slot maps the null or the scratch page, and no two slots a page."""
    import numpy as np

    cfg, params, dm = _draft_setup()
    want = _identity_table(dm)
    assert dm.cache.block_tables.shape == (2, 3)    # 32 + 5 rows, pages of 16
    assert dm.cache.k.shape[1] == want.max() + 1    # and no page more
    np.testing.assert_array_equal(np.asarray(dm.cache.block_tables), want)
    first = dm.draft([[5, 9, 3, 7], [11, 13, 2]], [3, 2])
    dm.draft([[5, 9, 3, 7, first[0][0], 1], [11, 13, 2, first[1][0]]],
             [3, 2])
    dm.free_slot(1)
    dm.draft([[5, 9, 3, 7, first[0][0], 1, 4], None], [2, 0])
    np.testing.assert_array_equal(np.asarray(dm.cache.block_tables), want)
    assert len(set(want.ravel())) == want.size


def test_draft_model_rolls_back_a_rejected_tail():
    """Two drafters reach the same committed history, one of them
    through a draft whose tail the target rejected (its rows stand in
    the pool past the common prefix): the rolled-back rows are
    overwritten before any mask admits them, so both give the root
    distribution of that history and the same next chains."""
    import numpy as np

    cfg, params, dm = _draft_setup()
    _, _, fresh = _draft_setup()
    h = [5, 9, 3, 7, 2, 8]
    tail = dm.draft([h, None], [4, 0])[0]
    # one draft accepted, then a token the drafter did not propose
    h2 = h + [tail[0], (tail[1] + 1) % cfg.vocab_size]
    assert len(dm._tokens[0]) == len(h) + 3    # h and three fed drafts
    rolled = dm._sync([h2, None])
    assert dm._tokens[0] == h2                  # the tail is forgotten
    np.testing.assert_allclose(rolled[0], fresh._sync([h2, None])[0],
                               rtol=1e-2, atol=1e-2)   # a bfloat16 pool
    assert dm.draft([h2, None], [3, 0]) == fresh.draft([h2, None], [3, 0])


def test_draft_model_resyncs_after_a_skipped_tick():
    """A tick whose ``draft_exec`` site fired never calls the drafter
    while the target goes on committing: the next call finds a backlog
    longer than one chunk (7 tokens, chunks of 5: two rounds for one
    slot, one for the other) and still matches the greedy reference."""
    cfg, params, dm = _draft_setup()
    h0, h1 = [5, 9, 3, 7], [11, 13, 2]
    dm.draft([h0, h1], [3, 2])
    h0 = h0 + [21, 22, 23, 24, 25, 26, 27]      # committed meanwhile
    h1 = h1 + [31]
    chains = dm.draft([h0, h1], [3, 2])
    assert chains[0] == _greedy_reference(params, cfg, h0, 3)
    assert chains[1] == _greedy_reference(params, cfg, h1, 2)
    assert dm._tokens[0][:len(h0)] == h0


def test_draft_model_full_row_stays_in_its_own_pages():
    """A slot drafting from a history of ``max_len`` tokens writes its
    chain into the chunk's slack at the end of ITS pages; its neighbour
    drafts what it drafts alone."""
    cfg, params, dm = _draft_setup()
    _, _, alone = _draft_setup()
    full = [(7 * i + 3) % cfg.vocab_size for i in range(32)]
    other = [11, 13, 2]
    want = alone.draft([None, other], [0, 2])[1]
    got = dm.draft([full, other], [3, 2])
    assert got[1] == want
    assert got[0] == _greedy_reference(params, cfg, full, 3)
    assert dm.draft([full, other + want[:1]], [3, 2])[1] == \
        alone.draft([None, other + want[:1]], [0, 2])[1]


def test_draft_model_tp_twin_drafts_the_same():
    """``model=GPTModel(cfg, 2)`` runs the drafter's two forwards under
    ``shard_map`` over the target's mesh (the pool's head axis sharded,
    the identity table replicated): the chains are the unsharded
    drafter's."""
    import jax
    import pytest

    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving import DraftModel
    from apex_tpu.transformer import parallel_state as ps

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg, params, dm = _draft_setup()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    tp = DraftModel(params, cfg, num_slots=2, max_len=32,
                    model=GPTModel(cfg, tp_size=2), mesh=mesh)
    hists, ks = [[5, 9, 3, 7], [11, 13, 2]], [3, 2]
    want = dm.draft(hists, ks)
    assert tp.draft(hists, ks) == want
    hists = [h + c[:1] + [4] for h, c in zip(hists, want)]
    assert tp.draft(hists, ks) == dm.draft(hists, ks)
