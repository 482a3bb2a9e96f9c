"""Learned-sparse attention's selection over a paged pool
(``transformer.functional.sparse_index``): the index kernel (interpreted)
against plain ``jax.numpy``; who is scored and who is not; the exact pick;
the gather of the picked rows and the tail; the whole decode path (index,
top-k, gather, ``mla_decode_attention`` at a row with no roped part) against
a masked softmax; the pooled key as the cache keeps it; the seam's write of
the key a token closes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import sparse_index as si
from apex_tpu.transformer.functional.mla_attention import mla_decode_attention

POOL, WIDTH, HEADS = 4, 16, 2
NEG = float(np.finfo(np.float32).min)


def made(pages_a_slot, keys_a_page, slots=3, seed=0, latent=128):
    """A pool of latents, the indexer's cache beside it and block tables
    that scatter the slots' pages."""
    rng = np.random.RandomState(seed)
    page = keys_a_page * POOL
    pages = 2 + slots * pages_a_slot
    rows = jnp.asarray(rng.randn(1, pages, keys_a_page, WIDTH), jnp.float32)
    pool = jnp.asarray(rng.randn(1, pages, page, latent), jnp.float32)
    bt = jnp.asarray(2 + rng.permutation(slots * pages_a_slot).reshape(
        slots, pages_a_slot), jnp.int32)
    q = jnp.asarray(rng.randn(slots, HEADS, WIDTH), jnp.float32)
    w = jnp.asarray(rng.randn(slots, HEADS), jnp.float32)
    return rows, pool, bt, q, w, page


def plain_scores(q, w, rows, bt, groups):
    keys = np.asarray(rows)[0][np.asarray(bt)].reshape(
        bt.shape[0], -1, rows.shape[3])
    s = np.einsum("bhd,bgd->bhg", np.asarray(q, np.float64), keys)
    score = (np.maximum(s, 0) * np.asarray(w)[..., None]).sum(1)
    return np.where(np.arange(keys.shape[1])[None] < np.asarray(
        groups)[:, None], score, NEG)


@pytest.mark.parametrize("keys_a_page", [1, 2])
@pytest.mark.parametrize("pages_a_slot", [1, 2, 5])
def test_the_index_kernel_scores_what_plain_numpy_scores(pages_a_slot,
                                                        keys_a_page):
    rows, _, bt, q, w, _ = made(pages_a_slot, keys_a_page)
    most = pages_a_slot * keys_a_page
    groups = jnp.asarray([most, max(most - 1, 0), most // 2], jnp.int32)
    got = si.index_scores(q, w, rows, bt, groups, jnp.int32(0))
    assert got.shape == (3, most)
    np.testing.assert_allclose(got, plain_scores(q, w, rows, bt, groups),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        si.index_scores_reference(q, w, rows, bt, groups, 0), got,
        rtol=1e-5, atol=1e-5)


def test_an_inactive_slot_is_scored_nowhere_and_reads_no_page():
    rows, _, bt, q, w, _ = made(3, 2)
    # a table row of page ids past the cache: dereferenced, it would raise
    # (interpreted) or read rubbish; with no whole group it is not
    bt = bt.at[1].set(10 ** 6)
    groups = jnp.asarray([6, 0, 3], jnp.int32)
    got = np.asarray(si.index_scores(q, w, rows, bt, groups, jnp.int32(0)))
    assert (got[1] == NEG).all() and (got[0] > NEG).all()
    assert (got[2, :3] > NEG).all() and (got[2, 3:] == NEG).all()


def test_fewer_whole_groups_than_the_top_k_are_all_picked_and_counted():
    rows, _, bt, q, w, _ = made(5, 2)
    groups = jnp.asarray([10, 3, 0], jnp.int32)
    scores = si.index_scores(q, w, rows, bt, groups, jnp.int32(0))
    picked, count = si.pick_groups(scores, groups, 4)
    assert count.tolist() == [4, 3, 0] and picked.shape == (3, 4)
    assert sorted(np.asarray(picked)[1, :3].tolist()) == [0, 1, 2]
    best = np.argsort(-np.asarray(scores)[0], kind="stable")[:4]
    assert np.asarray(picked)[0].tolist() == best.tolist()
    # a top-k wider than the cache has groups: all of them
    picked, count = si.pick_groups(scores, groups, 64)
    assert picked.shape == (3, 10) and count.tolist() == [10, 3, 0]


@pytest.mark.parametrize("tail", [1, 2, 3, 4])
def test_the_tail_is_always_gathered_and_never_scored(tail):
    """A query at ``pos`` attends its own group up to itself: ``tail - 1``
    rows out of the pool (the token's own row comes in beside them) behind
    the picked groups' rows; that group has no score."""
    rows, pool, bt, q, w, page = made(5, 2)
    pos = jnp.asarray([4 * 7 + tail - 1, 4 * 2 + tail - 1, 0], jnp.int32)
    groups = pos // POOL
    scores = np.asarray(si.index_scores(q, w, rows, bt, groups, jnp.int32(0)))
    assert (scores[0, 7:] == NEG).all() and (scores[1, 2:] == NEG).all()
    picked, count = si.pick_groups(jnp.asarray(scores), groups, 3)
    buffer, table, length = si.gather_picked(pool, 0, bt, picked, count, pos,
                                             POOL)
    assert length.tolist() == [3 * 4 + tail - 1, 2 * 4 + tail - 1, 0]
    assert buffer.shape[2] == page and table.shape[0] == 3
    flat = np.asarray(pool)[0][np.asarray(bt)].reshape(3, -1, pool.shape[-1])
    got = np.asarray(buffer).reshape(3, -1, pool.shape[-1])
    for slot in (0, 1):
        want = [flat[slot, 4 * g + j] for g in np.asarray(picked)[
            slot, :int(count[slot])] for j in range(4)]
        want += [flat[slot, 4 * int(groups[slot]) + j]
                 for j in range(tail - 1)]
        np.testing.assert_array_equal(got[slot, :int(length[slot])],
                                      np.stack(want) if want else
                                      np.zeros((0, pool.shape[-1])))
    # an identity table: a slot's pages of the buffer, in order
    assert np.asarray(table).reshape(-1).tolist() == list(range(table.size))


@pytest.mark.parametrize("keys_a_page", [1, 2])
def test_index_pick_gather_and_attend_give_the_masked_softmax(keys_a_page):
    """The decode path of the sparse layer at a row with no roped part (key
    width = value width) against a softmax over exactly the positions the
    rule names: the best groups' and the tail's."""
    rows, pool, bt, q, w, page = made(6, keys_a_page, latent=128)
    rng = np.random.RandomState(7)
    top = 3
    most = 6 * keys_a_page * POOL
    pos = jnp.asarray([most - 2, 9, 0], jnp.int32)
    groups = pos // POOL
    ql = jnp.asarray(rng.randn(3, 4, 128) * 0.2, jnp.float32)
    new = jnp.asarray(rng.randn(3, 128), jnp.float32)
    scores = si.index_scores(q, w, rows, bt, groups, jnp.int32(0))
    picked, count = si.pick_groups(scores, groups, top)
    buffer, table, length = si.gather_picked(pool, 0, bt, picked, count, pos,
                                             POOL)
    got = mla_decode_attention(ql, new, buffer, table, length, jnp.int32(0),
                               value_width=128)
    flat = np.asarray(pool)[0][np.asarray(bt)].reshape(3, -1, 128)
    plain = plain_scores(q, w, rows, bt, groups)
    for slot in range(3):
        t, g_own = int(pos[slot]), int(groups[slot])
        best = np.argsort(-plain[slot], kind="stable")[:min(top, g_own)]
        at = sorted({4 * g + j for g in best for j in range(4)}
                    | set(range(4 * g_own, t)))
        keys = np.concatenate([flat[slot, at], np.asarray(new)[slot][None]])
        s = np.asarray(ql, np.float64)[slot] @ keys.T
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ keys
        np.testing.assert_allclose(got[slot], want, rtol=2e-5, atol=2e-5)


def test_the_pooled_key_is_the_float32_mean_rounded_once():
    """What the prompt path keeps of a group is the mean of its four roped
    keys, taken in float32 and rounded to the cache's dtype ONCE (not a mean
    of rounded keys, not a rounded running sum)."""
    from apex_tpu.models import glm_next
    from apex_tpu.models.nemotron_h import _rms

    cfg = glm_next.glm_next_tiny()
    params = glm_next.init(jax.random.PRNGKey(1), cfg)
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (24, cfg.hidden_size))
    held = glm_next._held_of_a_prompt(cfg, 24, jnp.bfloat16)
    out = glm_next.dsa_mix_prefill(lp, x, cfg, jnp.bfloat16, held,
                                   jnp.int32(0))
    ik = glm_next._dsa_in(lp, _rms(lp["norm"], x, cfg.rms_norm_eps), cfg,
                          jnp.arange(24))[3]
    want = np.asarray(ik, np.float32).reshape(6, 4, -1).mean(1)
    got = np.asarray(out[1]["keys"].astype(jnp.float32))
    np.testing.assert_array_equal(
        got, np.asarray(jnp.asarray(want).astype(jnp.bfloat16).astype(
            jnp.float32)))
    twice = np.asarray(jnp.asarray(np.asarray(ik.astype(
        jnp.bfloat16).astype(jnp.float32)).reshape(6, 4, -1).mean(1)).astype(
            jnp.bfloat16).astype(jnp.float32))
    assert (got != twice).any()


def test_a_token_that_closes_a_group_writes_its_key_and_no_other_does():
    """The seam's write (``serving.decode._write_index_keys``): the slot
    whose token is the last of its group puts the pooled key at that group's
    place in the page its block table names; the others, and an inactive
    one, write to the scratch page alone."""
    from apex_tpu.serving.cache import SCRATCH_PAGE, HybridKVCache
    from apex_tpu.serving.decode import _write_index_keys

    slots, page, keys_a_page = 4, 8, 2
    bt = jnp.asarray([[5, 6], [7, 8], [9, 10], [11, 12]], jnp.int32)
    pos = jnp.asarray([11, 7, 6, 15], jnp.int32)    # closes: 11, 7, -, 15
    active = jnp.asarray([True, True, True, False])
    cache = HybridKVCache(
        k=jnp.zeros((1, 14, page, 128)), v=None, lengths=pos, block_tables=bt,
        state=jnp.zeros((1,)), conv=jnp.zeros((1,)), counters=None,
        index={"rows": jnp.zeros((1, 14, keys_a_page, WIDTH)),
               "tail": jnp.zeros((1, slots, POOL - 1, WIDTH))})
    keys = jnp.arange(1, slots + 1, dtype=jnp.float32)[None, :, None] \
        * jnp.ones((1, slots, WIDTH))
    tail = jnp.full((1, slots, POOL - 1, WIDTH), 9.0)
    new = _write_index_keys(cache, keys, tail, active)
    rows = np.asarray(new["rows"])[0]
    assert (rows[6, 0] == 1).all()      # slot 0: position 11, page 1, key 0
    assert (rows[7, 1] == 2).all()      # slot 1: position 7, page 0, key 1
    written = {(p, k) for p in range(14) for k in range(keys_a_page)
               if rows[p, k].any()}
    assert written - {(SCRATCH_PAGE, 0), (SCRATCH_PAGE, 1)} \
        == {(6, 0), (7, 1)}
    assert new["tail"] is tail
