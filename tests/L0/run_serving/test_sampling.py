"""sample_tokens: greedy/temperature selection, top-k / top-p support
restriction, determinism under explicit PRNG keys — and the
speculative grid/accept helpers that reuse the same sampler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import (finite_rows, sample_stream,
                              sample_stream_checked, sample_stream_grid,
                              sample_stream_grid_checked, sample_token_grid,
                              sample_tokens, speculative_accept)

V = 64


def _logits(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, V),
                             jnp.float32)


def _keys(n, seed=7):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def test_greedy_is_argmax():
    logits = _logits(4)
    out = sample_tokens(logits, _keys(4), jnp.zeros((4,), jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, -1)))


def test_greedy_ignores_keys():
    logits = _logits(3)
    temps = jnp.zeros((3,), jnp.float32)
    a = sample_tokens(logits, _keys(3, seed=1), temps)
    b = sample_tokens(logits, _keys(3, seed=2), temps)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sampling_deterministic_per_key():
    logits = _logits(4)
    temps = jnp.full((4,), 0.9, jnp.float32)
    a = sample_tokens(logits, _keys(4), temps)
    b = sample_tokens(logits, _keys(4), temps)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = sample_tokens(logits, _keys(4, seed=99), temps)
    assert (np.asarray(a) != np.asarray(c)).any()


def test_top_k_restricts_support():
    logits = _logits(8, seed=3)
    k = 5
    temps = jnp.full((8,), 1.3, jnp.float32)
    allowed = np.asarray(jnp.argsort(logits, -1)[:, -k:])
    for seed in range(4):
        out = np.asarray(sample_tokens(logits, _keys(8, seed=seed),
                                       temps, top_k=k))
        for i, tok in enumerate(out):
            assert tok in allowed[i]


def test_top_k_one_is_argmax():
    logits = _logits(4, seed=5)
    out = sample_tokens(logits, _keys(4), jnp.ones((4,), jnp.float32),
                        top_k=1)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, -1)))


def test_mixed_greedy_and_sampled_rows():
    logits = _logits(4, seed=6)
    temps = jnp.asarray([0.0, 1.0, 0.0, 1.0], jnp.float32)
    out = np.asarray(sample_tokens(logits, _keys(4), temps))
    greedy = np.asarray(jnp.argmax(logits, -1))
    assert out[0] == greedy[0] and out[2] == greedy[2]


# -- top-p (nucleus) --------------------------------------------------------

def _nucleus(logits, p):
    """Reference support: per row, the smallest set of top tokens whose
    softmax mass reaches p (the argmax always belongs)."""
    probs = np.asarray(jax.nn.softmax(logits, -1))
    order = np.argsort(-probs, axis=-1)
    allowed = []
    for r in range(probs.shape[0]):
        mass, keep = 0.0, []
        for tok in order[r]:
            keep.append(int(tok))
            mass += probs[r, tok]
            if mass >= p:
                break
        allowed.append(set(keep))
    return allowed


def test_top_p_restricts_support():
    logits = _logits(8, seed=3)
    p = 0.6
    temps = jnp.full((8,), 1.3, jnp.float32)
    allowed = _nucleus(logits, p)
    for seed in range(4):
        out = np.asarray(sample_tokens(logits, _keys(8, seed=seed),
                                       temps, top_p=p))
        for i, tok in enumerate(out):
            assert int(tok) in allowed[i]


def test_top_p_tiny_is_argmax():
    """A nucleus smaller than any single token's mass still keeps the
    argmax — the support can never be empty."""
    logits = _logits(4, seed=5)
    out = sample_tokens(logits, _keys(4), jnp.ones((4,), jnp.float32),
                        top_p=1e-6)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, -1)))


def test_top_p_off_values_are_full_vocab():
    """0 and 1 both mean "off": identical draws to the unrestricted
    sampler (bitwise — same keys, same program shape)."""
    logits = _logits(6, seed=8)
    temps = jnp.full((6,), 1.1, jnp.float32)
    base = np.asarray(sample_tokens(logits, _keys(6), temps))
    for p in (0.0, 1.0):
        np.testing.assert_array_equal(
            np.asarray(sample_tokens(logits, _keys(6), temps, top_p=p)),
            base)


def test_top_p_composes_with_top_k():
    """With both set, the support is the intersection (top-k applies
    first, nucleus prunes within it)."""
    logits = _logits(8, seed=9)
    k, p = 5, 0.7
    temps = jnp.full((8,), 1.3, jnp.float32)
    topk = np.asarray(jnp.argsort(logits, -1)[:, -k:])
    nuc = _nucleus(logits, p)
    for seed in range(4):
        out = np.asarray(sample_tokens(logits, _keys(8, seed=seed),
                                       temps, top_k=k, top_p=p))
        for i, tok in enumerate(out):
            assert int(tok) in topk[i] and int(tok) in nuc[i]


def test_top_p_does_not_disturb_greedy():
    logits = _logits(4, seed=2)
    out = sample_tokens(logits, _keys(4), jnp.zeros((4,), jnp.float32),
                        top_p=0.3)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, -1)))


# -- speculative grid + accept ----------------------------------------------

def test_sample_token_grid_matches_per_position_sampler():
    """Grid position (b, j) must draw exactly what sample_tokens draws
    for row b with key[b, j] — the property the speculative
    bit-identity contract stands on."""
    b, k1 = 3, 4
    logits = jax.random.normal(jax.random.PRNGKey(4), (b, k1, V),
                               jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(11), b * k1).reshape(
        b, k1, 2)
    temps = jnp.asarray([0.0, 0.9, 1.2], jnp.float32)
    grid = np.asarray(sample_token_grid(logits, keys, temps, top_p=0.9))
    for j in range(k1):
        col = np.asarray(sample_tokens(logits[:, j], keys[:, j], temps,
                                       top_p=0.9))
        np.testing.assert_array_equal(grid[:, j], col)


def test_speculative_accept_counts_matching_prefix():
    toks = jnp.asarray([[5, 6, 7, 9],    # full match
                        [5, 6, 7, 9],    # mismatch at j=1
                        [5, 6, 7, 9],    # match but draft_len caps at 2
                        [5, 6, 7, 9]],   # empty draft
                       jnp.int32)
    drafts = jnp.asarray([[5, 6, 7],
                          [5, 0, 7],
                          [5, 6, 7],
                          [0, 0, 0]], jnp.int32)
    lens = jnp.asarray([3, 3, 2, 0], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(speculative_accept(toks, drafts, lens)),
        [3, 1, 2, 0])


def test_speculative_accept_pad_positions_never_match():
    """0-padded draft tails must not count as accepts even when the
    sampled token happens to be 0 (the pad value)."""
    toks = jnp.asarray([[0, 0]], jnp.int32)
    drafts = jnp.asarray([[0, 0]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(speculative_accept(
            toks, drafts, jnp.asarray([1], jnp.int32))), [1])
    np.testing.assert_array_equal(
        np.asarray(speculative_accept(
            toks, drafts, jnp.asarray([0], jnp.int32))), [0])


# -- the finiteness gate in the sampler's program -----------------------------

_BAD = {1: jnp.nan, 3: jnp.inf, 4: -jnp.inf}      # row -> what spoils it


@pytest.mark.parametrize("restrict", [{}, {"top_k": 5}, {"top_p": 0.6},
                                      {"top_k": 9, "top_p": 0.8}],
                         ids=["full", "top_k", "top_p", "top_k_top_p"])
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "T0.8"])
@pytest.mark.parametrize("grid", [False, True], ids=["BV", "Bk1V"])
def test_checked_sampler_is_the_sampler_and_the_gate(grid, temperature,
                                                     restrict):
    """One jitted program, one result: row 0 is what ``sample_stream`` /
    ``sample_stream_grid`` gives for the same arguments bit for bit (the
    spoiled rows too: a row's draw never reads another row), row 1 is
    ``finite_rows`` of the same logits. NaN, +inf and -inf each spoil a row
    in ONE element; on the grid they sit at different positions, so a flag
    that read its slot and not its position would show."""
    b, k1 = 6, 3
    shape = (b, k1, V) if grid else (b, V)
    logits = jax.random.normal(jax.random.PRNGKey(11), shape, jnp.float32)
    for row, bad in _BAD.items():
        at = (row, row % k1, 7 + row) if grid else (row, 7 + row)
        logits = logits.at[at].set(bad)
    base = np.asarray(jax.random.split(jax.random.PRNGKey(5), b), np.uint32)
    counts = np.arange(b, dtype=np.int32) * 3
    if grid:
        counts = counts[:, None] + np.arange(k1, dtype=np.int32)
    temps = np.full((b,), temperature, np.float32)
    plain, checked = ((sample_stream_grid, sample_stream_grid_checked)
                      if grid else (sample_stream, sample_stream_checked))
    jit = lambda f: jax.jit(f, static_argnames=("top_k", "top_p"))
    out = np.asarray(jit(checked)(logits, base, counts, temps, **restrict))
    assert out.dtype == np.int32 and out.shape == (2,) + shape[:-1]
    np.testing.assert_array_equal(
        out[0], np.asarray(jit(plain)(logits, base, counts, temps,
                                      **restrict)))
    finite = np.asarray(jax.jit(finite_rows)(logits))
    np.testing.assert_array_equal(out[1].astype(bool), finite)
    assert set(out[1].ravel().tolist()) == {0, 1}
    assert int((~finite).sum()) == len(_BAD)
    for row in _BAD:        # and only the spoiled position of the row
        assert not finite[(row, row % k1) if grid else row]
    # a finite row's token is in range whatever its neighbours hold
    assert ((out[0] >= 0) & (out[0] < V))[finite].all()
