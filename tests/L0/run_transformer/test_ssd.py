"""The Mamba-2 recurrence (``transformer.functional.ssd``): the chunked form
over a prompt and the decode kernel ``apex_ssd_decode_fwd`` (interpret mode
here) against the recurrence written token by token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional.ssd import ssd_chunked, ssd_step

H, P, G, N = 8, 16, 2, 32


def problem(s, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.standard_normal((s, H, P)).astype(np.float32)
    delta = np.log1p(np.exp(rng.standard_normal((s, H)))).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, (H,)).astype(np.float32)
    b = rng.standard_normal((s, G, N)).astype(np.float32)
    c = rng.standard_normal((s, G, N)).astype(np.float32)
    return tuple(map(jnp.asarray, (x, delta, a, b, c)))


def token_by_token(x, delta, a, b, c):
    """``S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T``, ``y_t = S_t
    C_t`` in float64 numpy: (y, the state after the last token)."""
    x, delta, a, b, c = (np.asarray(t, np.float64) for t in (x, delta, a, b,
                                                            c))
    b, c = np.repeat(b, H // G, 1), np.repeat(c, H // G, 1)
    state = np.zeros((H, P, N))
    ys = []
    for t in range(x.shape[0]):
        state = np.exp(delta[t] * a)[:, None, None] * state \
            + (delta[t][:, None] * x[t])[:, :, None] * b[t][:, None, :]
        ys.append(np.einsum("hpn,hn->hp", state, c[t]))
    return np.stack(ys), state


@pytest.mark.parametrize("s, chunk", [(32, 8), (128, 128), (24, 8)])
def test_chunked_is_the_recurrence(s, chunk):
    args = problem(s)
    y, state = ssd_chunked(*args, chunk=chunk)
    want_y, want_state = token_by_token(*args)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


def test_padded_positions_decay_nothing_and_write_nothing():
    """``delta = 0`` is how the caller pads: the state after 19 real tokens
    and 13 padded ones is the state after 19."""
    x, delta, a, b, c = problem(32, seed=1)
    delta = delta.at[19:].set(0.0)
    _, state = ssd_chunked(x, delta, a, b, c, chunk=8)
    _, want = token_by_token(x[:19], delta[:19], a, b[:19], c[:19])
    np.testing.assert_allclose(state, want, rtol=2e-4, atol=2e-4)


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        ssd_chunked(*problem(20), chunk=8)


def stacked(states, layers=3, layer=1):
    """``states`` (slots, H, P, N) as layer ``layer`` of a stacked array
    whose other layers hold a sentinel."""
    full = np.full((layers,) + np.asarray(states).shape, 7.0, np.float32)
    full[layer] = states
    return jnp.asarray(full)


def test_step_after_chunked_is_chunked_one_token_longer():
    x, delta, a, b, c = problem(17, seed=2)
    _, before = ssd_chunked(x[:16], delta[:16], a, b[:16], c[:16], chunk=8)
    want_y, want_state = token_by_token(x, delta, a, b, c)
    state = stacked(before[None])
    y, after = ssd_step(x[16:], delta[16:], a, b[16:], c[16:], state,
                        jnp.int32(1), jnp.ones((1,), bool))
    np.testing.assert_allclose(y[0], want_y[16], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(after[1, 0], want_state, rtol=2e-4, atol=2e-4)
    # the other layers of the stacked state are as they were
    assert np.all(np.asarray(after[0]) == 7.0)
    assert np.all(np.asarray(after[2]) == 7.0)


def test_step_leaves_an_inactive_slots_state_untouched_and_gives_zeros():
    slots = 3
    rng = np.random.RandomState(3)
    x, delta, a, b, c = problem(slots, seed=3)    # one token a slot
    states = rng.standard_normal((slots, H, P, N)).astype(np.float32)
    active = jnp.asarray([True, False, True])
    y, after = jax.jit(ssd_step)(x, delta, a, b, c, stacked(states),
                                 jnp.int32(1), active)
    np.testing.assert_array_equal(after[1, 1], states[1])
    assert not np.any(np.asarray(y[1]))
    bb, cc = np.repeat(b, H // G, 1), np.repeat(c, H // G, 1)
    for slot in (0, 2):
        want = np.exp(np.asarray(delta[slot] * a))[:, None, None] \
            * states[slot] + np.asarray(delta[slot][:, None] * x[slot])[
                :, :, None] * np.asarray(bb[slot])[:, None, :]
        np.testing.assert_allclose(after[1, slot], want, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(
            y[slot], np.einsum("hpn,hn->hp", want, np.asarray(cc[slot])),
            rtol=1e-4, atol=1e-4)


def test_step_refuses_a_state_that_is_not_the_stacked_float32_array():
    x, delta, a, b, c = problem(2)
    with pytest.raises(ValueError, match="does not hold float32"):
        ssd_step(x, delta, a, b, c, jnp.zeros((3, 2, H, P, N), jnp.bfloat16),
                 jnp.int32(0), jnp.ones((2,), bool))
    with pytest.raises(ValueError, match="does not hold float32"):
        ssd_step(x, delta, a, b, c, jnp.zeros((3, 4, H, P, N), jnp.float32),
                 jnp.int32(0), jnp.ones((2,), bool))


def test_step_over_two_blocks_of_heads_reads_each_heads_own_group(
        monkeypatch):
    """At the published widths the kernel takes a slot's 128 heads in two
    blocks of 64 (8 groups: four to a block). Here 8 heads in two blocks of 4
    (2 groups: one to a block): block 1's heads read group 1's rows."""
    from apex_tpu.transformer.functional import ssd as module

    monkeypatch.setattr(module, "_STEP_HEADS", 4)
    slots = 2
    rng = np.random.RandomState(8)
    x, delta, a, b, c = problem(slots, seed=8)
    states = rng.standard_normal((slots, H, P, N)).astype(np.float32)
    y, after = ssd_step(x, delta, a, b, c, stacked(states), jnp.int32(1),
                        jnp.ones((slots,), bool))
    bb, cc = np.repeat(b, H // G, 1), np.repeat(c, H // G, 1)
    for slot in range(slots):
        want = np.exp(np.asarray(delta[slot] * a))[:, None, None] \
            * states[slot] + np.asarray(delta[slot][:, None] * x[slot])[
                :, :, None] * np.asarray(bb[slot])[:, None, :]
        np.testing.assert_allclose(after[1, slot], want, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(
            y[slot], np.einsum("hpn,hn->hp", want, np.asarray(cc[slot])),
            rtol=1e-4, atol=1e-4)
