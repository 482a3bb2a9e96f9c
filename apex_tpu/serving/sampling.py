"""Token sampling under explicit PRNG keys.

Serving needs reproducible sampling: every stochastic draw threads an
explicit ``jax.random`` key, and token ``n`` of a request draws with
``fold_in(PRNGKey(request.seed), n)``, so a replayed request stream
regenerates byte-identical outputs — the determinism contract the
training side already holds (see ``tests/L0/run_serving``).

Where the keys are derived: INSIDE the sampler programs
(:func:`stream_keys`). The scheduler takes ``PRNGKey(request.seed)``
once, when the request gets its slot, and keeps its two words on the
host; each tick it uploads one ``[num_slots, 2]`` array of those base
keys and one array of counts, and ``sample_stream`` /
``sample_stream_grid`` fold the count into the base for every slot in
the program that samples. The schedule is the one above; only the
number of device programs changed, which no longer grows with the
slots.

The finiteness gate rides in the same program. What the scheduler
launches behind a step or a prefill is ``sample_stream_checked`` /
``sample_stream_grid_checked``: the sampler's tokens and
``finite_rows(logits)`` stacked into ONE int32 array (row 0 the tokens,
row 1 the flags), so the host waits once and reads back once per tick
or admission. The flag has a row of its own because no token value can
stand for "not finite": an out-of-range token is a different fault
(``stats.bad_samples``) from a non-finite row (``stats.nan_events``).
A row that is not finite is sampled like any other and never committed.

One fused entry point handles the whole batch: per-slot temperature
(``<= 0`` selects greedy) so mixed greedy/sampled slots decode in one
jitted step instead of recompiling per request mix. ``top_k`` / ``top_p``
are static (part of the compiled program) — engine-level settings, not
per-request ones.

Speculative decoding shares this surface. ``sample_token_grid`` runs
the SAME sampler over the verify step's (B, k+1, V) logits, one key
per (slot, position) — position j uses ``fold_in(seed, n_generated +
j)``, i.e. exactly the key the plain decode stream would use for its
(n_generated + j)-th token. The host accept walk then commits the
longest prefix where the sampled token reproduces the draft, plus the
first non-matching sample. Because the n-gram draft is deterministic
(a point mass q = δ_d), this IS standard speculative sampling
(Leviathan et al.): the accept probability min(1, p(d)/q(d)) at the
drafted token is just p(d) — the chance the plain-key categorical
draw lands on d — and the residual distribution on first rejection
norm(max(p − q, 0)) is p restricted to tokens ≠ d, which is what the
non-matching draw realizes. Greedy rows degenerate to
longest-matching-argmax-prefix. Acceptance therefore changes only how
many STEPS a stream takes, never which tokens it emits: speculative
output is bit-identical to plain decode.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _restrict(logits: jax.Array, top_k: int, top_p: float) -> jax.Array:
    """Mask ``logits`` (…, V) to the top-k / nucleus support with
    ``-inf`` (applied to RAW logits, before temperature, so the support
    is temperature-independent — matching greedy's argmax view)."""
    if top_k:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p and top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]        # descending
        probs = jax.nn.softmax(srt, axis=-1)
        # keep a sorted token while the mass BEFORE it is < top_p: the
        # smallest prefix whose mass reaches top_p (the argmax always
        # survives — its "before" mass is 0)
        keep = jnp.cumsum(probs, axis=-1) - probs < top_p
        thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits >= thresh, logits, -jnp.inf)
    return logits


def sample_tokens(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array, top_k: int = 0,
                  top_p: float = 0.0) -> jax.Array:
    """logits (B, V) fp32; keys (B, 2) uint32 (stacked jax.random keys);
    temperature (B,) float — ``t <= 0`` means greedy for that slot, the
    scheduler's encoding for deterministic requests. ``top_k`` (static;
    0 = full vocab) restricts sampling to each row's k largest logits;
    ``top_p`` (static; 0 or 1 = off) to the smallest set whose softmax
    mass reaches p (nucleus sampling). Returns (B,) int32 token ids."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _restrict(logits, top_k, top_p)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(
        jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def sample_token_grid(logits: jax.Array, keys: jax.Array,
                      temperature: jax.Array, top_k: int = 0,
                      top_p: float = 0.0) -> jax.Array:
    """:func:`sample_tokens` over a verify step's (B, k1, V) logits with
    per-position keys (B, k1, 2): flattens to (B*k1, V), repeats each
    slot's temperature over its k1 positions, and reshapes back to
    (B, k1) int32. Position (b, j) draws with key[b, j] — the key the
    plain stream uses for that slot's (n_generated + j)-th token — so
    the committed prefix is bit-identical to plain decode."""
    b, k1, v = logits.shape
    toks = sample_tokens(logits.reshape(b * k1, v),
                         keys.reshape(b * k1, 2),
                         jnp.repeat(temperature, k1), top_k, top_p)
    return toks.reshape(b, k1)


def stream_keys(base: jax.Array, counts: jax.Array) -> jax.Array:
    """The replay contract's key schedule over arrays: ``base`` (B, 2)
    uint32 holds each slot's ``PRNGKey(request.seed)``, ``counts`` int32
    is (B,) — token numbers — or (B, k1) — one per verify position.
    Returns the (B, 2) or (B, k1, 2) keys ``fold_in(base[b],
    counts[b, ...])``, word for word what the eager call gives."""
    fold = jax.random.fold_in
    if counts.ndim == 2:
        fold = jax.vmap(fold, (None, 0))
    return jax.vmap(fold)(base, counts)


def sample_stream(logits: jax.Array, base: jax.Array, counts: jax.Array,
                  temperature: jax.Array, top_k: int = 0,
                  top_p: float = 0.0) -> jax.Array:
    """:func:`sample_tokens` with slot b's key derived in the same
    program: ``fold_in(base[b], counts[b])``."""
    return sample_tokens(logits, stream_keys(base, counts), temperature,
                         top_k, top_p)


def sample_stream_grid(logits: jax.Array, base: jax.Array,
                       counts: jax.Array, temperature: jax.Array,
                       top_k: int = 0, top_p: float = 0.0) -> jax.Array:
    """:func:`sample_token_grid` with position (b, j)'s key derived in
    the same program: ``fold_in(base[b], counts[b, j])``."""
    return sample_token_grid(logits, stream_keys(base, counts),
                             temperature, top_k, top_p)


def _checked(tokens: jax.Array, logits: jax.Array) -> jax.Array:
    """``tokens`` (…) int32 and ``finite_rows(logits)`` as ONE int32
    array (2, …): ``[0]`` the tokens, ``[1]`` 1 where the row they were
    drawn from is entirely finite."""
    return jnp.stack([tokens, finite_rows(logits).astype(jnp.int32)])


def sample_stream_checked(logits: jax.Array, base: jax.Array,
                          counts: jax.Array, temperature: jax.Array,
                          top_k: int = 0, top_p: float = 0.0) -> jax.Array:
    """:func:`sample_stream` and the finiteness gate in one program with
    one result: (2, B) int32, ``[0]`` the tokens :func:`sample_stream`
    gives bit for bit, ``[1]`` :func:`finite_rows` of the same logits."""
    return _checked(sample_stream(logits, base, counts, temperature,
                                  top_k, top_p), logits)


def sample_stream_grid_checked(logits: jax.Array, base: jax.Array,
                               counts: jax.Array, temperature: jax.Array,
                               top_k: int = 0,
                               top_p: float = 0.0) -> jax.Array:
    """:func:`sample_stream_grid` and the finiteness gate over a verify
    step's (B, k1, V) logits: (2, B, k1) int32, laid out as
    :func:`sample_stream_checked`."""
    return _checked(sample_stream_grid(logits, base, counts, temperature,
                                       top_k, top_p), logits)


def speculative_accept(tokens: jax.Array, drafts: jax.Array,
                       draft_lens: jax.Array) -> jax.Array:
    """Vectorized accept rule: ``tokens`` (B, k1) are the grid-sampled
    tokens, ``drafts`` (B, k) the (0-padded) drafted candidates,
    ``draft_lens`` (B,) the true draft lengths. Draft j is accepted iff
    every draft before it matched its sampled token and ``tokens[:, j]
    == drafts[:, j]`` with ``j < draft_len`` (pad positions never
    match). Returns (B,) int32 accepted counts in [0, k]; the commit is
    ``accepted + 1`` tokens — the accepted drafts plus the first
    non-matching (or bonus k-th) sample, ``tokens[:, :accepted + 1]``.
    Pure structure — no probabilities: the sampled grid already IS the
    plain stream (see the module docstring), so acceptance is just
    "did the plain stream reproduce the draft".
    """
    k = drafts.shape[1]
    match = (tokens[:, :k] == drafts) & \
        (jnp.arange(k)[None, :] < draft_lens[:, None])
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)


def tree_speculative_accept(samples: jax.Array, tokens: jax.Array,
                            parents: jax.Array, valid: jax.Array,
                            start=None):
    """:func:`speculative_accept` generalized to a draft TREE: walk the
    accepted root-to-leaf path. ``samples`` (B, k1) are the tree-verify
    grid's sampled tokens (node j drawn with the plain stream's key for
    depth ``depth[j]``); ``tokens`` (B, k1) the grid's INPUT tokens;
    ``parents`` (B, k1) int32 each node's parent grid index; ``valid``
    (B, k1) bool marks candidate draft nodes (forced/pad columns
    False); ``start`` (B,) is the walk root — the last forced column,
    whose sample is the stream's first new token.

    From ``cur = start``: commit ``samples[cur]``; descend to the valid
    child whose INPUT token equals the committed sample (drafter
    contract: children of one node carry distinct tokens, so the draw
    lands on at most one branch — the point-mass Leviathan accept per
    branch); stop when no child matches. Returns (counts (B,) int32 —
    committed tokens, in [1, k1]; path (B, k1) int32 — visited node
    indices, -1 beyond the path). The committed tokens are
    ``samples[b, path[b, i]]`` in path order: each visited node's
    sample is drawn with exactly the key and (teacher-forced)
    distribution the plain stream would use, so the committed stream
    stays bit-identical to plain decode — acceptance only changes how
    many steps it takes."""
    b, k1 = samples.shape
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    idx = jnp.arange(k1)[None, :]

    def step(carry, _):
        cur, alive = carry
        s = jnp.take_along_axis(samples, cur[:, None], 1)[:, 0]
        cand = valid & (parents == cur[:, None]) & \
            (tokens == s[:, None]) & (idx > cur[:, None])
        has = jnp.any(cand, axis=1)
        nxt = jnp.argmax(cand, axis=1).astype(jnp.int32)
        out = jnp.where(alive, cur, -1)
        alive = alive & has
        cur = jnp.where(alive, nxt, cur)
        return (cur, alive), out

    init = (start.astype(jnp.int32), jnp.ones((b,), bool))
    _, path = lax.scan(step, init, None, length=k1)
    path = path.T                                        # (B, k1)
    counts = jnp.sum(path >= 0, axis=1).astype(jnp.int32)
    return counts, path


def finite_rows(logits: jax.Array) -> jax.Array:
    """(…, V) -> (…,) bool — True where a row of ``logits`` is entirely
    finite. The scheduler's always-on NaN/Inf quarantine gate: a
    device-side reduction, so each tick ships B (or B×k1) flags to the
    host instead of the logits matrix, in the sampler's own result
    (:func:`sample_stream_checked`). The token of a False row is never
    committed to a stream — the slot is quarantined and the request
    retried (``serving.health.NonFiniteLogits``)."""
    return jnp.all(jnp.isfinite(logits), axis=-1)
