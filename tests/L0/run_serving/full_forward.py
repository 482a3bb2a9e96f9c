"""The oracle of the serving tests: the model's full-sequence forward.

What a cache path must equal is what the model computes with no cache at
all (``apply_gpt_unsharded`` over the whole sequence, tied head), so the
tests hold the engine to it directly: teacher-forced logits at the
positions asked for, and a request's committed stream (greedy or seeded
sampling) regenerated token by token with the scheduler's key schedule
as a loop, ``fold_in(PRNGKey(seed), n_generated)`` derived eagerly and
handed to the sampler."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import apply_gpt_unsharded
from apex_tpu.serving.sampling import sample_tokens


def full_logits(params, cfg, seq):
    """(b, s) ids -> (b, s, V) float32 logits of the full forward."""
    hidden = apply_gpt_unsharded(params, cfg, jnp.asarray(seq, jnp.int32))
    table = params["embedding"]["word"]["embedding"]
    return jnp.dot(hidden, table.T).astype(jnp.float32)


#: ``full_logits`` compiled once per (config, shape): a causal forward's row
#: ``p`` does not see what follows it, so a sequence is read in a buffer
#: whose tail is padding (a stream: ONE buffer of ``max_len`` positions).
_full_logits = jax.jit(full_logits, static_argnums=1)
_PAD_TO = 32


def teacher_forced(params, cfg, seq, positions):
    """Logits rows of ONE sequence (a list of ids) after reading
    ``seq[:p + 1]`` for each ``p`` in ``positions``: what a cache path
    that was fed ``seq`` must return there. (len(positions), V)."""
    toks = np.zeros((1, -(-len(seq) // _PAD_TO) * _PAD_TO), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(_full_logits(params, cfg, toks))[0, list(positions)]


def reference_stream(params, cfg, request, eos_id, max_len, top_k=0,
                     top_p=0.0):
    """The tokens the scheduler must commit for ``request`` whatever
    shares its batch: each one drawn from the full forward's last row
    with the key of its number, until EOS, ``max_new_tokens`` or a full
    cache row (``max_len`` positions)."""
    n, out = len(request.prompt), []
    toks = np.zeros((1, max_len), np.int32)
    toks[0, :n] = request.prompt
    temp = jnp.asarray([request.temperature], jnp.float32)
    while len(out) < request.max_new_tokens:
        last = _full_logits(params, cfg, toks)[:, n - 1]
        key = jax.random.fold_in(jax.random.PRNGKey(request.seed), len(out))
        nxt = int(sample_tokens(last, key[None, :], temp, top_k, top_p)[0])
        out.append(nxt)
        if nxt == eos_id or n >= max_len:
            break
        toks[0, n] = nxt
        n += 1
    return out
