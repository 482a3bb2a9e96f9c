"""Model-based drafting: a tiny GPT advanced in lockstep with the
target's slots.

The n-gram drafter (``serving.draft``) is free but collapses toward
m̄ = 1 on non-repetitive text. This module runs a LEARNED draft model
— a 2–4 layer GPT sharing the target's vocab (``models.draft_gpt_tiny``
pairs ``gpt_tiny``) — whose forward costs a few percent of the
target's parameter read (the ``gpt_draft_forward_step`` budget pins
<3%), so even modest acceptance amortizes (adjusted break-even
m̄ > 1.017 + draft_bytes/target_bytes, from the computed byte budgets).

Lockstep + resync contract
--------------------------
The draft keeps its OWN cache, one row stream per target slot, on the
layout every cache has (``serving.cache.PagedKVCache``) under a block
table that is the identity and never changes: logical page ``j`` of slot
``i`` is physical page ``RESERVED_PAGES + i * P + j``, so there is no
``PagePool``, nothing is shared and the host never touches the table
after construction. ``_tokens[slot]`` records exactly which tokens' K/V
rows the draft cache holds (rows ``0..len-1``). Each ``draft()`` call re-syncs
every slot to the target's committed history by COMMON PREFIX: rows
whose recorded token still matches the committed stream are kept;
``lengths`` is rolled back to the first divergence and the backlog
(newly committed tokens, plus anything past a divergence) is re-fed in
verify-shaped chunks. This is the target's own write-then-attend
rollback reused verbatim: a rolled-back row is overwritten before any
later mask admits it, so rejected-draft rows never need cleanup, and a
rejected TREE branch (or a fault-skipped tick) is handled by the same
prefix computation — there is no separate rollback path.

Chunked catch-up doubles as prefill: a fresh slot's whole prompt
streams through the same verify-fn chunks (pad columns repeat token 0;
their rows are garbage beyond the recorded length and are overwritten
by the next catch-up). The LAST chunk's logits row at the final real
token is the draft distribution for the next stream token — the root
of both the linear chain (greedy argmax, then batched single-token
decode steps) and the draft tree (top-``branch`` root children,
greedy-extended leftmost chain).

TP: pass a ``GPTModel(draft_cfg, tp_size)`` — the drafter then builds
``make_tp_paged_verify_fn``/``make_tp_paged_decode_fn`` over the same mesh the
target shards on (the draft partition table is
``partition.tables.draft_gpt_rules``).
"""

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import GPTConfig
from apex_tpu.serving.cache import (
    RESERVED_PAGES, init_paged_cache, max_pages_per_slot,
)
from apex_tpu.serving.decode import (
    make_paged_decode_fn, make_paged_verify_fn, make_tp_paged_decode_fn,
    make_tp_paged_verify_fn,
)

__all__ = ["DraftModel"]

#: Rows a page of the drafter's private pool. Its table is the identity, so
#: the size decides nothing but how many rows of slack a slot's last page has.
PAGE_SIZE = 16


def init_draft_cache(cfg: GPTConfig, num_slots: int, rows: int,
                     dtype=jnp.bfloat16):
    """The drafter's private cache: ``rows`` rows a slot in whole pages of
    a pool no other slot maps, under the identity block table (module
    docstring)."""
    pages = max_pages_per_slot(rows, PAGE_SIZE)
    cache = init_paged_cache(cfg, num_slots, rows,
                             RESERVED_PAGES + num_slots * pages, PAGE_SIZE,
                             dtype)
    return cache._replace(block_tables=(
        RESERVED_PAGES + jnp.arange(num_slots * pages, dtype=jnp.int32)
    ).reshape(num_slots, pages))


def _common_prefix(a: Sequence[int], b: Sequence[int]) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class DraftModel:
    """Host-side drafter wrapping a tiny GPT + its lockstep KV cache.

    ``params``/``cfg`` are the draft net (same vocab as the target);
    ``num_slots`` mirrors the target engine's slot count; ``max_len``
    is the TARGET's max_len — the draft cache adds ``chunk`` rows of
    slack so pad columns of the last catch-up chunk stay in bounds.
    ``model``/``mesh`` switch the forwards to the TP builders.
    """

    def __init__(self, params, cfg: GPTConfig, num_slots: int,
                 max_len: int, *, chunk: int = 5, compute_dtype=None,
                 model=None, mesh=None, cache_dtype=jnp.bfloat16):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.chunk = chunk
        self.cache = init_draft_cache(cfg, num_slots, max_len + chunk,
                                      cache_dtype)
        from apex_tpu.quant.params import is_quantized_tree
        quantized = is_quantized_tree(params)
        if model is not None:
            if model.cfg is not cfg and model.cfg != cfg:
                raise ValueError("TP draft model config mismatch")
            self._verify = make_tp_paged_verify_fn(model, mesh,
                                                   quantized=quantized)
            self._decode = make_tp_paged_decode_fn(model, mesh,
                                                   quantized=quantized)
        else:
            self._verify = make_paged_verify_fn(cfg, compute_dtype,
                                                quantized)
            self._decode = make_paged_decode_fn(cfg, compute_dtype,
                                                quantized)
        # per-slot record of which tokens' K/V rows the cache holds
        self._tokens: List[List[int]] = [[] for _ in range(num_slots)]

    def free_slot(self, slot: int) -> None:
        """Forget a slot (target slot freed/preempted): its rows become
        garbage beyond length 0 and are overwritten on reuse."""
        self._tokens[slot] = []
        self.cache = self.cache._replace(
            lengths=self.cache.lengths.at[slot].set(0))

    # -- sync ------------------------------------------------------------

    def _sync(self, histories: Sequence[Optional[Sequence[int]]]):
        """Catch every active slot up to its committed history and
        return the root logits (np (B, V)): the draft distribution for
        the token after ``history[-1]``. Inactive slots (None) idle on
        pad feeds at length 0."""
        hists = [list(h) if h else None for h in histories]
        # roll back to the common prefix, held strictly below len(h) so
        # the final chunk always re-feeds history[-1] and yields fresh
        # root logits
        cp = []
        for s in range(self.num_slots):
            h = hists[s]
            if h is None:
                cp.append(0)
                continue
            keep = min(_common_prefix(self._tokens[s], h), len(h) - 1)
            self._tokens[s] = self._tokens[s][:keep]
            cp.append(keep)
        root = np.zeros((self.num_slots, self.cfg.vocab_size), np.float32)
        while True:
            backlog = [len(h) - cp[s] if h is not None else 0
                       for s, h in enumerate(hists)]
            if not any(backlog):
                break
            last_round = max(backlog) <= self.chunk
            grid = np.zeros((self.num_slots, self.chunk), np.int32)
            fed = [0] * self.num_slots
            for s, h in enumerate(hists):
                if h is None:
                    continue
                # hold a slot's final partial chunk for the last round
                # so every active slot's root logits come from one call
                if not last_round and backlog[s] <= self.chunk:
                    continue
                n = min(backlog[s], self.chunk)
                grid[s, :n] = h[cp[s]:cp[s] + n]
                fed[s] = n
            self.cache = self.cache._replace(
                lengths=jnp.asarray(cp, jnp.int32))
            self.cache, logits = self._verify(
                self.params, self.cache, jnp.asarray(grid))
            if last_round:
                lg = np.asarray(logits)
                for s in range(self.num_slots):
                    if fed[s]:
                        root[s] = lg[s, fed[s] - 1]
            for s in range(self.num_slots):
                if fed[s]:
                    self._tokens[s].extend(hists[s][cp[s]:cp[s] + fed[s]])
                    cp[s] += fed[s]
            if last_round:
                break
        self.cache = self.cache._replace(lengths=jnp.asarray(cp, jnp.int32))
        return root

    def _greedy_steps(self, first: np.ndarray, ks: Sequence[int]):
        """Extend each slot's chain greedily: ``first`` (B,) is the
        chain's first token (already chosen from the root logits);
        returns per-slot chains of length ``ks[s]`` (0 -> []). Feeding
        a chain token writes its row and records it — the next sync's
        common prefix decides whether it survives."""
        chains = [[int(first[s])] if ks[s] >= 1 else []
                  for s in range(self.num_slots)]
        steps = max((k - 1 for k in ks), default=0)
        cur = np.array([c[0] if c else 0 for c in chains], np.int32)
        for i in range(steps):
            active = np.array([ks[s] - 1 > i for s in range(self.num_slots)])
            if not active.any():
                break
            self.cache, logits = self._decode(
                self.params, self.cache, jnp.asarray(cur),
                jnp.asarray(active))
            nxt = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
            for s in range(self.num_slots):
                if active[s]:
                    self._tokens[s].append(int(cur[s]))
                    chains[s].append(int(nxt[s]))
                    cur[s] = nxt[s]
        return chains

    # -- drafting --------------------------------------------------------

    def draft(self, histories: Sequence[Optional[Sequence[int]]],
              ks: Sequence[int]) -> List[List[int]]:
        """Linear drafts: for each active slot, up to ``ks[s]`` greedy
        continuation tokens of ``histories[s]``. The last chain token
        is never fed (its row would be pure waste), so the recorded
        rows are ``history + chain[:-1]``."""
        root = self._sync(histories)
        ks = [k if histories[s] is not None else 0
              for s, k in enumerate(ks)]
        first = root.argmax(axis=1).astype(np.int32)
        return self._greedy_steps(first, ks)

    def draft_tree(self, histories: Sequence[Optional[Sequence[int]]],
                   ks: Sequence[int]
                   ) -> List[Optional[Tuple[List[int], List[int]]]]:
        """Tree drafts of up to ``ks[s]`` nodes: a greedy leftmost
        chain of ``k - 1`` tokens plus the SECOND-best root child as an
        alternate branch (both roots are children of the walk root;
        top-2 of one distribution are distinct, the accept walk's
        distinct-children contract). Returns per-slot ``(tokens,
        parents)`` with parent ``-1`` = walk root — ``None`` for
        inactive slots or ``k == 0``. Only the leftmost chain is fed
        (and recorded): an accepted alternate branch simply diverges
        the next sync's common prefix."""
        root = self._sync(histories)
        ks = [k if histories[s] is not None else 0
              for s, k in enumerate(ks)]
        order = np.argsort(-root, axis=1)
        chains = self._greedy_steps(order[:, 0].astype(np.int32),
                                    [max(k - 1, min(k, 1)) for k in ks])
        out: List[Optional[Tuple[List[int], List[int]]]] = []
        for s in range(self.num_slots):
            k = ks[s]
            if k <= 0:
                out.append(None)
                continue
            tokens = list(chains[s])
            parents = [-1] + list(range(len(tokens) - 1))
            if k >= 2 and len(tokens) == k - 1:
                tokens.append(int(order[s, 1]))
                parents.append(-1)
            out.append((tokens, parents))
        return out
