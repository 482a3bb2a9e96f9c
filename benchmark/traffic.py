"""The one traffic generator. A mix is a data file under ``traffic/``; this
reads its parameters and makes the inputs from ``--seed``.

Two kinds of mix:

``"kind": "batches"``  training batches, made on the host one step at a time:
    token ids uniform over the vocabulary, every row full length.
``"kind": "requests"`` generation requests with the times they are due.

Every seed gets the same multiset of sizes and arrival gaps and other token
contents: the sizes and gaps are drawn from the mix's own ``sizes_seed``, so
that two seeds never differ in the amount of work. An open loop (``poisson``)
gets them in another order on each ``--seed``. A ``backlog`` gets them in ONE
order on every seed: a backlog has to outlast the window, so the window holds
only the head of the list, and a head that ``--seed`` chose moved
``serve_tokens_per_s`` by 2-5% from seed to seed (PERF.md, section 6, PR 27).
"""

import dataclasses
import math
from typing import List, Optional, Tuple

import numpy as np

_MASK = (1 << 32) - 1


def seeded(*seeds) -> np.random.RandomState:
    """A generator from any whole numbers, however large."""
    return np.random.RandomState([int(s) & _MASK for s in seeds]
                                 + [int(seeds[-1]) >> 32 & _MASK])


# -- training ---------------------------------------------------------------

class Batches:
    """``next()`` gives step ``i``'s host batch: ``(ids, mask)`` as int32
    arrays of ``(rows, seq)``. Rows all differ (uniform ids over a
    vocabulary of tens of thousands)."""

    def __init__(self, mix: dict, seed: int, vocab_size: int, chips: int):
        self.rows = int(mix["rows_per_chip"]) * chips
        self.seq = int(mix["seq"])
        self.vocab = vocab_size
        self.seed = seed
        self.step = 0
        self._mask = np.ones((self.rows, self.seq), np.int32)

    def batch(self, step: int):
        ids = seeded(step, self.seed).randint(
            0, self.vocab, size=(self.rows, self.seq)).astype(np.int32)
        return ids, self._mask

    def next(self):
        out = self.batch(self.step)
        self.step += 1
        return out


# -- serving ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float                 # seconds after the window opens
    prompt: Tuple[int, ...]
    max_new_tokens: int
    temperature: float
    seed: int
    shared_prefix: Optional[int]  # which shared prefix it opens with


def _draw(spec: dict, rng, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        x = rng.uniform(spec["lo"], spec["hi"], size=n)
    elif dist == "loguniform":
        x = np.exp(rng.uniform(math.log(spec["lo"]), math.log(spec["hi"]),
                               size=n))
    elif dist == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"],
                              size=n))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "lo" in spec or "hi" in spec:
        x = np.clip(x, spec.get("lo", -np.inf), spec.get("hi", np.inf))
    return np.rint(x).astype(np.int64)


def backlog_order(prompt_len, new_tokens, opens, sizes_seed: int):
    """The one order a backlog's sizes come in, whatever ``--seed`` is: the
    requests sorted by (prompt length, ``max_new_tokens``, shared prefix),
    which forgets the order they were drawn in, then permuted from the mix's
    own ``sizes_seed``, so that any head of the list is a sample of the
    whole mix. Request ``i`` has the same sizes, the same shared prefix and
    (the temperatures alternate by position) the same temperature on every
    seed; ``--seed`` gives the token contents and the samplers' seeds."""
    n = len(prompt_len)
    by_size = np.lexsort((opens, new_tokens, prompt_len))
    return by_size[seeded(n, sizes_seed, 5).permutation(n)]


def how_many(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        return int(arr["requests"])
    return max(1, int(round(float(arr["rate_per_s"]) * seconds)))


def requests(mix: dict, seed: int, seconds: float, vocab_size: int,
             max_len: int) -> List[Arrival]:
    """The mix's requests for a window of ``seconds``, in the order they
    are due."""
    n = how_many(mix, seconds)
    sizes = seeded(n, int(mix["sizes_seed"]))
    prompt_len = _draw(mix["prompt_tokens"], sizes, n)
    new_tokens = _draw(mix["max_new_tokens"], sizes, n)
    # prompt + output never pass the cache row
    new_tokens = np.minimum(new_tokens, max_len - prompt_len)
    shared = mix.get("shared_prefix")
    opens = np.full(n, -1)
    if shared:
        every = int(shared["one_request_in"])
        opens = np.where(np.arange(n) % every == 0,
                         np.arange(n) // every % int(shared["prefixes"]), -1)
        prompt_len = np.where(opens >= 0,
                              np.maximum(prompt_len,
                                         int(shared["tokens"]) + 1),
                              prompt_len)
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        due = np.zeros(n)
        perm = backlog_order(prompt_len, new_tokens, opens,
                             int(mix["sizes_seed"]))
    elif arr["process"] == "poisson":
        # n exponential gaps scaled to fill the window exactly: the same
        # gaps for every seed, permuted like the sizes
        gaps = sizes.exponential(1.0, size=n)
        gaps *= seconds / gaps.sum() * n / (n + 1)
        order = seeded(seed, 1)
        perm = order.permutation(n)
        due = np.cumsum(gaps[order.permutation(n)])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    prompt_len, new_tokens, opens = (prompt_len[perm], new_tokens[perm],
                                     opens[perm])

    content = seeded(seed, 2)
    lo = int(mix.get("token_lo", 2))
    prefixes = ([tuple(int(t) for t in content.randint(
        lo, vocab_size, size=int(shared["tokens"])))
        for _ in range(int(shared["prefixes"]))] if shared else [])
    temps = mix["temperatures"]
    out = []
    for i in range(n):
        head = prefixes[opens[i]] if opens[i] >= 0 else ()
        body = tuple(int(t) for t in content.randint(
            lo, vocab_size, size=int(prompt_len[i]) - len(head)))
        out.append(Arrival(
            due_s=float(due[i]), prompt=head + body,
            max_new_tokens=int(new_tokens[i]),
            temperature=float(temps[i % len(temps)]),
            seed=int(content.randint(0, 2 ** 31 - 1)),
            shared_prefix=int(opens[i]) if opens[i] >= 0 else None))
    return out
