"""The replay contract's key schedule, derived in one program.

Token ``n`` of a request samples with ``fold_in(PRNGKey(seed), n)``. The
scheduler used to run those two programs per slot per tick; now it takes
``PRNGKey(seed)`` once per admission (``scheduler._base_key``) and the
sampler programs fold the token number in for all slots at once
(``sampling.stream_keys``). Held here:

- the keys are the eager ones word for word, also for seeds outside int32,
  where ``PRNGKey(python int)`` has its own rule for the high word;
- the scheduler's streams are those of a loop that samples one request at a
  time with eagerly derived keys (the contract as it was first written);
- a steady decode tick of any kind derives no key eagerly and uploads the
  same number of host arrays at 2 slots as at 8; the block table is the
  host's (``PagedDecodeEngine``) and costs a tick one more upload when a slot
  crossed a page boundary, however many did, and none otherwise; a plain
  tick leaves no leaf of ``engine.cache`` to an eager operation;
- the finiteness gate rides in the sampler's program: a steady tick of any
  kind waits ONCE for a sampler's result (``stats.sampler_waits``), and a
  plain one launches two programs, the step and the checked sampler, and
  reads one device array back;
- the ``build_inputs`` span says how many slots it built for.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from full_forward import reference_stream

from apex_tpu.models.gpt import gpt_tiny, init_gpt
from apex_tpu.serving import (ContinuousBatchingScheduler,
                              PagedDecodeEngine, Request, Tracer,
                              stream_keys)
from apex_tpu.serving.draft_model import DraftModel
from apex_tpu.serving.scheduler import _base_key

EOS = -1
MAX_LEN = 64
SEEDS = (0, 1, 2 ** 31 - 2, 2 ** 31, 2 ** 32 - 1, 2 ** 40, 2 ** 40 + 5, -7)
COUNTS = (0, 1, 255, 1023)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(gpt_tiny(), use_rope=True, hidden_dropout=0.0)
    return cfg, init_gpt(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def derive():
    return jax.jit(stream_keys)


def _eager_key(seed, n):
    return np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), n))


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("seed", SEEDS)
def test_stream_keys_equal_eager_fold_in(derive, seed, count):
    """One row of the batch is this seed; its neighbours are other seeds, so
    a row that read its neighbour's base would show."""
    seeds = [seed, 3, seed + 1]
    base = np.stack([_base_key(s) for s in seeds])
    assert base.dtype == np.uint32 and base.shape == (3, 2)
    counts = np.asarray([count, 5, count], np.int32)
    keys = np.asarray(derive(base, counts))
    assert keys.dtype == np.uint32
    for row, (s, n) in enumerate(zip(seeds, counts)):
        np.testing.assert_array_equal(keys[row], _eager_key(s, int(n)))
    # the verify grids: position j folds count + j
    offs = counts[:, None] + np.arange(3, dtype=np.int32)
    grid = np.asarray(derive(base, offs))
    assert grid.shape == (3, 3, 2)
    for j in range(3):
        np.testing.assert_array_equal(grid[0, j],
                                      _eager_key(seed, count + j))


def _engine(model, num_slots, page_size=16, **kw):
    cfg, params = model
    return PagedDecodeEngine(
        params, cfg, num_slots=num_slots, max_len=MAX_LEN,
        num_pages=PagedDecodeEngine.full_pool_pages(num_slots, MAX_LEN,
                                                    page_size),
        page_size=page_size, **kw)


@pytest.mark.parametrize("page_size", [16, 4])
def test_scheduler_streams_equal_eager_key_reference(model, page_size):
    """The contract as a loop (``full_forward.reference_stream``): one
    request alone, no cache, its keys derived eagerly and the sampler given
    the keys themselves. Pages of 16 hold a whole stream, pages of 4 are
    crossed twice; a float32 pool, so that the logits are the model's."""
    cfg, params = model
    reqs = [Request(prompt=(5 + i, 7, 11 + i), max_new_tokens=6,
                    temperature=0.9, seed=s)
            for i, s in enumerate(SEEDS)]
    sched = ContinuousBatchingScheduler(
        _engine(model, 3, page_size, cache_dtype=jnp.float32), eos_id=EOS)
    for r in reqs:
        sched.submit(r)
    outs = sched.run()
    for r, got in zip(reqs, outs):
        assert got == reference_stream(params, cfg, r, EOS, MAX_LEN), r.seed
    # streams of different seeds differ: the sampler did read the keys
    assert len({tuple(o) for o in outs}) > len(outs) // 2


class _Counted:
    """A jitted program of the engine, counting its calls and the host
    arrays among its arguments: each is one upload."""

    def __init__(self, fn, tally):
        self.fn, self.tally = fn, tally

    def __call__(self, *args, **kw):
        self.tally["programs"] += 1
        self.tally["uploads"] += sum(
            isinstance(a, np.ndarray)
            for a in jax.tree_util.tree_leaves((args, kw)))
        return self.fn(*args, **kw)


class _CountedStep(_Counted):
    """The step program, which takes the cache and returns the next one. A
    leaf of the cache it is handed that is neither a leaf it returned last
    time nor the table the engine uploaded was made in between by an eager
    operation on a leaf of ``engine.cache``: counted."""

    def __init__(self, fn, tally, known):
        super().__init__(fn, tally)
        self.known = known      # arrays, kept alive so that no id comes back

    def __call__(self, params, cache, *args):
        ids = {id(a) for a in self.known}
        self.tally["eager_cache_ops"] += sum(
            id(leaf) not in ids for leaf in jax.tree_util.tree_leaves(cache))
        new_cache, logits = super().__call__(params, cache, *args)
        self.known[:] = jax.tree_util.tree_leaves(new_cache)
        return new_cache, logits


def _steady_tick_counts(model, monkeypatch, num_slots, mode, page_size):
    """Programs dispatched, arrays uploaded, device arrays read back, waits
    for a sampler, eager key derivations and eager operations on the cache
    in three decode ticks with every slot decoding and no admission; and per
    tick, what ``prepare_decode`` did to the block table beside the uploads
    the table cost."""
    cfg, params = model
    kw = {}
    if mode != "plain":
        kw["spec_k"] = 2
    if mode == "tree":
        kw.update(tree_spec=True, draft_model=DraftModel(
            params, cfg, num_slots=num_slots, max_len=MAX_LEN))
    eng = _engine(model, num_slots, page_size, **kw)
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS)
    for i in range(num_slots):
        # a repeating prompt, so that the n-gram drafter proposes
        sched.submit(Request(prompt=(7, 11) * 4, max_new_tokens=40,
                             temperature=0.0 if i % 2 else 0.8, seed=i))
    for _ in range(3):      # admission, and every program compiled
        sched.step()
    assert all(sched._decoding(s) for s in sched._slots)
    tally = {"programs": 0, "uploads": 0, "readbacks": 0, "eager_keys": 0,
             "table_uploads": 0, "eager_cache_ops": 0}
    known = jax.tree_util.tree_leaves(eng.cache)
    monkeypatch.setattr(eng, "_decode",
                        _CountedStep(eng._decode, tally, known))
    for name in ("_verify", "_tree_verify", "_sample", "_sample_grid"):
        if getattr(eng, name) is not None:
            monkeypatch.setattr(eng, name, _Counted(getattr(eng, name),
                                                    tally))
    monkeypatch.setattr(sched, "_tree_accept",
                        _Counted(sched._tree_accept, tally))

    def counting(real, key):
        def call(*args, **kw):
            # a call on tracers is a program being compiled (the tree
            # grid's width follows the drafts), not a program run
            if not any(isinstance(a, jax.core.Tracer)
                       for a in jax.tree_util.tree_leaves(args)):
                tally[key] += 1
            return real(*args, **kw)
        return call

    for name in ("PRNGKey", "fold_in", "key"):
        monkeypatch.setattr(jax.random, name,
                            counting(getattr(jax.random, name),
                                     "eager_keys"))
    # every explicit upload the scheduler could make
    for name in ("asarray", "array", "stack"):
        monkeypatch.setattr(jnp, name,
                            counting(getattr(jnp, name), "uploads"))
    # every blocking copy down: numpy asked for a device array's value
    real_asarray = np.asarray

    def read_back(x, *args, **kw):
        tally["readbacks"] += isinstance(x, jax.Array)
        return real_asarray(x, *args, **kw)

    monkeypatch.setattr(np, "asarray", read_back)
    real_put = jax.device_put

    def table_put(x, *args, **kw):
        # the one transfer the engine makes itself: its block table
        assert x.shape == eng._table.shape and x.dtype == np.int32
        tally["table_uploads"] += 1
        out = real_put(x, *args, **kw)
        known.append(out)
        return out

    monkeypatch.setattr(jax, "device_put", table_put)
    stats = sched.stats
    before = (stats.plain_ticks, stats.spec_ticks, stats.sampler_waits)
    per_tick = []
    for _ in range(3):
        was = (stats.page_boundaries + stats.cow_copies,
               stats.block_table_uploads)
        sched.step()
        per_tick.append((stats.page_boundaries + stats.cow_copies - was[0],
                         stats.block_table_uploads - was[1]))
    monkeypatch.undo()
    ticks = (stats.plain_ticks - before[0], stats.spec_ticks - before[1])
    tally["sampler_waits"] = stats.sampler_waits - before[2]
    assert all(sched._decoding(s) for s in sched._slots)
    assert tally["table_uploads"] == sum(up for _, up in per_tick)
    return tally, ticks, per_tick


@pytest.mark.parametrize("page_size", [16, 4])
@pytest.mark.parametrize("mode", ["plain", "spec", "tree"])
def test_tick_programs_and_uploads_do_not_grow_with_slots(
        model, monkeypatch, mode, page_size):
    """With pages of 4 rows the counted ticks cross page boundaries (every
    slot holds the 8-token prompt: rows 8 and 12 open a page), with pages of
    16 the plain ones cross none."""
    few, ticks_few, table_few = _steady_tick_counts(
        model, monkeypatch, 2, mode, page_size)
    many, ticks_many, table_many = _steady_tick_counts(
        model, monkeypatch, 8, mode, page_size)
    assert few["eager_keys"] == 0 and many["eager_keys"] == 0
    assert ticks_few == ticks_many
    # the table: one upload on a tick that mapped or retargeted any number
    # of pages, none on a tick that did not
    for mapped, uploads in table_few + table_many:
        assert uploads == (1 if mapped else 0), (table_few, table_many)
    # slots that speculate cross their boundaries on different ticks, so the
    # table's uploads are compared by that law and the rest by count
    rest = ("programs", "uploads", "readbacks", "eager_keys")
    assert {k: few[k] for k in rest} == {k: many[k] for k in rest}
    # ONE wait for a sampler in a tick of any kind, with the finite flags in
    # its result: no second program, no second read-back for the gate
    assert few["sampler_waits"] == many["sampler_waits"] == 3 == sum(ticks_few)
    if mode != "tree":      # the tree walk is a program of its own: 2 more
        assert few["readbacks"] == 3
    if mode == "plain":
        assert ticks_few == (3, 0)
        crossing = [(2, 1), (8, 1)] if page_size == 4 else [(0, 0), (0, 0)]
        assert [max(table_few), max(table_many)] == crossing
        # a tick: TWO programs, decode(tokens, active) and the checked
        # sampler (base, counts, temps) launched behind it, ONE read-back, and
        # in the one tick of the three that opens a page of 4 rows for every
        # slot, the table; no eager operation on a leaf of the cache
        assert few == many == {
            "programs": 6, "uploads": 15, "readbacks": 3,
            "sampler_waits": 3, "eager_keys": 0,
            "table_uploads": 1 if page_size == 4 else 0,
            "eager_cache_ops": 0}
    else:
        assert ticks_few[1] > 0


@pytest.mark.parametrize("mode", ["plain", "spec", "chunked"])
@pytest.mark.parametrize("sharing", [True, False],
                         ids=["shared", "private"])
def test_sampler_waits_once_a_tick_and_once_an_admission(model, sharing,
                                                         mode):
    """``stats.sampler_waits``, the counter that says the gate and the
    sampler are one program read back once: +1 for every first token
    sampled (an admission, monolithic or after the final chunk; a resumed
    request samples none) and +1 for every decode tick of any kind,
    whatever the number of slots, and whether the two requests of one
    prompt share its pages (and copy on their first write) or hold their
    own. It rides the registry as the others do."""
    trc = Tracer()
    eng = _engine(model, 3, page_size=4, tracer=trc, prefix_sharing=sharing,
                  **({"spec_k": 2} if mode == "spec" else {}))
    sched = ContinuousBatchingScheduler(
        eng, eos_id=EOS, **({"chunk_tokens": 4} if mode == "chunked" else {}))
    stats = sched.stats
    for i in range(2):
        sched.submit(Request(prompt=(7, 11) * 3, max_new_tokens=12,
                             temperature=0.8 * i, seed=i))
    # two first tokens; on the monolithic path the tick that admits both
    # runs one decode step behind them, chunks take the ticks they take
    sched.step()
    if mode != "chunked":
        assert stats.sampler_waits == 3
    while not all(map(sched._decoding, sched._slots[:2])):
        sched.step()
    assert stats.sampler_waits == 2 + stats.plain_ticks + stats.spec_ticks
    for _ in range(3):      # steady: both slots decode, nobody is admitted
        was = stats.sampler_waits
        sched.step()
        assert stats.sampler_waits == was + 1
    # one more admission beside two decoding slots
    sched.submit(Request(prompt=(5, 3, 5), max_new_tokens=3))
    was = stats.sampler_waits
    sched.step()
    assert stats.sampler_waits == was + 2
    sched.run()
    ticks = stats.plain_ticks + stats.spec_ticks
    assert stats.sampler_waits == 3 + ticks
    assert trc.registry.counter("serving_sampler_waits_total").value \
        == stats.sampler_waits
    assert "serving_sampler_waits_total" in trc.registry.to_prometheus()


def test_sampler_waits_not_for_a_resumed_request(model):
    """A preempted request re-admitted with its progress samples no first
    token: its admission waits for no sampler."""
    eng = _engine(model, 1)
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS)
    sched.submit(Request(prompt=(7, 11, 13), max_new_tokens=6))
    sched.step()
    sched.step()
    slot = sched._slots[0]
    sched._queue.appendleft((slot.request_id, slot.request,
                             list(slot.generated)))
    sched._slots[0] = None
    eng.free_slot(0)
    was = sched.stats.sampler_waits
    sched.step()                    # re-prefill, then one decode tick
    assert sched.stats.sampler_waits == was + 1
    sched.run()
    assert sched.outcomes[0].ok and len(sched.outcomes[0].tokens) == 6


def test_build_inputs_span_counts_decoding_slots(model):
    trc = Tracer()
    eng = _engine(model, 3, tracer=trc)
    sched = ContinuousBatchingScheduler(eng, eos_id=EOS)
    for i, n in enumerate((2, 5, 5)):
        sched.submit(Request(prompt=(3, 5 + i), max_new_tokens=n))
    sched.run()
    slots = [dict(e.args)["slots"] for e in trc.events
             if e.name == "build_inputs"]
    # the first request ends after its second token: three slots decode in
    # the first tick, two in the rest
    assert slots == [3, 2, 2, 2]
