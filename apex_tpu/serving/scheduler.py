"""Continuous batching over a fixed-slot KV cache.

The scheduler is the host-side half of serving: a FIFO of requests is
multiplexed onto ``num_slots`` cache rows. A slot is admitted with one
bucketed prefill (compiling once per bucket length, never per request),
then every tick advances ALL occupied slots with a single decode step;
a slot is evicted the moment it emits EOS, hits its ``max_new_tokens``,
or fills its cache row — and the freed row is re-admitted from the
queue on the same tick. The decode step therefore always runs at the
full slot batch and only two executables exist in steady state: one
decode program plus one prefill program per touched bucket.

Determinism: every sampled token draws from
``fold_in(PRNGKey(request.seed), n_generated)`` — replaying the same
request stream regenerates identical outputs regardless of how requests
interleave across slots. The key is derived in the program that samples
(``sampling.stream_keys``) from two host-built arrays over the slots:
each request's base key, ``PRNGKey(request.seed)`` read once when it is
admitted or resumed (``_base_key``), and the number of the token. A tick
dispatches and uploads the same whatever the number of slots.

One wait a tick: everywhere logits become tokens (the plain, verify and
tree-verify ticks, a request's first token after a prefill or a final
chunk) the scheduler launches ONE program behind the step that made
them, the sampler with the finiteness gate in it
(``sampling.sample_stream_checked``; ``engine.sample`` / ``sample_grid``
return its result on the device, the copy down started), and reads ONE
array back (``_await_sampler``, inside the ``accept`` span): the tokens
and the finite flags. A steady plain tick is two device programs and one
read-back; ``stats.sampler_waits`` counts the waits.

Chunked prefill (``chunk_tokens=``, the Sarathi-Serve move): a
monolithic prompt forward stalls every co-tenant decode for the whole
prompt length, which is exactly what blows up p99 inter-token latency
under mixed prompt/decode load. With chunking on, admission only
STAGES a prefill (pages allocated up front, all-or-nothing); each tick
then runs the decode step first and spends whatever remains of
``tick_token_budget`` on page-aligned prompt chunks — one jitted
executable total, every chunk padded to ``chunk_tokens``. Concurrent
prefills are ordered earliest-deadline-first and round-robined one
chunk at a time (fair share); at least one chunk always runs so a
saturated decode batch cannot starve admission. A mid-prefill slot is
invisible to the decode path, and on the paged cache its block-table
row stays parked on scratch until the final chunk installs it — the
garbage row co-tenant ticks write for every slot must never land in a
shared page. The final chunk yields the same first-token logits
position as monolithic prefill and samples with the same key, so the
COMMITTED token streams are bit-identical to the synchronous
scheduler: chunking only reorders when prompt work happens, never what
any request observes.

Speculative decoding (``spec_k > 0``): each tick first asks the
host-side n-gram drafter (``serving.draft``) for up to ``spec_k``
candidate tokens per slot, then runs ONE verify step over the k+1
candidate positions (``serving.decode``), samples every position with
the key the plain stream would have used there
(``fold_in(seed, n_generated + j)``, folded from the same base keys in
the grid sampler's program), and commits the longest prefix
where the samples reproduce the drafts, plus the first non-matching
sample — 1..k+1 tokens per slot per tick. Because the keys are the
plain stream's keys, the committed tokens are BIT-IDENTICAL to plain
decode; acceptance only changes the step count (see
``serving.sampling``). A tick that commits m tokens advances the
scheduler clock by m, so deadlines and watchdog progress stay
comparable between modes. The tick degrades to a plain decode step
whenever every draft is empty (including a fired ``draft_exec`` fault
site) or any active slot lacks ``spec_k + 1`` rows of cache headroom.

Model-based & tree speculation (PR 12) layer three upgrades onto that
base, each independently switchable and all preserving the committed
streams bit-for-bit:

- **model drafting** (``draft_model=``): a tiny TP-sharded draft GPT
  (``serving.draft_model.DraftModel``) replaces the n-gram lookup,
  advanced in lockstep with the target's slots and re-synced by common
  prefix after rejections. Its ``draft_exec`` fault ladder degrades
  model draft → n-gram draft → plain tick, charging no retry budget.
- **tree speculation** (``tree_spec=True``): drafts become small trees
  (chain + alternate root branch) verified in ONE tree-attention
  forward (``decode.make_paged_tree_verify_fn``); the accept walk
  (``sampling.tree_speculative_accept``) follows the sampled
  root-to-leaf path. Cache lengths only ever advance by the
  row-contiguous committed prefix; committed tokens stranded off the
  leftmost chain are RE-SENT as next tick's forced chain (the
  forced-prefix rule — bounded by tree depth, never compounding).
- **adaptive depth** (``adaptive_spec=True``): a per-stream EWMA of
  the measured acceptance rate scales each slot's draft depth between
  0 (plain ticks, with a periodic probe) and ``spec_k``, and the
  verify grid narrows to the widest draft actually proposed — so a
  stream that stops accepting stops paying for speculation.

Failure is an expected state (the dynamic-loss-scaler discipline,
applied to serving — see ``serving.health``): pool exhaustion, NaN
logits, bad samples, and transient exec faults all degrade gracefully
instead of crashing or spinning:

- **typed taxonomy** — ``PagedDecodeEngine.prefill`` raises
  :class:`~apex_tpu.serving.health.PoolExhausted` instead of returning
  ``None``; every request ends in a
  :class:`~apex_tpu.serving.health.RequestOutcome` with a typed
  reason, in ``scheduler.outcomes``.
- **quarantine + retry budget** — non-finite logits (the gate's flag,
  read back beside the tokens) or an out-of-vocabulary sampled token
  quarantines the slot: the corrupt token is never committed, the
  slot is freed and the request requeued
  at the queue FRONT with its progress. Because resume re-prefills the
  committed tokens and keys depend only on ``(seed, n_generated)``,
  the recovered stream is bit-identical to the fault-free one — and
  co-tenant slots never notice. Each fault-path requeue charges the
  request's retry budget (``max_retries``); exhaustion terminates it
  with ``RetryBudgetExhausted``. Capacity preemptions stay free: they
  consume no budget (pressure is not the request's fault).
- **backpressure** — ``max_queue`` bounds the admission queue;
  ``submit`` sheds load with ``AdmissionRejected`` beyond it.
- **deadlines** — ``Request.deadline_ticks`` bounds a request's
  lifetime in scheduler ticks (deterministic, unlike wall clocks);
  overruns terminate with ``DeadlineExceeded`` and partial tokens.
- **watchdog** — ``run()`` raises a diagnostic
  :class:`~apex_tpu.serving.health.LivelockError` (stuck requests +
  pool snapshot) after ``watchdog_limit`` ticks without progress,
  instead of spinning (the PR-8 COW livelock, generalized). Progress
  is strictly monotonic evidence of convergence: a token committed, a
  request terminated, or a (finite) retry consumed — capacity
  preemptions deliberately do NOT count.
- **audit** — ``audit=True`` runs the engine's pool-invariant checker
  after every tick (the chaos tier's setting).

Fault injection (``serving.faults``) drives all of these paths
deterministically: the engines consult their
:class:`~apex_tpu.serving.faults.FaultInjector` at the named sites
through host-side hooks, so the jitted programs — and a replayed chaos
run — stay bit-exact.

The engine's cache is DONATED to each jitted step (see
``serving.decode``); ``PagedDecodeEngine`` immediately rebinds
``self.cache``, so never hold a stale reference to it across a step.
"""

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import GPTConfig
from apex_tpu.serving.cache import (
    NULL_PAGE, RESERVED_PAGES, SCRATCH_PAGE, audit_block_tables,
    MODEL_POOLS, init_hybrid_cache, init_paged_cache, max_pages_per_slot,
)
from apex_tpu.serving.decode import (
    make_copy_page_fn, make_model_decode_fn, make_model_prefill_fn,
    make_paged_chunk_prefill_fn, make_paged_decode_fn,
    make_paged_prefill_fn, make_paged_tree_verify_fn,
    make_paged_verify_fn, model_cores,
)
from apex_tpu.serving.draft import ngram_draft, tree_arrays
from apex_tpu.serving.faults import FaultInjector, InjectedFault
from apex_tpu.serving.health import (
    AdmissionRejected, DeadlineExceeded, LivelockError, NonFiniteLogits,
    PoolExhausted, PromoteFailed, QuotaExhausted, RequestOutcome,
    RetryBudgetExhausted, ServingStats, SpillFailed,
)
from apex_tpu.quant.params import is_quantized_tree
from apex_tpu.serving.observe import Tracer
from apex_tpu.serving.paging import (
    PAGE_KEY_VERSION, SPILL_DTYPE_TAGS, PagePool, PrefixRegistry,
    SpillRecord, decode_spill_header, encode_spill_header,
    prefix_page_keys, spill_checksum,
)
from apex_tpu.serving.transfer import (
    make_extract_pages_fn, make_extract_pages_quant_fn,
    make_insert_pages_fn, make_insert_pages_quant_fn,
)
from apex_tpu.serving.sampling import (
    sample_stream_checked, sample_stream_grid_checked,
    tree_speculative_accept,
)
from apex_tpu.utils.profiler import span as profiler_span
from apex_tpu.utils.seqlen import bucket_for, default_buckets


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``temperature <= 0`` means greedy;
    ``seed`` roots this request's PRNG stream (independent of slot
    placement and co-tenants). ``deadline_ticks``, when set, bounds the
    request's lifetime in scheduler ticks since submission — a
    deterministic deadline (overruns end in a ``deadline`` outcome with
    the tokens committed so far). ``tenant_id`` names the traffic
    class the tenancy front-end (``serving.tenancy``) accounts the
    request under; the default tenant keeps the untenanted scheduler
    byte-compatible."""
    prompt: Tuple[int, ...]
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0
    deadline_ticks: Optional[int] = None
    tenant_id: str = "default"


@dataclasses.dataclass
class _PrefillProgress:
    """In-flight chunked prefill for a slot: the full teacher-forcing
    sequence being prefilled, the next chunk's start position, and the
    engine's opaque staging state from ``begin_chunk_prefill`` (page
    plan, prefix keys). While ``_Slot.prefill`` holds one of these the
    slot owns cache capacity but is invisible to the decode path."""
    tokens: Tuple[int, ...]
    next: int
    state: Dict


def _on_profiler_clock(tracer: Optional[Tracer]) -> Tracer:
    """The engine's tracer (a disabled one when none was given), its
    spans also opened on the profiler's clock: ``observe`` imports no
    jax, so the ``apex:`` span factory is injected here."""
    if tracer is None:
        tracer = Tracer(enabled=False)
    if tracer.annotate is None:
        tracer.annotate = profiler_span
    return tracer


def _refuse_for_recurrent(cfg, **features) -> None:
    """A model with recurrent layers (``cfg.recurrent``) is served by
    monolithic prefill and plain decode only. Its per-slot state is whole at
    every moment: each feature below would need a SNAPSHOT of it (kept,
    rolled back, shipped or re-rounded along with the pages), which no
    program takes yet (ROADMAP, Queue 2). So the feature is refused by name
    where it is asked for, at construction, and never falls back in
    silence. ``features``: name -> (asked for, what it would need)."""
    if getattr(cfg, "recurrent", False):
        _refuse(cfg, "for a model with recurrent layers", features)


def _refuse_over_an_indexed_pool(cfg, **features) -> None:
    """A model whose attention picks its rows (``cfg.indexed``) keeps a page's
    index keys beside the page, under the same page id
    (``HybridKVCache.index``). A page that is shared, copied on write or
    shipped to another engine would have to take its keys along, and no
    program here moves them: refused by name, whatever else would refuse
    it too."""
    if getattr(cfg, "indexed", False):
        _refuse(cfg, "over an indexed pool", features)


def _refuse(cfg, where: str, features) -> None:
    for name, (asked, needs) in features.items():
        if asked:
            raise ValueError(f"{name} is not offered {where} "
                             f"({type(cfg).__name__}): {needs}")


def _refuse_without_a_core(cfg, **features) -> None:
    """A model that brings its own cores (``serving.decode``, "the seam")
    and keeps no per-slot state brings exactly two, monolithic prefill and
    plain decode, over pools only they can read, which the config names
    (``cfg.pools``, a key of ``serving.cache.MODEL_POOLS``: ONE pool of rows
    that are key and value at once, or the full layers' pool and the window
    layers' cycle beside it). On the HOST a page of the pool the block table
    walks is a page like any other: prefix sharing, copy-on-write and
    preemption by requeue are offered (a window layer's cycle is rebuilt
    from the whole prompt by every prefill and is never shared or copied).
    What needs a third program over those pools (a verify step, a chunk's
    write-then-attend, the quantized pool's write and gather, dequant-fused
    projections) is refused by name, over THIS model's pools in the table's
    words, where it is asked for, at construction, and never falls back in
    silence (ROADMAP, M2 and M3). ``features``: name -> (asked for, what it
    would need over its pools, whichever they are)."""
    if model_cores(cfg) and not getattr(cfg, "recurrent", False):
        _refuse(cfg, "over " + MODEL_POOLS[cfg.pools][1], features)


def _pad_on_host(tokens: Sequence[int], buckets: Sequence[int]):
    """``tokens`` as the ``(1, bucket)`` int32 ids and ``(bucket,)`` int32
    mask (1 = real token) a prefill program takes, padded with numpy: what
    ``utils.seqlen.pad_to_bucket`` returns, without the two tiny device
    programs per distinct prompt length that its ``jnp.pad`` and mask
    compile (minutes of a cold start over a few hundred lengths)."""
    n = len(tokens)
    ids = np.zeros((1, bucket_for(n, buckets)), np.int32)
    ids[0, :n] = tokens
    return ids, (np.arange(ids.shape[1]) < n).astype(np.int32)


def _base_key(seed: int) -> np.ndarray:
    """The two uint32 words of ``PRNGKey(seed)``, on the host: the root
    of a request's key schedule, which the sampler programs fold the
    token number into (``sampling.stream_keys``). Taken from ``PRNGKey``
    itself, which has its own rule for a seed outside int32, and once
    per admission: one small program and one read-back, where the tick
    used to run two programs per slot."""
    return np.asarray(jax.random.PRNGKey(seed))


@dataclasses.dataclass
class _Slot:
    request_id: int
    request: Request
    prompt_len: int
    generated: List[int]
    pos: int            # cache rows written (prompt + decode steps)
    base_key: np.ndarray    # _base_key(request.seed)
    prefill: Optional[_PrefillProgress] = None


class PagedDecodeEngine:
    """Owns the params, the cache, and the jitted programs (bucketed
    prefill, batched decode, speculative verify, sampling) over the paged
    cache: a fixed page pool, per-slot block tables, and a host-side
    :class:`PagePool` deciding placement. ``top_k``, ``top_p`` and
    ``spec_k`` are static — engine settings, compiled into the programs
    (``spec_k`` is the DRAFT DEPTH; 0 disables speculation). ``injector``
    hooks the fault sites (inert by default); ``tracer`` hooks the
    observability sites the same way (``serving.observe`` — disabled by
    default: it records nothing, and its ``begin``/``end`` still open the
    phase's ``apex:sched/*`` span on the profiler's clock); ``stats`` is the
    :class:`~apex_tpu.serving.health.ServingStats` counter block the
    scheduler shares, a view over the tracer's metrics registry.

    Admission shares prefixes (page runs keyed by the chained
    prompt-prefix hash are retained instead of recomputed — including a
    partial last page on an exact match) and appends copy on write:
    ``prepare_decode`` runs before every decode tick to allocate
    page-boundary pages and clone any shared page a slot is about to
    append into, so the jitted decode step only ever writes
    exclusively-owned (or scratch) pages.

    **The block table is the host's.** ``_slot_pages`` decides what a
    slot maps, and ``_table`` (numpy, ``[num_slots, max_pages]`` int32)
    is that decision in the layout of the device leaf
    ``cache.block_tables``, which is only a copy. A page boundary, a
    COW retarget and the parking of a freed or preempted slot's row on
    scratch are host writes and mark the table dirty; a prefill program
    writes its slot's row on the device itself and the host mirrors it.
    The invariant: *the device table equals the host table whenever a
    program that goes through it is launched*. Those programs are the
    decode, verify and tree-verify steps (they write a row for EVERY
    slot, active or not, through the table), and each is preceded by
    :meth:`sync_table`: one transfer if the table is dirty, whatever
    the number of slots, boundaries, clones and freed slots behind it,
    and none otherwise. The prefill programs take their pages and rows
    as arguments and never read the leaf, so they need no upload.

    ``free_order`` permutes the initial free list — physical placement
    is an allocator detail the logits provably don't depend on (the
    bit-identity tests drive different orders through this knob).
    """

    #: The tenant whose request the scheduler is currently admitting —
    #: stamped (tenancy mode only) right before ``prefill`` /
    #: ``begin_chunk_prefill`` so composite engines can thread it into
    #: their routing observability and affinity tiebreaks
    #: (``serving.router``). Host state, never read under trace.
    admission_tenant: Optional[str] = None

    def __init__(self, params, cfg: GPTConfig, num_slots: int,
                 max_len: int, num_pages: int, page_size: int,
                 cache_dtype=jnp.bfloat16, top_k: int = 0,
                 top_p: float = 0.0, spec_k: int = 0,
                 buckets: Optional[Sequence[int]] = None,
                 compute_dtype=None,
                 free_order: Optional[Sequence[int]] = None,
                 prefix_sharing: bool = True,
                 injector: Optional[FaultInjector] = None,
                 draft_model=None, tree_spec: bool = False,
                 adaptive_spec: bool = False,
                 tracer: Optional[Tracer] = None,
                 host_tier: Optional[PrefixRegistry] = None,
                 promote_ticks_per_page: float = 0.125):
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages = max_pages_per_slot(max_len, page_size)
        self.prefix_sharing = prefix_sharing
        if buckets is None:
            buckets = default_buckets(max_len, min(128, max_len))
        self.buckets = tuple(sorted({min(int(b), max_len)
                                     for b in buckets}))
        bad = [b for b in self.buckets if b % page_size]
        if bad:
            raise ValueError(
                f"paged prefill writes whole pages: buckets {bad} are "
                f"not multiples of page_size {page_size}")
        self.top_k = top_k
        self.top_p = top_p
        self.spec_k = spec_k
        self._check_spec_config(draft_model, tree_spec, adaptive_spec)
        if tree_spec and jnp.dtype(cache_dtype) == jnp.int8:
            raise ValueError(
                "tree verify is not offered over the int8 page pool: a "
                "branch commit would re-round committed history at "
                "branch-dependent scales; kv8 keeps linear speculation")
        self.recurrent = bool(getattr(cfg, "recurrent", False))
        _refuse_over_an_indexed_pool(
            cfg,
            prefix_sharing=(prefix_sharing, "a shared page, and its copy on "
                            "the first write, would have to carry the "
                            "page's index keys; build the engine with "
                            "prefix_sharing=False"))
        _refuse_for_recurrent(
            cfg,
            prefix_sharing=(prefix_sharing, "a shared page stands for "
                            "tokens whose recurrent state was never kept; "
                            "build the engine with prefix_sharing=False"),
            draft_model=(draft_model is not None, "speculation is refused, "
                         "so a drafter has no use"),
            tree_spec=(tree_spec, "tree verify scores branches the "
                       "recurrent state cannot fork over"),
            spec_k=(spec_k > 0, "a rejected draft would have to roll the "
                    "recurrent state back, and verify advances k+1 tokens "
                    "with no state kept in between"),
            **{"the int8 pool (cache_dtype=int8)": (
                jnp.dtype(cache_dtype) == jnp.int8, "the recurrent layers' "
                "programs are not built over the quantized pool's "
                "per-layer write and gather"),
               "the host tier (host_tier=)": (
                   host_tier is not None, "a promoted prefix skips "
                   "computing its tokens, which needs the recurrent state "
                   "at the page boundary"),
               "weight-only int8 (a quantized tree)": (
                   is_quantized_tree(params), "the recurrent layers have "
                   "no dequant-fused projections"),
               "compute_dtype": (
                   compute_dtype is not None, "its programs fix their "
                   "precision: each product's inputs in the weights' "
                   "dtype, float32 between two products")})
        _refuse_without_a_core(
            cfg,
            draft_model=(draft_model is not None, "speculation is refused, "
                         "so a drafter has no use"),
            tree_spec=(tree_spec, "tree verify needs a core that scores "
                       "k+1 queries a slot under an ancestor mask over its "
                       "pools"),
            spec_k=(spec_k > 0, "verify needs a core that writes and "
                    "attends k+1 rows a slot in each of its pools, and can "
                    "roll a rejected draft back there"),
            **{"the int8 pool (cache_dtype=int8)": (
                jnp.dtype(cache_dtype) == jnp.int8, "its cores write and "
                "read rows in the pool's own dtype and keep no per-page "
                "scale"),
               "the host tier (host_tier=)": (
                   host_tier is not None, "a promoted prefix is attended by "
                   "the suffix as a chunk, which needs a chunked-prefill "
                   "core"),
               "weight-only int8 (a quantized tree)": (
                   is_quantized_tree(params), "its cores have no "
                   "dequant-fused projections"),
               "compute_dtype": (
                   compute_dtype is not None, "its programs fix their "
                   "precision: each product's inputs in the weights' "
                   "dtype, float32 between two products")})
        self.model_cores = model_cores(cfg)
        self.draft_model = draft_model
        self.tree_spec = tree_spec
        self.adaptive_spec = adaptive_spec
        self.injector = injector or FaultInjector()
        self.tracer = _on_profiler_clock(tracer)
        self.stats = ServingStats(registry=self.tracer.registry)
        # both quantization levers are independent: weight-only int8 is
        # detected from the tree (dequant-fused dense/logits kernels),
        # kv_dtype=int8 from the cache (the cores branch on the scale
        # leaves the int8 pool carries) — the host side (PagePool, COW,
        # block tables) is dtype-agnostic throughout
        quantized = is_quantized_tree(params)
        self.cache = (init_hybrid_cache if self.recurrent
                      else MODEL_POOLS[cfg.pools][0] if self.model_cores
                      else init_paged_cache)(cfg, num_slots, max_len,
                                             num_pages, page_size,
                                             cache_dtype)
        self.pool = PagePool(num_pages, page_size, free_order,
                             injector=self.injector,
                             host_tier=host_tier)
        # host spill tier (see serving.paging): the pool's eviction
        # sweep calls _spill_page for sole-registry-owned pages; a
        # prefix-registry hit at admission promotes records back via
        # _promote_chain. The staged admission charge reprices the
        # monolithic prefill's sequential depth at (suffix depth +
        # promote ticks) — pure clock accounting, streams untouched.
        self.host_tier = host_tier
        self.promote_ticks_per_page = float(promote_ticks_per_page)
        self._admit_charge: Optional[int] = None
        self._admit_extra = 0
        if host_tier is not None:
            quant = jnp.dtype(cache_dtype) == jnp.int8
            name = jnp.dtype(cache_dtype).name
            if name not in SPILL_DTYPE_TAGS:
                raise ValueError(
                    f"cache dtype {name!r} has no spill wire tag; the "
                    f"host tier speaks {sorted(SPILL_DTYPE_TAGS)}")
            self._spill_geometry = (cfg.num_layers, cfg.num_heads,
                                    page_size, cfg.head_dim,
                                    SPILL_DTYPE_TAGS[name])
            self._tier_extract = (make_extract_pages_quant_fn()
                                  if quant else make_extract_pages_fn())
            self._tier_insert = (make_insert_pages_quant_fn()
                                 if quant else make_insert_pages_fn())
            self.pool.spill_hook = self._spill_page
        self._slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
        # the block table (class docstring): every row parked on scratch,
        # as init_*_cache fills the device leaf
        self._table = np.full((num_slots, self.max_pages), SCRATCH_PAGE,
                              np.int32)
        self._table_dirty = False
        # slots mid-chunked-prefill: their block-table row is parked on
        # scratch (see begin_chunk_prefill), so the audit must not
        # expect it to mirror _slot_pages yet
        self._prefill_parked: set = set()
        if self.model_cores:
            # the ONE path of a model that brings its cores, whatever the
            # family: the model states them, its row width and whether it
            # keeps state beside the pool (serving.decode, "the seam"), and
            # what needs more programs was refused above. Bytes a prefill
            # writes besides its pages (recurrent state), or in a page (a
            # latent pool), or into the slot's cycle (a window pool: its
            # ring_pages pages of K and V in every window layer):
            self._state_bytes = cfg.state_bytes_per_slot() \
                if self.recurrent else None
            self._page_bytes = cfg.kv_layers * page_size * cfg.kv_row_width \
                * jnp.dtype(cache_dtype).itemsize
            self._window_bytes = 2 * self.cache.ring * (
                self.cache.wk.nbytes // self.cache.wk.shape[1]) \
                if getattr(cfg, "window", 0) else None
            self._latent = self.cache.v is None
            self._index_bytes = cfg.index_bytes_per_page(
                page_size, jnp.dtype(cache_dtype).itemsize) \
                if getattr(cfg, "indexed", False) else None
            self._prefill = make_model_prefill_fn(cfg)
            self._decode = make_model_decode_fn(cfg)
            self._chunk_prefill = self._verify = self._tree_verify = None
        else:
            self._prefill = make_paged_prefill_fn(cfg, compute_dtype,
                                                  quantized)
            self._chunk_prefill = make_paged_chunk_prefill_fn(
                cfg, compute_dtype, quantized)
            self._decode = make_paged_decode_fn(cfg, compute_dtype,
                                                quantized)
            self._verify = make_paged_verify_fn(cfg, compute_dtype,
                                                quantized)
            self._tree_verify = make_paged_tree_verify_fn(
                cfg, compute_dtype, quantized) if tree_spec else None
        self._copy = make_copy_page_fn()
        self._sample = jax.jit(sample_stream_checked,
                               static_argnames=("top_k", "top_p"))
        self._sample_grid = jax.jit(sample_stream_grid_checked,
                                    static_argnames=("top_k", "top_p"))

    def _check_spec_config(self, draft_model, tree_spec,
                           adaptive_spec) -> None:
        if (draft_model is not None or tree_spec or adaptive_spec) \
                and self.spec_k < 1:
            raise ValueError(
                "draft_model / tree_spec / adaptive_spec require "
                "spec_k >= 1 (speculation is otherwise disabled)")
        if draft_model is not None:
            if draft_model.num_slots != self.num_slots:
                raise ValueError(
                    f"draft model has {draft_model.num_slots} slots, "
                    f"engine has {self.num_slots}")
            if draft_model.cfg.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({draft_model.cfg.vocab_size} vs "
                    f"{self.cfg.vocab_size})")

    @staticmethod
    def full_pool_pages(num_slots: int, max_len: int,
                        page_size: int) -> int:
        """The ``num_pages`` at which every slot can hold ``max_len``
        tokens at once, so nothing is ever preempted for want of a page
        (the reserved null and scratch pages included)."""
        return num_slots * max_pages_per_slot(max_len, page_size) \
            + RESERVED_PAGES

    def trace_programs(self) -> Dict[str, Any]:
        """This engine's own jitted prefill (at its largest bucket) and
        decode, traced at the shapes :meth:`prefill` and :meth:`decode`
        call them with. Nothing runs and nothing is donated: the result
        is for looking at the programs — ``.jaxpr`` for the kernels they
        hold, ``.lower().compile()`` for XLA's account of their memory."""
        bucket = max(self.buckets)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        return {
            f"prefill_{bucket}": self._prefill.trace(
                self.params, self.cache, i32(1, bucket), i32(bucket),
                i32(), i32(bucket // self.page_size), i32(self.max_pages)),
            "decode": self._decode.trace(
                self.params, self.cache, i32(self.num_slots),
                jax.ShapeDtypeStruct((self.num_slots,), jnp.bool_)),
        }

    def page_demand(self, total_len: int) -> None:
        """Validate a request's worst-case capacity need at submit."""
        need = max_pages_per_slot(min(total_len, self.max_len),
                                  self.page_size)
        usable = self.pool.num_pages - RESERVED_PAGES
        if need > usable:
            raise ValueError(
                f"request needs up to {need} pages but the pool only "
                f"has {usable} usable pages")

    def _stage_pages(self, prompt: Sequence[int]):
        """The pages of an admission, all or nothing: share the longest
        cached prefix run, promote what the host tier holds of the rest,
        allocate private pages for what is left. Returns ``(toks, keys,
        pages, row, covered, promote_ticks)`` — ``covered`` leading pages
        hold rows already (shared or promoted), ``row`` is the slot's
        NULL-padded block-table row — with one reference taken per page;
        raises ``ValueError`` for a prompt beyond ``max_len`` BEFORE touching
        the pool and :class:`PoolExhausted` with every reference released."""
        toks = [int(t) for t in prompt]
        if len(toks) > self.max_len:
            raise ValueError(
                f"prompt length {len(toks)} exceeds cache max_len "
                f"{self.max_len}")
        n_pages = max_pages_per_slot(len(toks), self.page_size)
        keys = prefix_page_keys(toks, self.page_size)
        pages = self.pool.match_prefix(keys) if self.prefix_sharing \
            else []
        promote_ticks = 0
        if self.host_tier is not None and self.prefix_sharing \
                and len(pages) < n_pages:
            promoted, promote_ticks = self._promote_chain(keys, len(pages))
            pages = pages + promoted
        covered = len(pages)
        for _ in range(n_pages - covered):
            p = self.pool.alloc()
            if p is None:
                for q in pages:
                    self.pool.release(q)
                raise PoolExhausted(
                    f"prompt needs {n_pages} pages; pool has "
                    f"{self.pool.num_free} free and nothing left to "
                    "evict", need=n_pages, free=self.pool.num_free,
                    cached=self.pool.num_cached)
            pages.append(p)
        row = np.full((self.max_pages,), NULL_PAGE, np.int32)
        row[:n_pages] = pages
        return toks, keys, pages, row, covered, promote_ticks

    def prefill(self, slot: int, prompt: Sequence[int]) -> jax.Array:
        """Admit ``prompt`` into ``slot``: share the longest cached
        prefix run, allocate private pages for the rest, prefill —
        writing ONLY the private pages (shared ones are redirected to
        scratch; their rows were produced by the original request and
        are reused verbatim) — and register the chain for future
        requests. Raises :class:`PoolExhausted` when the pool can't
        cover the prompt even after LRU eviction, and
        :class:`InjectedFault` under an armed ``prefill_exec`` site;
        BOTH release every transient page reference first, so the
        caller can simply requeue (``check_invariants`` audits this
        rollback). Raises ``ValueError`` for a prompt beyond
        ``max_len`` BEFORE touching the pool (the scheduler's submit
        check normally screens this, but the engine must not leak page
        references when driven directly)."""
        toks, keys, pages, row, covered, promote_ticks = \
            self._stage_pages(prompt)
        n_pages = len(pages)
        fired, _ = self.injector.draw("prefill_exec")
        if fired:
            for q in pages:
                self.pool.release(q)
            raise InjectedFault("prefill_exec",
                                self.injector.calls("prefill_exec") - 1)
        self._slot_pages[slot] = list(pages)
        # either program below stores ``row`` as the slot's row on the
        # device: the host mirrors it and nothing is left to upload
        self._table[slot] = row
        # a host-tier engine skips fully-covered leading pages the way
        # chunked prefill does: the suffix runs as one final "chunk"
        # whose attention gathers the covered pages through the real
        # row — that sequential-depth saving is the promotion's whole
        # TTFT win. The int8 pool keeps the monolithic forward (the
        # chunk core refuses it); its covered pages are still reused
        # verbatim by decode, exactly like HBM-shared ones.
        skip = 0
        if self.host_tier is not None and covered \
                and self.cache.k_scale is None:
            skip = min(covered, max(n_pages - 1, 0))
        start = skip * self.page_size
        trc = self.tracer
        trc.begin("prefill", request_id=trc.admitting, slot=slot,
                  bucket=bucket_for(len(toks) - start, self.buckets),
                  prompt_tokens=len(toks), shared_pages=covered,
                  page_size=self.page_size,
                  **self._prefill_bytes(n_pages - covered))
        ids, mask = _pad_on_host(toks[start:], self.buckets)
        write = self._write_pages(ids.shape[1], skip, pages, covered)
        if skip:
            self.cache, logits = self._chunk_prefill(
                self.params, self.cache, ids, mask, jnp.int32(slot),
                jnp.int32(start), jnp.asarray(write), jnp.asarray(row),
                jnp.asarray(row))
        else:
            self.cache, logits = self._prefill(
                self.params, self.cache, ids, mask, jnp.int32(slot),
                jnp.asarray(write), jnp.asarray(row))
        trc.end("prefill")
        if self.prefix_sharing:
            self.pool.register_prefix(keys, pages)
        if self.host_tier is not None:
            # reprice the admission: the forward only ran the suffix's
            # depth, and each promotion costs transfer ticks (the same
            # pop_admit_charge handshake the disagg handoff uses)
            self._admit_charge = (len(toks) - start) + promote_ticks
        return logits

    def _write_pages(self, bucket: int, first_page: int,
                     pages: Sequence[int], covered: int) -> np.ndarray:
        """One physical page per page of a ``bucket``-token forward that
        starts at the slot's logical page ``first_page``: the slot's own
        page beyond the ``covered`` ones (shared and promoted pages hold
        their rows already and are never rewritten), scratch for those and
        for the pad beyond the prompt."""
        write = np.full((bucket // self.page_size,), SCRATCH_PAGE, np.int32)
        lo = max(covered, first_page)
        hi = min(len(pages), first_page + len(write))
        write[lo - first_page:hi - first_page] = pages[lo:hi]
        return write

    def _prefill_bytes(self, private_pages: int) -> Dict[str, int]:
        """What a prefill of a model that brings its cores writes, by the
        facts its config states (``serving.decode``, "the seam"): the slot's
        recurrent state, its cycle of window pages, its private pages of a
        latent pool; state AND latent pages where both facts hold, and the
        index keys of those pages where the pool is indexed."""
        if not self.model_cores:
            return {}
        said = {}
        if self.recurrent:
            said["state_bytes"] = self._state_bytes
        if self._window_bytes:
            said["window_bytes"] = self._window_bytes
        elif self._latent:
            said["latent_bytes"] = private_pages * self._page_bytes
        if self._index_bytes:
            said["index_bytes"] = private_pages * self._index_bytes
        return said

    # -- chunked prefill ------------------------------------------------

    def begin_chunk_prefill(self, slot: int,
                            prompt: Sequence[int]) -> Dict:
        """Stage a chunked prefill: share the longest cached prefix
        run and allocate the private pages UP FRONT (all-or-nothing,
        with the same rollback as :meth:`prefill`), but run no forward
        yet. While chunks are in flight the slot's block-table row
        stays parked on scratch, on the host and on the device, as
        :meth:`free_slot` left it: co-tenant decode/verify ticks
        write a garbage row for EVERY slot, and a mid-prefill slot's
        write target could be a SHARED page — parking routes those
        writes to the scratch page until the final chunk atomically
        installs the real row. Fully-shared leading pages are skipped
        (their rows are the original owner's, reused verbatim); the
        last page always runs so the final chunk yields the
        first-token logits."""
        toks, keys, pages, row, covered, promote_ticks = \
            self._stage_pages(prompt)
        n_pages = len(pages)
        self._slot_pages[slot] = list(pages)
        self._prefill_parked.add(slot)
        if promote_ticks:
            # promotions cost transfer ticks; chunked admission charges
            # per chunk, so the extra rides the next pop (additively —
            # several staged prefills may promote before one pops)
            self._admit_extra += promote_ticks
        skip = min(covered, max(n_pages - 1, 0))
        return {"keys": keys, "pages": pages, "shared": covered,
                "row": row, "start": skip * self.page_size}

    def chunk_prefill(self, slot: int, chunk: Sequence[int], pos: int,
                      state: Dict, bucket: int,
                      final: bool) -> jax.Array:
        """Run one page-aligned prompt chunk for ``slot``: the chunk's
        tokens write whole private pages (shared and beyond-prompt
        pages redirect to scratch) while attention gathers through the
        real NULL-padded row — earlier chunks' pages AND the shared
        prefix are visible, later positions are masked out. The final
        chunk additionally installs the real block-table row (ending
        the scratch parking, see :meth:`begin_chunk_prefill`). An
        armed ``chunk_prefill_exec`` site raises
        :class:`InjectedFault` before touching the cache — the caller
        frees the slot, which releases every staged page."""
        fired, _ = self.injector.draw("chunk_prefill_exec")
        if fired:
            raise InjectedFault(
                "chunk_prefill_exec",
                self.injector.calls("chunk_prefill_exec") - 1)
        ids, mask = _pad_on_host(chunk, (bucket,))
        write = self._write_pages(bucket, pos // self.page_size,
                                  state["pages"], state["shared"])
        if final:
            store = state["row"]
            self._prefill_parked.discard(slot)
        else:
            store = np.full((self.max_pages,), SCRATCH_PAGE, np.int32)
        trc = self.tracer
        trc.begin("chunk_prefill", slot=slot, pos=pos, bucket=bucket,
                  final=final, shared_pages=state["shared"])
        self.cache, logits = self._chunk_prefill(
            self.params, self.cache, ids, mask, jnp.int32(slot),
            jnp.int32(pos), jnp.asarray(write),
            jnp.asarray(state["row"]), jnp.asarray(store))
        self._table[slot] = store   # what the program stored: mirrored
        trc.end("chunk_prefill")
        return logits

    def finish_chunk_prefill(self, slot: int, state: Dict) -> None:
        """Register the completed prompt's prefix chain for future
        admissions — the same registration monolithic prefill does."""
        if self.prefix_sharing:
            self.pool.register_prefix(state["keys"], state["pages"])

    def pop_admit_charge(self, default: int) -> int:
        """Tick-clock cost of the admission/prefill forward the scheduler
        just ran — consumed (and reset) by
        ``ContinuousBatchingScheduler._charge_work``: the ``default`` (the
        forward's sequential depth) unless cheaper work replaced part of
        that depth. A host-tier prefill stages an ABSOLUTE charge (suffix
        depth + promote ticks); chunked admissions accumulate promote ticks
        ADDITIVELY on top of the per-chunk default; the disaggregated
        composite prices a remote prefill at handoff ticks, and the pool
        composite at the per-link reshard horizon it extends
        (``serving.router``). Purely accounting — sampling keys never see
        the clock."""
        charge, self._admit_charge = self._admit_charge, None
        extra, self._admit_extra = self._admit_extra, 0
        return (default if charge is None else charge) + extra

    def _spill_page(self, key: bytes, page: int) -> None:
        """Pool eviction hook: copy ``page`` (sole-owned by the prefix
        registry, so its content is pristine — COW guarantees no slot
        ever appended to it) out to the host tier under its chain key.
        A fired ``host_spill`` site drops the spill on the floor: the
        prefix simply leaves both tiers and a later admission
        re-prefills it — graceful, nothing retried."""
        fired, _ = self.injector.draw("host_spill")
        if fired:
            self.stats.host_spill_failures += 1
            if self.tracer.enabled:
                self.tracer.instant("host_spill", page=page, ok=False)
            return
        ids = jnp.asarray([page], jnp.int32)
        tiles = self._tier_extract(self.cache, ids)
        if len(tiles) == 4:
            k, v, ks, vs = (np.asarray(t) for t in tiles)
        else:
            k, v = (np.asarray(t) for t in tiles)
            ks = vs = None
        header = encode_spill_header(key, *self._spill_geometry)
        rec = SpillRecord(header, k, v, ks, vs,
                          spill_checksum(header, k, v, ks, vs))
        if self.host_tier.put(key, rec):
            self.stats.host_spills += 1
            self.stats.host_spill_bytes += rec.nbytes
            if self.tracer.enabled:
                self.tracer.instant("host_spill", page=page,
                                    bytes=rec.nbytes)

    def _verify_spill(self, key: bytes, rec: SpillRecord) -> None:
        """Checksum + header verification for a promoted record — the
        same trust boundary the cross-replica page handoff enforces.
        Raises :class:`PromoteFailed` on any mismatch."""
        digest = spill_checksum(rec.header, rec.k, rec.v,
                                rec.k_scale, rec.v_scale)
        if digest != rec.digest:
            raise PromoteFailed(
                f"spill record checksum mismatch for {key.hex()[:16]}",
                key=key.hex())
        hdr = decode_spill_header(rec.header)
        if hdr["key"] != key:
            raise PromoteFailed(
                f"spill header bound to {hdr['key'].hex()[:16]} but "
                f"registered under {key.hex()[:16]}", key=key.hex())
        geom = (hdr["num_layers"], hdr["num_heads"], hdr["page_size"],
                hdr["head_dim"], hdr["dtype_tag"])
        if hdr["version"] != PAGE_KEY_VERSION \
                or geom != self._spill_geometry:
            raise PromoteFailed(
                f"spill geometry {hdr} does not match this engine",
                key=key.hex())

    def _promote_chain(self, keys: List[bytes],
                       start: int) -> Tuple[List[int], int]:
        """Extend an HBM prefix match by promoting consecutive chain
        links from the host tier: for each key past the HBM-shared run,
        verify the registry record, allocate an HBM page and batch-copy
        the payload back in. The chain breaks at the first miss, fired
        ``host_promote`` site, verification failure (the stale record
        is dropped), or pool exhaustion — pages promoted so far are
        kept and the remainder of the prompt re-prefills. Returns
        ``(pages, ticks)``; the caller owns one reference per page and
        must charge ``ticks`` on the work clock."""
        pages: List[int] = []
        records: List[SpillRecord] = []
        failed: Optional[PromoteFailed] = None
        for key in keys[start:]:
            rec = self.host_tier.get(key)
            if rec is None:
                break
            fired, _ = self.injector.draw("host_promote")
            if fired:
                failed = PromoteFailed(
                    "injected host_promote fault", key=key.hex(),
                    pages=len(pages))
                break
            try:
                self._verify_spill(key, rec)
            except PromoteFailed as e:
                self.host_tier.drop(key)
                failed = e
                break
            p = self.pool.alloc()
            if p is None:
                break
            pages.append(p)
            records.append(rec)
        if failed is not None:
            self.stats.host_promote_failures += 1
            if self.tracer.enabled:
                self.tracer.instant("host_promote", ok=False,
                                    pages=len(pages))
        if not pages:
            return [], 0
        ids = jnp.asarray(pages, jnp.int32)
        k = np.concatenate([r.k for r in records], axis=1)
        v = np.concatenate([r.v for r in records], axis=1)
        if records[0].k_scale is not None:
            ks = np.concatenate([r.k_scale for r in records], axis=1)
            vs = np.concatenate([r.v_scale for r in records], axis=1)
            self.cache = self._tier_insert(self.cache, ids, k, v, ks, vs)
        else:
            self.cache = self._tier_insert(self.cache, ids, k, v)
        ticks = max(1, int(np.ceil(
            len(pages) * self.promote_ticks_per_page)))
        nbytes = sum(r.nbytes for r in records)
        self.stats.host_promotes += len(pages)
        self.stats.host_promote_bytes += nbytes
        self.stats.host_promote_ticks += ticks
        if self.tracer.enabled:
            self.tracer.instant("host_promote", pages=len(pages),
                                bytes=nbytes, ticks=ticks)
        return pages, ticks

    def prepare_decode(self, positions: Dict[int, int],
                       n_new: int = 1) -> List[int]:
        """Before a tick writes rows ``pos .. pos + n_new - 1`` for each
        slot (``n_new = spec_k + 1`` on a verify tick): cross each page
        boundary by allocating a fresh page, and clone (COW) a shared
        page about to receive an appended row — unless the failed clone
        alloc's registry eviction left the slot sole owner, in which
        case the append proceeds in place. Pages past the committed
        length may already exist from a prior verify tick's overshoot;
        they were allocated privately then and are simply reused. A
        slot the pool genuinely cannot serve (or whose ``cow_clone``
        fault site fired) is preempted — its pages are released (often
        unblocking the rest of the batch) and the caller requeues the
        request.

        Everything here but the clone's page copy is host work: a new
        page and a retarget are written into the host table
        (``_table``), which the step's launch uploads once
        (:meth:`sync_table`); ``stats.page_boundaries`` and
        ``stats.cow_copies`` count what was done."""
        preempted: List[int] = []
        boundaries = 0
        for i, pos in sorted(positions.items()):
            pages = self._slot_pages[i]
            first = pos // self.page_size
            last = (pos + n_new - 1) // self.page_size
            for idx in range(first, last + 1):
                if idx == len(pages):                   # page boundary
                    p = self.pool.alloc()
                    if p is None:
                        self._preempt(i, preempted)
                        break
                    pages.append(p)
                    self._table[i, idx] = p
                    self._table_dirty = True
                    boundaries += 1
                elif self.pool.needs_copy(pages[idx]):  # COW
                    dst = None if self.injector.fire("cow_clone") \
                        else self.pool.alloc()
                    if dst is None:
                        # the failed alloc's LRU sweep emptied the
                        # prefix registry; if the page's only co-owner
                        # was the registry the append is now in-place
                        # legal — no copy needed. Preempting instead
                        # would livelock: re-admission recreates the
                        # exact same state (registered partial last
                        # page at refcount 2, pool at the validated
                        # worst-case fit)
                        if not self.pool.needs_copy(pages[idx]):
                            continue
                        self._preempt(i, preempted)
                        break
                    self.stats.cow_copies += 1
                    self.cache = self._copy(self.cache,
                                            np.int32(pages[idx]),
                                            np.int32(dst))
                    self._table[i, idx] = dst
                    self._table_dirty = True
                    self.pool.release(pages[idx])
                    pages[idx] = dst
        if boundaries:
            self.stats.page_boundaries += boundaries
        return preempted

    def _preempt(self, slot: int, preempted: List[int]) -> None:
        self.free_slot(slot)
        self.stats.preemptions += 1
        preempted.append(slot)

    def free_slot(self, slot: int) -> None:
        """Release the slot's page references (and the attached draft
        model's lockstep cache row, when present) and park its block-table
        row on scratch (a freed slot's parked decode writes must never
        land in a page the allocator may hand to someone else). The
        parking is a host write: the decode program writes a row for
        every slot, active or not, and :meth:`sync_table` uploads the
        table before that program is launched, so the device never
        steps through the freed row. No device program runs here."""
        for p in self._slot_pages[slot]:
            self.pool.release(p)
        self._slot_pages[slot] = []
        self._prefill_parked.discard(slot)
        row = self._table[slot]
        if (row != SCRATCH_PAGE).any():     # a staged slot is parked already
            row[:] = SCRATCH_PAGE
            self._table_dirty = True
        if self.draft_model is not None:
            self.draft_model.free_slot(slot)

    def install_slot(self, slot: int, pages: Sequence[int],
                     length: int) -> None:
        """What a prefill leaves behind for ``slot``, for a prompt whose
        rows were computed elsewhere (the disaggregated handoff,
        ``serving.router``): the slot's page list, its NULL-padded row
        in the host table (uploaded before the next step, like any host
        write) and the prompt's length on the device."""
        self._slot_pages[slot] = list(pages)
        self._table[slot] = NULL_PAGE
        self._table[slot, :len(pages)] = pages
        self._table_dirty = True
        self.cache = self.cache._replace(
            lengths=self.cache.lengths.at[slot].set(jnp.int32(length)))

    def sync_table(self) -> None:
        """Hold the invariant of the class docstring: if a host write has
        not reached the device, replace the leaf by ONE transfer of the
        whole table (a copy of it: the host goes on writing while the
        transfer is in flight). No program runs and none compiles; the
        new leaf is placed as the old one was (its sharding if it was
        committed to one, the default device otherwise, so the step
        programs see the arguments they were compiled for). Whoever
        takes ``engine.cache`` to launch a program of their own calls
        this first."""
        if not self._table_dirty:
            return
        leaf = self.cache.block_tables
        self.cache = self.cache._replace(block_tables=jax.device_put(
            self._table.copy(), leaf.sharding if leaf.committed else None))
        self._table_dirty = False
        self.stats.block_table_uploads += 1

    def decode(self, tokens: jax.Array, active: jax.Array) -> jax.Array:
        """One token for every slot; ``active`` gates length advance.
        Returns (num_slots, V) fp32 logits. An armed ``decode_exec``
        fault site overwrites one deterministic victim row with NaN
        AFTER the jitted step — the compiled program and the other
        rows stay bit-exact, and the finiteness gate in the sampler's
        program (:func:`~apex_tpu.serving.sampling.finite_rows`) must
        catch it."""
        return self._step("decode", self._decode, tokens, active,
                          **self._exec_stats())

    def _exec_stats(self) -> Dict[str, int]:
        """What the ``exec`` span of a decode step says beyond its kind: a
        model with recurrent layers says how many slots' state the step
        updates, the slots that hold a request (each maps pages)."""
        if not self.recurrent:
            return {}
        return {"state_slots": sum(1 for p in self._slot_pages if p)}

    def _step(self, kind: str, program, *args, **said) -> jax.Array:
        """Launch a step program on the cache under the ``exec`` span, the
        block table uploaded first if the host changed it, and draw the
        ``decode_exec`` fault site on its logits: one deterministic victim
        row overwritten with NaN AFTER the jitted step (across all
        positions of a verify grid) — the compiled program and the other
        rows stay bit-exact."""
        trc = self.tracer
        trc.begin("exec", kind=kind, **said)
        self.sync_table()
        self.cache, logits = program(self.params, self.cache, *args)
        trc.end("exec")
        fired, payload = self.injector.draw("decode_exec")
        if fired:
            victim = int(payload % logits.shape[0])
            logits = logits.at[victim].set(jnp.nan)
        return logits

    def sample(self, logits, base, counts, temperature) -> jax.Array:
        """LAUNCH the checked sampler on ``logits`` (B, V) and return its
        result on the device, not waited for: (2, B) int32, ``[0]`` one
        token per row — row b draws with ``fold_in(base[b], counts[b])``,
        derived inside the program
        (:func:`~apex_tpu.serving.sampling.stream_keys`) from the
        request's base key and the number of the token — and ``[1]``
        which rows are finite, i.e. safe to commit
        (:func:`~apex_tpu.serving.sampling.finite_rows`). Called right
        behind the step or prefill that made ``logits``, with host
        arrays for the rest: they go up while the chip still runs the
        step, the program queues behind it, and the copy down starts
        when it ends. The caller's ``np.asarray`` is the one wait."""
        return self._launched(self._sample(
            logits, base, counts, temperature, top_k=self.top_k,
            top_p=self.top_p))

    def sample_grid(self, logits, base, counts, temperature) -> jax.Array:
        """:meth:`sample` over a verify step's (B, k1, V) logits: launches
        the checked grid sampler and returns (2, B, k1) int32 on the
        device, every (slot, position) drawn with its own key,
        ``fold_in(base[b], counts[b, j])``, derived in the same program;
        the ``sample`` fault site corrupts the victim slot's FIRST
        position (the one a plain tick would have drawn), so the
        scheduler's range gate quarantines before any commit."""
        return self._launched(self._sample_grid(
            logits, base, counts, temperature, top_k=self.top_k,
            top_p=self.top_p))

    def _launched(self, out: jax.Array) -> jax.Array:
        """A checked sampler's result (2, B[, k1]) on its way down: the
        ``sample`` fault site drawn (it writes into the victim slot's
        token, on a grid its FIRST position), the copy to the host
        started, nothing waited for."""
        fired, payload = self.injector.draw("sample")
        if fired:
            # out-of-vocabulary id: negative, so it can never collide
            # with a real token — the scheduler's range check quarantines
            at = (0, int(payload % out.shape[1])) + (0,) * (out.ndim - 2)
            out = out.at[at].set(jnp.int32(-1 - payload % 7))
        out.copy_to_host_async()
        return out

    # -- speculative decoding -------------------------------------------

    def draft(self, history: Sequence[int]) -> List[int]:
        """Host-side n-gram draft of up to ``spec_k`` candidates from
        one slot's prompt+generated history. An armed ``draft_exec``
        fault site raises :class:`InjectedFault` — the scheduler
        degrades that slot to an empty draft (plain decode pace) for
        the tick; drafting is best-effort, so no retry budget is
        charged."""
        fired, _ = self.injector.draw("draft_exec")
        if fired:
            raise InjectedFault("draft_exec",
                                self.injector.calls("draft_exec") - 1)
        return ngram_draft(history, self.spec_k)

    def _draft_ladder(self) -> bool:
        """The model drafter's two-rung ``draft_exec`` ladder: one draw
        decides whether the MODEL draft fails this tick; a fired draw
        counts a draft fault and takes a second draw deciding whether
        the n-gram fallback fails too (raising :class:`InjectedFault`,
        which the scheduler turns into a plain tick). Returns True when
        the caller should use the n-gram rung. No rung charges retry
        budget — drafting is best-effort."""
        fired, _ = self.injector.draw("draft_exec")
        if not fired:
            return False
        self.stats.draft_faults += 1
        fired, _ = self.injector.draw("draft_exec")
        if fired:
            raise InjectedFault("draft_exec",
                                self.injector.calls("draft_exec") - 1)
        return True

    def draft_batch(self, histories, ks) -> List[List[int]]:
        """Model-draft every slot in ONE batched call: up to ``ks[i]``
        greedy continuation tokens of ``histories[i]`` from the
        attached :class:`~apex_tpu.serving.draft_model.DraftModel`
        (``None`` history or ``k = 0`` yields an empty draft). The
        ``draft_exec`` ladder (:meth:`_draft_ladder`) degrades model →
        n-gram → plain."""
        if self._draft_ladder():
            return [list(ngram_draft(h, k)) if h is not None else []
                    for h, k in zip(histories, ks)]
        return [[int(t) for t in c]
                for c in self.draft_model.draft(histories, ks)]

    def draft_tree_batch(self, histories, ks):
        """Tree drafts (``(tokens, parents)`` per slot, ``None`` when
        inactive) from the model drafter — a greedy chain plus an
        alternate root branch, see :meth:`DraftModel.draft_tree`. The
        same ``draft_exec`` ladder applies; its n-gram rung emits
        single-chain trees."""
        if self._draft_ladder():
            out = []
            for h, k in zip(histories, ks):
                c = [int(t) for t in ngram_draft(h, k)] \
                    if h is not None else []
                out.append((c, [-1] + list(range(len(c) - 1)))
                           if c else None)
            return out
        return self.draft_model.draft_tree(histories, ks)

    def verify(self, tokens: jax.Array) -> jax.Array:
        """One speculative verify step: ``tokens`` (num_slots, spec_k+1)
        int32 — column 0 the pending token, columns 1.. the (0-padded)
        drafts. Returns (num_slots, spec_k+1, V) fp32 logits; slot
        lengths are committed separately (:meth:`commit`) once the host
        accept walk knows each slot's count. The ``decode_exec`` fault
        site covers this step too (the victim row goes NaN across all
        positions, post-jit)."""
        return self._step("verify", self._verify, tokens,
                          k1=int(tokens.shape[1]))

    def tree_verify(self, tokens: jax.Array, depth: jax.Array,
                    anc: jax.Array) -> jax.Array:
        """One tree-attention verify step over a packed draft grid (see
        :func:`~apex_tpu.serving.draft.tree_arrays`): column j writes
        K/V at physical row ``lengths + j`` with sequence position
        ``lengths + depth[:, j]`` and attends committed rows plus its
        ancestor columns under ``anc``. Returns (num_slots, k1, V) fp32
        logits; commits stay host-side (:meth:`commit`). Shares the
        ``decode_exec`` fault site with the other step kinds."""
        return self._step("tree_verify", self._tree_verify, tokens, depth,
                          anc, k1=int(tokens.shape[1]))

    def commit(self, counts: Sequence[int]) -> None:
        """Advance slot lengths by each slot's committed token count —
        the host half of the verify step's rollback contract: rows
        beyond ``lengths + count`` were written but are never admitted
        by any mask before the next step re-writes them."""
        trc = self.tracer
        trc.begin("commit")
        self.cache = self.cache._replace(
            lengths=self.cache.lengths
            + jnp.asarray(counts, jnp.int32))
        trc.end("commit")

    def check_invariants(self) -> bool:
        """Full pool audit: host-side refcount/free-list/registry
        accounting against the per-slot page lists
        (:meth:`PagePool.check_invariants`), then the device block
        tables against those same lists
        (:func:`~apex_tpu.serving.cache.audit_block_tables`): what the
        next step would go through, so a pending host write is uploaded
        first (:meth:`sync_table`) and the audit holds the device leaf,
        and with it the host table, against ``_slot_pages``. Raises
        :class:`~apex_tpu.serving.health.PoolInvariantError`."""
        self.pool.check_invariants(self._slot_pages)
        self.sync_table()
        # mid-chunked-prefill slots hold pages but park their device
        # row on scratch until the final chunk installs it — audit
        # those rows as empty (all scratch/null) instead
        expect = [[] if i in self._prefill_parked else p
                  for i, p in enumerate(self._slot_pages)]
        audit_block_tables(self.cache.block_tables, expect)
        return True

    def read_counters(self) -> Optional[Dict[str, np.ndarray]]:
        """What the model's decode program has counted on the device since
        the engine was built (``cfg.counter_shapes``: int32, so a caller
        takes differences), fetched now: one read-back, on request, which no
        tick makes. ``None`` for a model that counts nothing."""
        counters = getattr(self.cache, "counters", None)
        if counters is None:
            return None
        return {name: np.asarray(value)
                for name, value in jax.device_get(counters).items()}

    def pool_snapshot(self) -> Dict:
        snap = self.pool.snapshot()
        snap["slot_pages"] = [list(p) for p in self._slot_pages]
        return snap

    def pool_gauges(self) -> Dict[str, float]:
        gauges = {"free": self.pool.num_free,
                  "cached": self.pool.num_cached,
                  "occupancy": self.pool.occupancy}
        if self.host_tier is not None:
            stats = self.pool.stats()
            gauges["hbm_used"] = stats["hbm_used"]
            gauges["host_pages"] = stats["host_pages"]
            gauges["host_bytes"] = stats["host_bytes"]
            gauges["host_hit_rate"] = stats["host_hit_rate"]
        return gauges


class ContinuousBatchingScheduler:
    """FIFO → fixed slots → batched decode ticks, with the
    graceful-degradation layer (see module doc): typed outcomes in
    ``self.outcomes``, shared ``self.stats`` counters, per-request
    retry budgets, deterministic deadlines, bounded admission, a
    progress watchdog, and an optional per-tick invariant audit."""

    def __init__(self, engine: PagedDecodeEngine, eos_id: int, *,
                 max_retries: int = 3, max_queue: Optional[int] = None,
                 watchdog_limit: int = 64, audit: bool = False,
                 chunk_tokens: Optional[int] = None,
                 tick_token_budget: Optional[int] = None,
                 tenancy=None, streams=None):
        self.engine = engine
        self.eos_id = eos_id
        self.max_retries = max_retries
        self.max_queue = max_queue
        self.watchdog_limit = watchdog_limit
        self.audit = audit
        # chunked prefill: split every admission's prompt forward into
        # chunk_tokens-sized pieces run BETWEEN decode ticks under a
        # per-tick token budget (see _prefill_phase). None keeps the
        # classic monolithic admission prefill.
        _refuse_for_recurrent(
            engine.cfg,
            **{"chunked prefill (chunk_tokens=)": (
                chunk_tokens is not None, "a chunk would have to start "
                "from the recurrent state the chunk before it left, which "
                "no program carries")})
        _refuse_without_a_core(
            engine.cfg,
            **{"chunked prefill (chunk_tokens=)": (
                chunk_tokens is not None, "a chunk attends the rows of the "
                "chunks before it, which needs a core that reads them back "
                "out of its pools at prompt length")})
        if chunk_tokens is not None:
            chunk_tokens = int(chunk_tokens)
            if chunk_tokens < 1:
                raise ValueError(f"chunk_tokens must be >= 1, got "
                                 f"{chunk_tokens}")
            if engine.max_len % chunk_tokens:
                raise ValueError(
                    f"chunk_tokens {chunk_tokens} must divide the "
                    f"cache max_len {engine.max_len} (chunk starts "
                    "must never overrun the cache row)")
            if chunk_tokens % engine.page_size:
                raise ValueError(
                    f"paged chunks write whole pages: chunk_tokens "
                    f"{chunk_tokens} is not a multiple of page_size "
                    f"{engine.page_size}")
            if engine.cache.k_scale is not None:
                raise ValueError(
                    "chunked prefill is not offered over the int8 "
                    "page pool: incremental chunk writes would "
                    "re-round committed history at chunk-dependent "
                    "scales; kv8 keeps monolithic prefill")
        self.chunk_tokens = chunk_tokens
        if tick_token_budget is not None:
            tick_token_budget = int(tick_token_budget)
            if tick_token_budget < 1:
                raise ValueError(f"tick_token_budget must be >= 1, "
                                 f"got {tick_token_budget}")
        elif chunk_tokens is not None:
            # default: every decode slot's token plus one prefill chunk
            tick_token_budget = engine.num_slots + chunk_tokens
        self.tick_token_budget = tick_token_budget
        self.stats = engine.stats  # one counter block per engine
        self.tracer = engine.tracer  # one tracer per engine, like stats
        self.outcomes: Dict[int, RequestOutcome] = {}
        self._queue: deque = deque()
        self._slots: List[Optional[_Slot]] = [None] * engine.num_slots
        self._next_id = 0
        self._retries: Dict[int, int] = {}
        self._submit_tick: Dict[int, int] = {}
        # tick-clock latency bookkeeping (feeds RequestOutcome.ttft/
        # total_ticks and, when tracing, the TTFT/ITL histograms)
        self._first_token_tick: Dict[int, int] = {}
        self._last_token_tick: Dict[int, int] = {}
        # ticks that ran prefill work per request (feeds
        # RequestOutcome.prefill_ticks); accumulates across retries
        self._prefill_ticks: Dict[int, int] = {}
        self._tick_no = 0
        self._tokens_emitted = 0
        # progress-watchdog state (instance-held so external drivers
        # can call step() directly, as benchmark/runners/gpt_serve.py does)
        self._stalled = 0
        self._watch_snap = None
        self._tree_accept = jax.jit(tree_speculative_accept)
        # adaptive controller state: per-slot EWMA of the measured
        # draft acceptance rate (reset to optimistic 1.0 at admission);
        # converged-off slots get one probe draft every _probe_every
        # ticks so repetitive text can re-earn its depth
        self._accept_ewma = [1.0] * engine.num_slots
        self._probe_every = 16
        # tenancy front-end (serving.tenancy): admission selection,
        # quotas, priority preemption, per-tenant SLOs. None keeps the
        # untenanted FIFO path byte-identical. The quota ledger hangs
        # under the engine's page pool so the per-tick invariant audit
        # covers the reservation books.
        self.tenancy = tenancy
        if tenancy is not None:
            engine.pool.ledger = tenancy.ledger
        # per-token streaming (serving.streaming): streams=True builds
        # a StreamMux on the engine's injector/tracer/stats; passing a
        # StreamMux keeps the caller's sink. None disables staging.
        if streams is True:
            from apex_tpu.serving.streaming import StreamMux
            streams = StreamMux(injector=engine.injector,
                                tracer=engine.tracer, stats=engine.stats)
        self.streams = streams
        self._req_tenant: Dict[int, str] = {}
        # worst inter-token gap per request (tenancy mode only — feeds
        # the ITL SLO check at finish)
        self._max_itl: Dict[int, int] = {}

    @property
    def clock(self) -> int:
        """The scheduler's work-charged tick clock (decode-step
        equivalents): every forward advances it by the sequential
        depth it covers, so open-loop load generators can pace
        arrivals against it as a wall-time proxy."""
        return self._tick_no

    def advance_clock(self, tick: int) -> None:
        """Fast-forward an idle scheduler's clock to ``tick`` (no-op
        when already past it): load generators jump over quiet gaps
        between arrivals instead of spinning empty ticks through the
        watchdog."""
        self._tick_no = max(self._tick_no, int(tick))
        if self.tracer.enabled:
            self.tracer.set_tick(self._tick_no)

    def submit(self, request: Request,
               at_tick: Optional[int] = None) -> int:
        if self.max_queue is not None \
                and len(self._queue) >= self.max_queue:
            self.stats.admission_rejections += 1
            raise AdmissionRejected(
                f"admission queue is at its bound ({self.max_queue}); "
                "shed load and retry after completions")
        if not len(request.prompt):
            raise ValueError("empty prompt")
        if len(request.prompt) > self.engine.max_len:
            raise ValueError(
                f"prompt length {len(request.prompt)} exceeds cache "
                f"max_len {self.engine.max_len}")
        # fail fast at submit, not mid-run inside _admit: the prompt
        # must have a bucket rung and (paged) fit the pool even running
        # alone at its worst-case generated length — plus the verify
        # step's overshoot (speculative writes can land up to spec_k
        # rows past the final committed token)
        bucket_for(len(request.prompt), self.engine.buckets)
        self.engine.page_demand(
            len(request.prompt) + request.max_new_tokens
            + self.engine.spec_k)
        ten = self.tenancy
        if ten is not None:
            if not ten.has(request.tenant_id):
                raise ValueError(
                    f"unknown tenant {request.tenant_id!r}: declare it "
                    "in the TenancyPolicy before submitting under it")
            # the quota analogue of the page_demand fail-fast above: a
            # request whose worst-case reservation can NEVER fit its
            # tenant's quota is refused typed at submit, not deferred
            # forever at admission
            need = self._quota_need(request)
            if not ten.fits_quota(request.tenant_id, need):
                self.stats.quota_exhausted += 1
                raise QuotaExhausted(
                    f"request needs {need} pages worst-case but tenant "
                    f"{request.tenant_id!r} is capped at "
                    f"{ten.tenants[request.tenant_id].page_quota}",
                    tenant=request.tenant_id, need=need,
                    quota=ten.tenants[request.tenant_id].page_quota)
        rid = self._next_id
        self._next_id += 1
        self._req_tenant[rid] = request.tenant_id
        if ten is not None:
            # idle -> backlogged bookkeeping: clamps a RETURNING
            # tenant's vtime to the busy floor; a tenant with work
            # already outstanding keeps its fair-share deficit
            ten.note_enqueued(request.tenant_id)
        if self.streams is not None:
            self.streams.open(rid, request.tenant_id)
        # ``at_tick`` backdates the arrival for open-loop drivers: a
        # charged forward can jump the clock PAST a request's true
        # arrival time before the driver gets to submit it, and the
        # wait spent behind that forward must still show up in TTFT
        # (and burn the deadline) — otherwise monolithic prefill hides
        # exactly the head-of-line blocking the chunked scheduler is
        # measured against
        self._submit_tick[rid] = self._tick_no if at_tick is None \
            else min(int(at_tick), self._tick_no)
        trc = self.tracer
        if trc.enabled:
            trc.instant("submitted", request_id=rid,
                        prompt_len=len(request.prompt))
        # third element: tokens already generated — empty for fresh
        # submissions, carried through preemption/quarantine requeue
        self._queue.append((rid, request, []))
        return rid

    def _sampler_inputs(self):
        """What a decode tick's programs read of the slots, one
        host-built array each whatever the number of slots: the pending
        token, the decoding flag, the temperature, the request's base
        key and the number of the token to sample (``len(generated)``).
        The sampler folds the last into the base key on the device
        (``sampling.stream_keys``): token n of a request draws with
        ``fold_in(PRNGKey(seed), n)``. A slot that is not decoding
        reads zeros, and nothing reads its sample."""
        n = len(self._slots)
        last = np.zeros((n,), np.int32)
        active = np.zeros((n,), bool)
        temps = np.zeros((n,), np.float32)
        base = np.zeros((n, 2), np.uint32)
        counts = np.zeros((n,), np.int32)
        for i, s in enumerate(self._slots):
            if self._decoding(s):
                last[i] = s.generated[-1]
                active[i] = True
                temps[i] = s.request.temperature
                base[i] = s.base_key
                counts[i] = len(s.generated)
        return last, active, temps, base, counts

    def _await_sampler(self, launched) -> Tuple[np.ndarray, np.ndarray]:
        """The ONE blocking read-back of a tick or an admission: the
        result of ``engine.sample`` / ``sample_grid``, launched behind
        the step, as (tokens, finite) on the host.
        ``stats.sampler_waits`` counts these waits: 1 per decode tick of
        any kind and 1 per first token sampled."""
        out = np.asarray(launched)
        self.stats.sampler_waits += 1
        return out[0], out[1].astype(bool)

    def _first_token(self, logits, base_key: np.ndarray,
                     temperature: float) -> Tuple[int, bool]:
        """Sample a request's token 0 from its prefill logits (1, V)
        with ``fold_in(PRNGKey(seed), 0)``: the tick's checked sampler at
        a batch of one, launched behind the prefill and waited for once.
        Returns the token and whether the logits were finite."""
        toks, finite = self._await_sampler(self.engine.sample(
            logits, base_key[None, :], np.zeros((1,), np.int32),
            np.asarray([temperature], np.float32)))
        return int(toks[0]), bool(finite[0])

    # -- typed termination ------------------------------------------------

    def _finish(self, rid: int, tokens: Sequence[int], reason: str,
                error=None) -> None:
        ttft = None
        if rid in self._first_token_tick:
            ttft = (self._first_token_tick[rid]
                    - self._submit_tick.get(rid, 0))
        total = self._tick_no - self._submit_tick.get(rid, self._tick_no)
        trc = self.tracer
        if trc.enabled:
            if error is not None:
                trc.attach(error)  # ship the flight-recorder ring
            trc.instant("finished", request_id=rid, reason=reason,
                        ok=error is None)
        tenant = self._req_tenant.get(rid, "default")
        ten = self.tenancy
        slo = None
        if ten is not None:
            # the single exit point every request passes through:
            # credit the quota reservation here and ONLY here, so the
            # ledger is leak-free by construction
            ten.credit(rid)
            ten.note_finished(tenant)
            slo = ten.slo_check(tenant, ttft, self._max_itl.get(rid))
            if slo is not None:
                self.stats.slo_violations += 1
                if trc.enabled:
                    trc.attach(slo)
                    trc.instant("slo_violation", request_id=rid,
                                tenant=tenant, metric=slo.metric,
                                observed=slo.observed, bound=slo.bound)
        if self.streams is not None:
            self.streams.finish(rid, reason)
        self.outcomes[rid] = RequestOutcome(
            tuple(int(t) for t in tokens), reason, error,
            retries=self._retries.get(rid, 0),
            ttft_ticks=ttft, total_ticks=total,
            prefill_ticks=self._prefill_ticks.get(rid),
            tenant_id=tenant, slo=slo)

    def _charge_work(self, tokens: int) -> None:
        """Advance the scheduler clock by a prefill forward's
        sequential depth. Same decode-step-equivalents rule as the
        multi-token speculative commit (a tick that commits m tokens
        counts m): a forward that advances one stream by ``tokens``
        positions costs that many ticks, so tick-clock TTFT/ITL and
        deadlines price head-of-line blocking honestly — a monolithic
        S-token prefill opens an ~S-tick gap in co-tenant streams,
        while chunked prefill bounds the gap at the tick token
        budget. Purely an accounting change: sampling keys fold in
        token counts, never ticks, so committed streams are
        untouched. The engine may reprice the charge via
        :meth:`PagedDecodeEngine.pop_admit_charge` — a host-tier promote
        shrinks the forward to the suffix depth but adds transfer
        ticks, and the disaggregated router charges handoff ticks the
        same way."""
        tokens = self.engine.pop_admit_charge(tokens)
        if tokens > 1:
            self._tick_no += tokens - 1
            if self.tracer.enabled:
                self.tracer.set_tick(self._tick_no)

    def _note_token(self, rid: int, slot: int) -> None:
        """Per-committed-token tick-clock bookkeeping. The first token
        stamps TTFT; later ones stamp the inter-token gap (tokens
        within one multi-token speculative commit share a tick, so
        their gap records as 0 — honest SLO accounting)."""
        tick = self._tick_no
        trc = self.tracer
        ten = self.tenancy
        if rid not in self._first_token_tick:
            self._first_token_tick[rid] = tick
            if trc.enabled:
                trc.instant("first_token", request_id=rid, slot=slot)
                trc.observe_ttft(tick - self._submit_tick.get(rid, tick))
                if ten is not None:
                    trc.observe_tenant_ttft(
                        self._req_tenant.get(rid, "default"),
                        tick - self._submit_tick.get(rid, tick))
        else:
            gap = tick - self._last_token_tick[rid]
            if ten is not None and gap > self._max_itl.get(rid, 0):
                self._max_itl[rid] = gap
            if trc.enabled:
                trc.observe_itl(gap)
                if ten is not None:
                    trc.observe_tenant_itl(
                        self._req_tenant.get(rid, "default"), gap)
        self._last_token_tick[rid] = tick
        if ten is not None:
            # stride clock: one committed token advances the tenant's
            # virtual time by 1 / weight
            ten.charge_tokens(self._req_tenant.get(rid, "default"), 1)
        if self.streams is not None:
            # stage for the end-of-tick flush — delivery is host-side
            # fan-out, the committed stream is already in the slot
            self.streams.stage(rid, self._slots[slot].generated[-1])

    def _charge_retry(self, rid: int) -> bool:
        """Consume one unit of ``rid``'s retry budget; True when the
        budget is now exhausted (the caller must terminate it)."""
        trc = self.tracer
        if trc.enabled:
            trc.instant("retried", request_id=rid)
        self.stats.retries += 1
        n = self._retries.get(rid, 0) + 1
        self._retries[rid] = n
        return n > self.max_retries

    def _budget_error(self, rid: int, cause) -> RetryBudgetExhausted:
        return RetryBudgetExhausted(
            f"request {rid}: retry budget ({self.max_retries}) "
            f"exhausted; last fault: {cause}", request_id=rid,
            retries=self._retries.get(rid, 0))

    def _quarantine(self, i: int, err: NonFiniteLogits) -> None:
        """Free a slot whose tick output was corrupt; retry the request
        from its committed tokens (requeue at the FRONT — the resumed
        stream is bit-identical to the uncontended one) or, with the
        budget gone, terminate it typed."""
        s = self._slots[i]
        trc = self.tracer
        if trc.enabled:
            trc.instant("quarantined", request_id=s.request_id, slot=i,
                        cause=str(err))
        self._slots[i] = None
        self.engine.free_slot(i)
        rid = s.request_id
        if self._charge_retry(rid):
            self._finish(rid, s.generated, "retry_budget",
                         self._budget_error(rid, err))
        else:
            self._queue.appendleft((rid, s.request, list(s.generated)))

    def _expire_deadlines(self) -> None:
        def expired(req: Request, rid: int) -> bool:
            return (req.deadline_ticks is not None
                    and self._tick_no - self._submit_tick.get(rid, 0)
                    >= req.deadline_ticks)

        if any(expired(req, rid) for rid, req, _ in self._queue):
            keep: deque = deque()
            for rid, req, resume in self._queue:
                if expired(req, rid):
                    self.stats.deadline_expired += 1
                    self._finish(rid, resume, "deadline",
                                 DeadlineExceeded(
                                     f"request {rid}: queued past its "
                                     f"{req.deadline_ticks}-tick "
                                     "deadline"))
                else:
                    keep.append((rid, req, resume))
            self._queue = keep
        for i, s in enumerate(self._slots):
            if s is not None and expired(s.request, s.request_id):
                self.stats.deadline_expired += 1
                self._slots[i] = None
                self.engine.free_slot(i)
                self._finish(s.request_id, s.generated, "deadline",
                             DeadlineExceeded(
                                 f"request {s.request_id}: exceeded its "
                                 f"{s.request.deadline_ticks}-tick "
                                 "deadline mid-decode"))

    # -- tenancy: selection, quotas, priority preemption ------------------

    def _quota_need(self, req: Request) -> int:
        """Worst-case page reservation for one request: the pages that
        hold prompt + ``max_new_tokens`` + the verify step's spec_k
        overshoot, capped at the cache row — the same sizing the
        submit-time ``page_demand`` fail-fast prices."""
        eng = self.engine
        total = min(len(req.prompt) + req.max_new_tokens + eng.spec_k,
                    eng.max_len)
        return max_pages_per_slot(total, eng.page_size)

    def _promote_next(self) -> bool:
        """Tenancy admission selection: rotate the best queued
        candidate to the queue FRONT (the head-pop admission logic
        then runs unchanged), preserving relative order among the
        rest — FIFO within a tenant. The key is the policy's
        ``(chargeable, priority desc, vtime asc, tenant id)`` with
        queue position appended, so ties resolve deterministically.
        Returns False when every candidate's tenant is quota-blocked:
        admission defers until a completion credits pages back.
        Untenanted schedulers keep strict FIFO (always True)."""
        ten = self.tenancy
        if ten is None:
            return True
        best = None
        best_key = None
        for idx, (rid, req, _resume) in enumerate(self._queue):
            chargeable = ten.can_admit(rid, req.tenant_id,
                                       self._quota_need(req))
            k = ten.selection_key(req.tenant_id, chargeable) + (idx,)
            if best_key is None or k < best_key:
                best_key, best = k, idx
        if best_key[0] == 1:  # even the best candidate is quota-blocked
            self.stats.quota_deferrals += 1
            return False
        if best:
            q = self._queue
            items = list(q)
            sel = items.pop(best)
            q.clear()
            q.append(sel)
            q.extend(items)
        return True

    def _charge_head_admission(self, rid: int, req: Request) -> None:
        """Reserve the queue head's quota pages (idempotent — a
        preempted request being re-admitted already holds its
        reservation) and stamp the admitting tenant on the engine for
        the router's observability/affinity threading. Only called
        after :meth:`_promote_next` returned True, so the charge
        cannot fail."""
        ten = self.tenancy
        if ten is None:
            return
        ten.charge_admission(rid, req.tenant_id, self._quota_need(req))
        self.engine.admission_tenant = req.tenant_id

    def _preempt_for_priority(self) -> None:
        """A strictly-higher-priority waiting tenant may requeue ONE
        resident lower-priority slot per tick — through the exact
        requeue-resume path pool pressure uses (committed tokens ride
        along, re-prefilled on re-admission, streams bit-identical),
        with no retry charged: priority preemption is a capacity
        decision, not a fault. One victim per tick bounds the churn;
        a quota-blocked burst preempts nobody (the freed slot could
        not admit it anyway)."""
        ten = self.tenancy
        if ten is None or not self._queue:
            return
        if any(s is None for s in self._slots):
            return  # a free slot serves the burst without eviction
        best = None
        best_key = None
        for idx, (rid, req, _resume) in enumerate(self._queue):
            chargeable = ten.can_admit(rid, req.tenant_id,
                                       self._quota_need(req))
            k = ten.selection_key(req.tenant_id, chargeable) + (idx,)
            if best_key is None or k < best_key:
                best_key, best = k, req
        if best_key[0] == 1:
            return  # quota-blocked: a preemption could not admit it
        wait_prio = ten.priority(best.tenant_id)
        victim = None
        victim_key = None
        for i, s in enumerate(self._slots):
            rung = ten.priority(s.request.tenant_id)
            if rung >= wait_prio:
                continue  # only STRICTLY lower rungs are preemptible
            k = (rung, -s.request_id)  # lowest rung, then newest work
            if victim_key is None or k < victim_key:
                victim_key, victim = k, i
        if victim is None:
            return
        s = self._slots[victim]
        self.stats.tenant_preemptions += 1
        if self.tracer.enabled:
            self.tracer.instant(
                "preempted", request_id=s.request_id, slot=victim,
                cause="tenant_priority",
                tenant=self._req_tenant.get(s.request_id, "default"))
        self._queue.appendleft((s.request_id, s.request,
                                list(s.generated)))
        self._slots[victim] = None
        self.engine.free_slot(victim)

    # -- admission / decode ticks -----------------------------------------

    def _admit(self) -> None:
        if self.tenancy is not None:
            self._preempt_for_priority()
        if self.chunk_tokens is not None:
            self._admit_chunked()
            return
        eng = self.engine
        for i in range(eng.num_slots):
            if self._slots[i] is not None or not self._queue:
                continue
            if not self._promote_next():
                break
            rid, req, resume = self._queue[0]
            self._charge_head_admission(rid, req)
            # a preempted request resumes by re-prefilling everything
            # it had produced EXCEPT its last sampled token, which the
            # next decode tick feeds (the normal teacher-forcing shape)
            tokens = tuple(req.prompt) + tuple(resume[:-1])
            self.tracer.admitting = rid
            try:
                logits = eng.prefill(i, tokens)
            except PoolExhausted as e:
                # out of pages: keep FIFO order, wait for evictions —
                # unless the pool can't serve the head even with every
                # slot free and no fault injection to blame, which is a
                # submit-validation bug worth surfacing typed
                self.stats.pool_exhausted += 1
                if all(s is None for s in self._slots) \
                        and not eng.injector.armed:
                    err = PoolExhausted(
                        "page pool cannot admit the queue head even "
                        f"with every slot free (request {rid}) — "
                        "submit-time validation should have rejected "
                        "it", need=e.need, free=e.free,
                        cached=e.cached)
                    if self.tracer.enabled:
                        self.tracer.attach(err)
                    raise err from e
                break
            except InjectedFault as e:
                # transient exec failure; the engine rolled back its
                # page references, the request stays at the queue front
                if self._charge_retry(rid):
                    self._queue.popleft()
                    self._finish(rid, resume, "retry_budget",
                                 self._budget_error(rid, e))
                    continue
                break
            self._prefill_ticks[rid] = \
                self._prefill_ticks.get(rid, 0) + 1
            self._charge_work(len(tokens))
            base_key = _base_key(req.seed)
            first_tok = None
            if not resume:
                # the FIRST generated token comes from the prefill
                # logits; on resume it already exists. Both gates below
                # are the always-on production checks the decode tick
                # also applies.
                first_tok, finite = self._first_token(logits, base_key,
                                                      req.temperature)
                if not finite:
                    self.stats.nan_events += 1
                    if self._fail_admission(i, rid, NonFiniteLogits(
                            f"request {rid}: non-finite prefill "
                            "logits")):
                        continue
                    break
                if not 0 <= first_tok < eng.cfg.vocab_size:
                    self.stats.bad_samples += 1
                    if self._fail_admission(i, rid, NonFiniteLogits(
                            f"request {rid}: first sampled token "
                            f"{first_tok} outside "
                            f"[0, {eng.cfg.vocab_size})")):
                        continue
                    break
            self._queue.popleft()
            slot = _Slot(rid, req, len(req.prompt), list(resume),
                         len(tokens), base_key)
            trc = self.tracer
            if trc.enabled:
                trc.instant("admitted", request_id=rid, slot=i,
                            resumed=bool(resume))
            if first_tok is not None:
                slot.generated.append(first_tok)
                self._tokens_emitted += 1
            self._slots[i] = slot
            if first_tok is not None:
                self._note_token(rid, i)
            self._accept_ewma[i] = 1.0
            self._maybe_evict(i)

    def _fail_admission(self, i: int, rid: int, err) -> bool:
        """Roll back a corrupt admission (slot freed, retry charged).
        True when the request terminated (budget gone) — the caller
        moves on; False when it should back off and retry later."""
        self.engine.free_slot(i)
        if self._charge_retry(rid):
            self._queue.popleft()
            # only fresh admissions sample a first token, so there are
            # no committed tokens to carry into the outcome
            self._finish(rid, (), "retry_budget",
                         self._budget_error(rid, err))
            return True
        return False

    def _admit_chunked(self) -> None:
        """Chunked admission: claim a free slot and STAGE the prefill
        (pages allocated, no forward run) — the chunks execute in
        :meth:`_prefill_phase` under the tick token budget, so a long
        prompt never monopolizes a tick that co-tenant decodes need."""
        eng = self.engine
        trc = self.tracer
        for i in range(eng.num_slots):
            if self._slots[i] is not None or not self._queue:
                continue
            if not self._promote_next():
                break
            rid, req, resume = self._queue[0]
            self._charge_head_admission(rid, req)
            tokens = tuple(req.prompt) + tuple(resume[:-1])
            try:
                state = eng.begin_chunk_prefill(i, tokens)
            except PoolExhausted as e:
                self.stats.pool_exhausted += 1
                if all(s is None for s in self._slots) \
                        and not eng.injector.armed:
                    err = PoolExhausted(
                        "page pool cannot admit the queue head even "
                        f"with every slot free (request {rid}) — "
                        "submit-time validation should have rejected "
                        "it", need=e.need, free=e.free,
                        cached=e.cached)
                    if trc.enabled:
                        trc.attach(err)
                    raise err from e
                break
            self._queue.popleft()
            slot = _Slot(rid, req, len(req.prompt), list(resume),
                         len(tokens), _base_key(req.seed))
            slot.prefill = _PrefillProgress(
                tokens=tokens, next=int(state.get("start", 0)),
                state=state)
            if trc.enabled:
                trc.instant("admitted", request_id=rid, slot=i,
                            resumed=bool(resume), chunked=True)
            self._slots[i] = slot
            self._accept_ewma[i] = 1.0

    def _decoding(self, s: Optional[_Slot]) -> bool:
        """A slot the decode path may touch: occupied AND past its
        (possibly in-flight chunked) prefill."""
        return s is not None and s.prefill is None

    def _fail_prefill(self, i: int, err) -> None:
        """A chunk faulted or the completed prefill's first token was
        corrupt: free the slot (releasing every staged page), charge
        the retry budget, and requeue at the FRONT with any committed
        progress — the retried prefill restarts from the prompt start,
        so the recovered stream stays bit-identical."""
        s = self._slots[i]
        self._slots[i] = None
        self.engine.free_slot(i)
        rid = s.request_id
        if self._charge_retry(rid):
            self._finish(rid, s.generated, "retry_budget",
                         self._budget_error(rid, err))
        else:
            self._queue.appendleft((rid, s.request, list(s.generated)))

    def _finish_prefill(self, i: int, logits) -> None:
        """The final chunk just ran: install the slot into the decode
        set, sampling the first token from the chunk logits with the
        SAME gates (finiteness, vocab range) and the same key —
        ``fold_in(seed, 0)`` — the monolithic path uses."""
        eng = self.engine
        s = self._slots[i]
        rid = s.request_id
        eng.finish_chunk_prefill(i, s.prefill.state)
        s.prefill = None
        if not s.generated:
            first_tok, finite = self._first_token(logits, s.base_key,
                                                  s.request.temperature)
            if not finite:
                self.stats.nan_events += 1
                self._fail_prefill(i, NonFiniteLogits(
                    f"request {rid}: non-finite prefill logits"))
                return
            if not 0 <= first_tok < eng.cfg.vocab_size:
                self.stats.bad_samples += 1
                self._fail_prefill(i, NonFiniteLogits(
                    f"request {rid}: first sampled token {first_tok} "
                    f"outside [0, {eng.cfg.vocab_size})"))
                return
            s.generated.append(first_tok)
            self._tokens_emitted += 1
            self._note_token(rid, i)
        self._maybe_evict(i)

    def _prefill_phase(self, spent: int) -> None:
        """Run prompt chunks with whatever token budget the decode
        phase left over (always at least one chunk — a saturated decode
        batch must not starve prefill, or TTFT would be unbounded).
        Slots are ordered earliest-deadline-first with request id as
        the deterministic tiebreak, then round-robined one chunk at a
        time — fair share across concurrent prefills. Tenancy
        generalizes the ordering: priority rung first, then the
        tenant's fair-share vtime, then the EDF + id key — and every
        chunk's tokens advance the tenant's stride clock, so prefill
        work is priced against the share exactly like decode. Tenancy
        also THROTTLES: a tenant whose vtime has run more than one
        chunk-stride past the busy floor (the minimum vtime among
        resident tenants) has spent its share this interval, and its
        chunks defer until the floor catches up — so a flood tenant's
        prompt ingest converges to its weight ratio instead of
        consuming the whole leftover budget every tick. The floor
        tenant itself always qualifies, so a tick with prefill work
        and no decode can never go progress-free (watchdog-safe)."""
        if not any(s is not None and s.prefill is not None
                   for s in self._slots):
            return
        eng = self.engine
        budget = max(self.tick_token_budget - spent, 0)
        n_chunks = max(budget // self.chunk_tokens, 1)

        def key(i):
            s = self._slots[i]
            dl = s.request.deadline_ticks
            abs_dl = (self._submit_tick.get(s.request_id, 0) + dl
                      if dl is not None else float("inf"))
            ten = self.tenancy
            if ten is not None:
                t = s.request.tenant_id
                return (-ten.priority(t), ten.vtime(t), abs_dl,
                        s.request_id)
            return (abs_dl, s.request_id)

        order = deque(sorted(
            (i for i, s in enumerate(self._slots)
             if s is not None and s.prefill is not None), key=key))
        ten = self.tenancy
        floor = None
        if ten is not None:
            for s in self._slots:
                if s is not None:
                    v = ten.vtime(s.request.tenant_id)
                    if floor is None or v < floor:
                        floor = v
        progressed = set()
        while n_chunks > 0 and order:
            i = order.popleft()
            s = self._slots[i]
            if ten is not None:
                t = s.request.tenant_id
                slack = self.chunk_tokens / ten.tenants[t].weight
                if ten.vtime(t) > floor + slack:
                    # over its share this interval: the chunk defers
                    # until the busy floor catches up (dropped from
                    # THIS tick's rotation only — the slot re-sorts
                    # into next tick's order)
                    self.stats.chunk_deferrals += 1
                    continue
            p = s.prefill
            n_chunks -= 1
            chunk = p.tokens[p.next:p.next + self.chunk_tokens]
            final = p.next + self.chunk_tokens >= len(p.tokens)
            try:
                logits = eng.chunk_prefill(i, chunk, p.next, p.state,
                                           self.chunk_tokens, final)
            except InjectedFault as e:
                self._fail_prefill(i, e)
                continue
            self.stats.prefill_chunks += 1
            progressed.add(s.request_id)
            self._charge_work(len(chunk))
            if self.tenancy is not None:
                self.tenancy.charge_tokens(s.request.tenant_id,
                                           len(chunk))
            if final:
                self._finish_prefill(i, logits)
            else:
                p.next += self.chunk_tokens
                order.append(i)
        for rid in sorted(progressed):
            self._prefill_ticks[rid] = \
                self._prefill_ticks.get(rid, 0) + 1

    def _maybe_evict(self, i: int) -> None:
        slot = self._slots[i]
        if slot.generated[-1] == self.eos_id:
            reason = "eos"
        elif len(slot.generated) >= slot.request.max_new_tokens:
            reason = "length"
        elif slot.prompt_len + len(slot.generated) > self.engine.max_len:
            # cache row full: the committed stream no longer fits even
            # after a tree tick's forced-chain catch-up (in plain mode
            # this reduces to the classic ``pos >= max_len``)
            reason = "cache_full"
        else:
            return
        self.stats.evictions += 1
        self._finish(slot.request_id, slot.generated, reason)
        self._slots[i] = None
        self.engine.free_slot(i)

    def _spec_ks(self, positions: Dict[int, int]) -> List[int]:
        """Per-slot draft depth for this tick. Fixed engines always ask
        for ``spec_k``; adaptive engines scale it by the slot's
        acceptance EWMA (rounding to 0 turns the slot's speculation
        off entirely), with a periodic probe draft so a stream whose
        text turns predictable again can re-earn its depth."""
        eng = self.engine
        ks = [0] * eng.num_slots
        for i in positions:
            if not eng.adaptive_spec:
                ks[i] = eng.spec_k
                continue
            k = int(round(self._accept_ewma[i] * eng.spec_k))
            if k <= 0 and self._tick_no % self._probe_every == 0:
                k = 1
            ks[i] = max(0, min(k, eng.spec_k))
        return ks

    def _histories(self, ks: List[int]) -> List[Optional[Tuple[int, ...]]]:
        return [tuple(s.request.prompt) + tuple(s.generated)
                if s is not None and ks[i] > 0 else None
                for i, s in enumerate(self._slots)]

    def _draft_all(self, ks: List[int]) -> List[List[int]]:
        """One linear draft per slot, up to ``ks[i]`` tokens deep
        (empty for free slots, depth-0 slots, and fired ``draft_exec``
        sites — drafting is best-effort, so a fault degrades to plain
        pace without charging retry budget; model-drafter engines
        degrade down the ladder in
        :meth:`PagedDecodeEngine.draft_batch`)."""
        eng = self.engine
        hists = self._histories(ks)
        if eng.draft_model is not None:
            try:
                return eng.draft_batch(hists, ks)
            except InjectedFault:
                self.stats.draft_faults += 1
                return [[] for _ in self._slots]
        drafts: List[List[int]] = []
        for i, h in enumerate(hists):
            if h is None:
                drafts.append([])
                continue
            try:
                d = self.engine.draft(h)
            except InjectedFault:
                self.stats.draft_faults += 1
                d = []
            drafts.append([int(t) for t in d[:ks[i]]])
        return drafts

    def _draft_trees(self, ks: List[int]):
        """One draft tree per slot (``None`` for free slots, depth-0
        slots, and fault-degraded ticks). Model-drafter engines walk
        the ``draft_exec`` ladder in
        :meth:`PagedDecodeEngine.draft_tree_batch`; n-gram engines chain
        their linear drafts as single-branch trees."""
        eng = self.engine
        hists = self._histories(ks)
        if eng.draft_model is not None:
            try:
                return eng.draft_tree_batch(hists, ks)
            except InjectedFault:
                self.stats.draft_faults += 1
                return [None] * eng.num_slots
        trees = []
        for i, h in enumerate(hists):
            if h is None:
                trees.append(None)
                continue
            try:
                d = self.engine.draft(h)
            except InjectedFault:
                self.stats.draft_faults += 1
                d = []
            d = [int(t) for t in d[:ks[i]]]
            trees.append((d, [-1] + list(range(len(d) - 1)))
                         if d else None)
        return trees

    def _prepare_decode(self, positions: Dict[int, int],
                        n_new: int) -> List[int]:
        """The engine's ``prepare_decode`` under its span, which closes
        with what the call did: page boundaries crossed, shared pages
        cloned, slots preempted (the recorded event's; the profiler's
        span takes arguments only when it opens)."""
        trc, stats = self.tracer, self.stats
        boundaries, cow = stats.page_boundaries, stats.cow_copies
        trc.begin("prepare_decode")
        preempted = self.engine.prepare_decode(positions, n_new=n_new)
        trc.end("prepare_decode",
                boundaries=stats.page_boundaries - boundaries,
                cow=stats.cow_copies - cow, preempted=len(preempted))
        return preempted

    def _tick(self) -> None:
        spent = self._decode_phase()
        if self.chunk_tokens is not None:
            self._prefill_phase(spent)

    def _decode_phase(self) -> int:
        """One decode/verify step over every DECODING slot (slots mid
        chunked-prefill are invisible here — no cache row of theirs is
        complete). Returns the tick's decode token charge (positions
        computed), which the prefill phase subtracts from the tick
        token budget."""
        eng = self.engine
        trc = self.tracer
        # give every decoding slot an exclusive write target for this
        # tick; slots the pool can't serve are preempted back to the
        # queue FRONT with their progress (sampling keys depend only on
        # (seed, n_generated), so a resumed request continues its
        # original stream bit-for-bit)
        positions = {i: s.pos for i, s in enumerate(self._slots)
                     if self._decoding(s)}
        if eng.tree_spec and eng.spec_k > 0 and positions:
            spent = self._tree_tick(positions)
            if spent is not None:
                return spent
            # every forced chain was trivial and no draft survived —
            # fall through to a plain decode step
            drafts, spec, k1 = None, False, 1
        else:
            # speculate only when EVERY active slot has k1 rows of
            # headroom (a clamped out-of-range cache write would shift
            # onto committed rows) and some draft is non-empty;
            # otherwise this tick is a plain decode step — the k=0
            # degradation the chaos tier leans on. Fixed engines always
            # verify at the compiled spec_k + 1 width; adaptive ones
            # narrow to 1 + the widest draft actually proposed, so the
            # per-tick page charge below tracks the controller.
            if eng.spec_k > 0 and positions:
                trc.begin("draft")
                drafts = self._draft_all(self._spec_ks(positions))
                trc.end("draft")
            else:
                drafts = None
            k1 = eng.spec_k + 1
            if drafts is not None and eng.adaptive_spec:
                k1 = 1 + max((len(drafts[i]) for i in positions),
                             default=0)
            spec = bool(drafts is not None and k1 > 1
                        and all(pos + k1 <= eng.max_len
                                for pos in positions.values())
                        and any(drafts[i] for i in positions))
        # requeue in submission order: appendleft of the newest request
        # first leaves the oldest at the queue front (slot-index order
        # would let a later request resume before an earlier one)
        preempted = self._prepare_decode(positions, k1 if spec else 1)
        for i in sorted(preempted,
                        key=lambda j: self._slots[j].request_id,
                        reverse=True):
            s = self._slots[i]
            if trc.enabled:
                trc.instant("preempted", request_id=s.request_id,
                            slot=i)
            self._queue.appendleft((s.request_id, s.request,
                                    list(s.generated)))
            self._slots[i] = None
        occupied = [s for s in self._slots if self._decoding(s)]
        if not occupied:
            return 0
        if spec:
            self._spec_tick(drafts, k1)
            return k1 * len(occupied)
        self.stats.plain_ticks += 1
        trc.begin("build_inputs", slots=len(occupied))
        tokens, active, temps, base, counts = self._sampler_inputs()
        trc.end("build_inputs")
        logits = eng.decode(tokens, active)
        trc.begin("accept")
        next_tokens, finite = self._await_sampler(
            eng.sample(logits, base, counts, temps))
        trc.end("accept")
        trc.begin("commit")
        vocab = eng.cfg.vocab_size
        quarantined: List[Tuple[int, NonFiniteLogits]] = []
        for i, slot in enumerate(self._slots):
            if not self._decoding(slot):
                continue
            if not bool(finite[i]):
                self.stats.nan_events += 1
                quarantined.append((i, NonFiniteLogits(
                    f"slot {i} (request {slot.request_id}): non-finite "
                    "decode logits")))
                continue
            tok = int(next_tokens[i])
            if not 0 <= tok < vocab:
                self.stats.bad_samples += 1
                quarantined.append((i, NonFiniteLogits(
                    f"slot {i} (request {slot.request_id}): sampled "
                    f"token {tok} outside [0, {vocab})")))
                continue
            slot.generated.append(tok)
            slot.pos += 1
            self._tokens_emitted += 1
            self._note_token(slot.request_id, i)
            self._maybe_evict(i)
        trc.end("commit")
        # quarantine AFTER the healthy slots commit, requeueing at the
        # front in submission order (same rule as preemption)
        for i, err in sorted(
                quarantined,
                key=lambda t: self._slots[t[0]].request_id,
                reverse=True):
            self._quarantine(i, err)
        return len(occupied)

    def _spec_tick(self, drafts: List[List[int]], k1: int) -> None:
        """Draft → verify → accept: one verify step over ``k1``
        candidate positions per slot (``spec_k + 1`` for fixed engines;
        adaptive ones narrow to the widest draft proposed), then a host
        walk that commits the longest prefix of grid samples
        reproducing the drafts plus the first non-matching sample
        (1..k1 tokens per slot). Grid position j samples with
        ``fold_in(seed, n_generated + j)`` — the PLAIN stream's key for
        that token — so the committed stream is bit-identical to
        non-speculative decode (see ``serving.sampling``); acceptance
        only compresses ticks."""
        eng = self.engine
        trc = self.tracer
        self.stats.spec_ticks += 1
        trc.begin("build_inputs",
                  slots=sum(map(self._decoding, self._slots)))
        last, _, temps, base, n_gen = self._sampler_inputs()
        tokens = np.zeros((eng.num_slots, k1), np.int32)
        tokens[:, 0] = last
        for i, d in enumerate(drafts):
            d = d[:k1 - 1]
            tokens[i, 1:1 + len(d)] = d
        # grid position j of a slot samples its (n_generated + j)-th
        # token: the plain stream's key for it
        offs = n_gen[:, None] + np.arange(k1, dtype=np.int32)
        trc.end("build_inputs")
        logits = eng.verify(tokens)
        trc.begin("accept")
        grid, finite = self._await_sampler(                # (B, k1) each
            eng.sample_grid(logits, base, offs, temps))
        vocab = eng.cfg.vocab_size
        counts = [0] * eng.num_slots
        quarantined: List[Tuple[int, NonFiniteLogits]] = []
        for i, slot in enumerate(self._slots):
            if not self._decoding(slot):
                continue
            draft = drafts[i]
            committed = accepted = 0
            for j in range(k1):
                # the always-on production gates run per committed
                # position, never on the grid tail beyond the walk —
                # those rows condition on rejected drafts and are
                # garbage a plain tick would never have computed
                if not bool(finite[i, j]):
                    self.stats.nan_events += 1
                    quarantined.append((i, NonFiniteLogits(
                        f"slot {i} (request {slot.request_id}): "
                        "non-finite verify logits")))
                    break
                tok = int(grid[i, j])
                if not 0 <= tok < vocab:
                    self.stats.bad_samples += 1
                    quarantined.append((i, NonFiniteLogits(
                        f"slot {i} (request {slot.request_id}): "
                        f"sampled token {tok} outside [0, {vocab})")))
                    break
                slot.generated.append(tok)
                slot.pos += 1
                self._tokens_emitted += 1
                self._note_token(slot.request_id, i)
                committed += 1
                matched = j < len(draft) and draft[j] == tok
                if matched:
                    accepted += 1
                if tok == self.eos_id or len(slot.generated) \
                        >= slot.request.max_new_tokens:
                    break
                if not matched:
                    # the non-matching sample IS the committed token
                    # (the residual-distribution resample; see
                    # serving.sampling) — the walk ends here
                    break
            counts[i] = committed
            self.stats.tokens_drafted += len(draft)
            self.stats.tokens_accepted += accepted
            if trc.enabled and draft:
                trc.stream_acceptance(i, accepted / len(draft))
            if eng.adaptive_spec and draft:
                self._accept_ewma[i] = 0.5 * self._accept_ewma[i] \
                    + 0.5 * accepted / len(draft)
        trc.end("accept")
        eng.commit(counts)
        # a tick that commits m tokens counts m toward deadlines: the
        # scheduler clock stays in decode-step equivalents across modes
        extra = max(counts) - 1
        if extra > 0:
            self._tick_no += extra
        qset = {i for i, _ in quarantined}
        for i, slot in enumerate(self._slots):
            if slot is not None and i not in qset and counts[i]:
                self._maybe_evict(i)
        # quarantine keeps any partially committed (plain-stream
        # bit-identical) tokens: the requeue resumes from them
        for i, err in sorted(
                quarantined,
                key=lambda t: self._slots[t[0]].request_id,
                reverse=True):
            self._quarantine(i, err)

    def _tree_tick(self, positions: Dict[int, int]) -> Optional[int]:
        """Tree-speculative tick: pack every slot's FORCED chain (the
        committed tokens past its cache length — at least the pending
        token) plus its draft tree into one tree-attention verify grid,
        sample every node with the plain stream's key for its depth,
        and commit along the accepted root-to-leaf path
        (:func:`~apex_tpu.serving.sampling.tree_speculative_accept`).
        Cache lengths only advance by the row-CONTIGUOUS committed
        prefix: tokens a path stranded off the leftmost chain are
        re-sent as next tick's forced chain (the forced-prefix rule —
        bounded by the tree depth, never compounding; see
        ``serving.decode``). Returns the tick's token charge (grid
        positions computed), 0 when every slot was preempted before
        the verify, or None — tick not taken — when every forced chain
        is trivial and no draft survived, so the caller runs the plain
        path instead."""
        eng = self.engine
        trc = self.tracer
        ks = self._spec_ks(positions)
        trc.begin("draft")
        trees = self._draft_trees(ks)
        trc.end("draft")
        forced: Dict[int, List[int]] = {}
        for i, s in enumerate(self._slots):
            if self._decoding(s):
                h = list(s.request.prompt) + list(s.generated)
                forced[i] = h[s.pos:]        # f >= 1: the pending token
        if all(len(f) == 1 for f in forced.values()) \
                and not any(trees[i] is not None for i in positions):
            return None
        # grid width: the widest forced-chain + tree, clamped to the
        # scarcest slot's cache headroom (a slot whose chain overflows
        # the clamped grid catches up across ticks, committing rows
        # but sampling nothing until its chain fits)
        avail = min(eng.max_len - pos for pos in positions.values())
        k1 = max(len(forced[i])
                 + (len(trees[i][0]) if trees[i] is not None else 0)
                 for i in positions)
        k1 = max(1, min(k1, avail))
        preempted = self._prepare_decode(positions, k1)
        for i in sorted(preempted,
                        key=lambda j: self._slots[j].request_id,
                        reverse=True):
            s = self._slots[i]
            if trc.enabled:
                trc.instant("preempted", request_id=s.request_id,
                            slot=i)
            self._queue.appendleft((s.request_id, s.request,
                                    list(s.generated)))
            self._slots[i] = None
            forced.pop(i, None)
        if not forced:
            return 0
        trc.begin("build_inputs", slots=len(forced))
        _, active, temps, base, n_gen = self._sampler_inputs()
        f_chain: List[List[int]] = []
        g_trees: List[Optional[Tuple[List[int], List[int]]]] = []
        for i, s in enumerate(self._slots):
            if not self._decoding(s):
                f_chain.append([0])
                g_trees.append(None)
                continue
            chain = forced[i][:k1]
            room = k1 - len(chain)
            tree = trees[i]
            if tree is not None and len(chain) == len(forced[i]) \
                    and room > 0:
                # truncating a topological tree keeps parent validity
                toks = [int(t) for t in tree[0][:room]]
                pars = [int(p) for p in tree[1][:room]]
                g_trees.append((toks, pars) if toks else None)
            else:
                g_trees.append(None)
            f_chain.append(chain)
        tok_np, dep_np, anc_np, val_np, par_np, start_np = tree_arrays(
            f_chain, g_trees, k1)
        # column j samples the (n_generated - f + 1 + depth[j])-th
        # generated token — exactly the plain stream's key offset for
        # that position (forced columns before the walk root land on
        # already-committed offsets; their samples are never read)
        chain_len = np.asarray([len(f) for f in f_chain], np.int32)
        offs = np.where(active[:, None],
                        (n_gen - chain_len + 1)[:, None] + dep_np,
                        0).astype(np.int32)
        trc.end("build_inputs")
        logits = eng.tree_verify(tok_np, dep_np, anc_np)
        trc.begin("accept")
        grid, finite = self._await_sampler(                # (B, k1) each
            eng.sample_grid(logits, base, offs, temps))
        cnts, path = self._tree_accept(grid, tok_np, par_np, val_np,
                                       start_np)
        cnts, path = np.asarray(cnts), np.asarray(path)
        vocab = eng.cfg.vocab_size
        counts = [0] * eng.num_slots          # cache ROWS to commit
        new_tok_max = 0
        quarantined: List[Tuple[int, NonFiniteLogits]] = []
        for i, slot in enumerate(self._slots):
            if not self._decoding(slot):
                continue
            f = len(f_chain[i])
            if f < len(forced[i]):
                # catch-up-only: the truncated chain's rows commit,
                # nothing is sampled for this slot this tick
                counts[i] = f
                slot.pos += f
                continue
            nodes = len(g_trees[i][0]) if g_trees[i] is not None else 0
            committed = accepted = g = 0
            bad = None
            for v in range(int(cnts[i])):
                col = int(path[i, v])
                # the always-on production gates run per VISITED node
                # only — unvisited grid columns condition on rejected
                # branches a plain tick would never have computed
                if not bool(finite[i, col]):
                    self.stats.nan_events += 1
                    bad = NonFiniteLogits(
                        f"slot {i} (request {slot.request_id}): "
                        "non-finite tree-verify logits")
                    break
                tok = int(grid[i, col])
                if not 0 <= tok < vocab:
                    self.stats.bad_samples += 1
                    bad = NonFiniteLogits(
                        f"slot {i} (request {slot.request_id}): "
                        f"sampled token {tok} outside [0, {vocab})")
                    break
                slot.generated.append(tok)
                self._tokens_emitted += 1
                self._note_token(slot.request_id, i)
                committed += 1
                if v:
                    accepted += 1
                    if g == v - 1 and col == f - 1 + v:
                        g += 1    # the walk stayed on the leftmost chain
                if tok == self.eos_id or len(slot.generated) \
                        >= slot.request.max_new_tokens:
                    break
            # rows: the forced chain plus the contiguous accepted run
            # (the final committed sample never has a row — it is the
            # next pending token, exactly as in the linear walk)
            counts[i] = f + g
            slot.pos += f + g
            new_tok_max = max(new_tok_max, committed)
            self.stats.tokens_drafted += nodes
            self.stats.tokens_accepted += accepted
            if trc.enabled and nodes:
                trc.stream_acceptance(i, accepted / nodes)
            if eng.adaptive_spec and nodes:
                self._accept_ewma[i] = 0.5 * self._accept_ewma[i] \
                    + 0.5 * accepted / nodes
            if bad is not None:
                quarantined.append((i, bad))
        trc.end("accept")
        eng.commit(counts)
        self.stats.spec_ticks += 1
        # a tick that commits m tokens counts m toward deadlines: the
        # scheduler clock stays in decode-step equivalents across modes
        if new_tok_max > 1:
            self._tick_no += new_tok_max - 1
        qset = {i for i, _ in quarantined}
        for i, slot in enumerate(self._slots):
            if slot is not None and i not in qset and counts[i]:
                self._maybe_evict(i)
        for i, err in sorted(
                quarantined,
                key=lambda t: self._slots[t[0]].request_id,
                reverse=True):
            self._quarantine(i, err)
        return k1 * len(forced)

    # -- drive loop --------------------------------------------------------

    def _raise_livelock(self, stalled: int) -> None:
        stuck = {"queued": [rid for rid, _, _ in self._queue],
                 "slots": {i: s.request_id
                           for i, s in enumerate(self._slots)
                           if s is not None}}
        err = LivelockError(
            f"no progress (token committed, request terminated, or "
            f"retry consumed) in {stalled} consecutive scheduler "
            f"ticks; stuck requests: queued={stuck['queued']} "
            f"slots={stuck['slots']}; pool={self.engine.pool_snapshot()}",
            stuck=stuck, pool=self.engine.pool_snapshot())
        if self.tracer.enabled:
            self.tracer.attach(err)  # the stuck slots' last events
        raise err

    @property
    def busy(self) -> bool:
        """Work pending: queued requests or occupied slots."""
        return bool(self._queue) or any(s is not None
                                        for s in self._slots)

    def step(self) -> None:
        """One scheduler tick: expire deadlines, admit, decode (and,
        when chunked prefill is on, run prompt chunks with the budget
        the decode phase left). Public so external load generators
        (``benchmark/runners``) can interleave ``submit`` calls
        with ticks; :meth:`run` is just the drain loop over this. The
        progress watchdog spans steps: a chunk forward counts as
        progress (a long prompt prefilling is converging), so its
        counter joins tokens/completions/retries in the snapshot."""
        trc = self.tracer
        self._tick_no += 1
        if trc.enabled:
            trc.set_tick(self._tick_no)
        trc.begin("step", tick=self._tick_no,
                  decoding=sum(map(self._decoding, self._slots)),
                  queued=len(self._queue))
        try:
            self._phases()
        finally:
            trc.end("step")
        snap = (self._tokens_emitted, len(self.outcomes),
                self.stats.retries, self.stats.prefill_chunks)
        if snap == self._watch_snap:
            self._stalled += 1
            if self._stalled >= self.watchdog_limit:
                self._raise_livelock(self._stalled)
        else:
            self._stalled, self._watch_snap = 0, snap

    def _phases(self) -> None:
        """The tick's phases, each under its span, inside ``step``."""
        trc = self.tracer
        before = self._tokens_emitted
        trc.begin("expire")
        self._expire_deadlines()
        trc.end("expire")
        trc.begin("admit")
        self._admit()
        trc.admitting = -1
        trc.end("admit")
        self._tick()
        if self.streams is not None:
            # end-of-tick delivery: every stream gets exactly the
            # tokens this tick committed for it (1..k+1 under
            # speculation), one stream_emit draw per delivering stream
            trc.begin("flush")
            self.streams.flush()
            trc.end("flush")
        if trc.enabled:
            trc.tick_metrics(self._tokens_emitted - before,
                             len(self._queue),
                             self.engine.pool_gauges())
            if self.tenancy is not None:
                trc.tenant_gauges(self.tenancy.gauge_snapshot())
        if self.audit:
            self.engine.check_invariants()

    def run(self) -> List[List[int]]:
        """Drain the queue; returns generated tokens (EOS included when
        emitted) per request, in submission order. Typed outcomes —
        including degraded terminations, whose token lists are a prefix
        of their fault-free streams — live in ``self.outcomes``. Raises
        :class:`LivelockError` after ``watchdog_limit`` consecutive
        ticks without progress instead of spinning."""
        while self.busy:
            self.step()
        return [list(self.outcomes[rid].tokens)
                for rid in sorted(self.outcomes)]
