"""Serving: KV-cached incremental decode for the in-tree GPT.

Reference anchor: the apex-fed Megatron stacks are served with
KV-cached autoregressive generation (``megatron/text_generation``);
this package is that path for ``apex_tpu.models.gpt``, TPU-first:

- ``cache``     — the cache layout, updated in place via donated
  buffers (apxlint APX512 pins the donation in the trace tier): the
  paged ``PagedKVCache`` (fixed page pool + per-slot block tables, K/V
  HBM proportional to allocated pages instead of ``slots x S_max``);
- ``paging``    — host-side page allocator: free list, refcounts,
  prefix-hash cache with LRU eviction, copy-on-write bookkeeping, and
  the hierarchical KV-cache's host tier: a byte-budgeted
  content-addressed ``PrefixRegistry`` that LRU-evicted sole-owned
  prefix pages spill to (versioned checksum-bound ``SpillRecord``
  wire format) and admission-time registry hits promote from —
  shareable across engines and replicas so any replica's prefill
  seeds everyone's cache;
- ``decode``    — bucketed prefill + single-token decode + k+1-position
  speculative *verify* steps over the page pool, an unsharded path and
  a TP-sharded path (heads over the ``model`` axis);
- ``draft``     — host-side n-gram / prompt-lookup drafting for
  self-speculative decode (pure function of the token history — no
  draft model, no device work), plus the ``tree_arrays`` grid packer
  for tree speculation;
- ``draft_model`` — model-based drafting: a tiny (optionally
  TP-sharded) draft GPT advanced in lockstep with the target's slots,
  re-synced by common prefix after rejections;
- ``sampling``  — greedy / temperature / top-k / top-p under explicit
  PRNG keys, including the speculative accept/resample grid whose
  committed stream is bit-identical to plain decode;
- ``scheduler`` — fixed-slot continuous batching (admit/evict on EOS or
  max-len; jit recompiles only per prompt bucket, never per request),
  over the one engine (``PagedDecodeEngine``), with prefix sharing at
  admission and preemption-by-requeue when the pool runs dry; ``spec_k > 0``
  turns ticks into draft → verify → accept steps committing 1..k+1
  tokens per slot, with optional model drafting (``draft_model=``),
  tree speculation (``tree_spec=True``) and per-stream adaptive depth
  (``adaptive_spec=True``); ``chunk_tokens=`` switches admission to
  chunked prefill — page-aligned prompt chunks run between decode
  ticks under a ``tick_token_budget``, bounding p99 inter-token
  latency under mixed load while keeping committed streams
  bit-identical;
- ``health``    — typed failure taxonomy (``PoolExhausted``,
  ``NonFiniteLogits``, ``RetryBudgetExhausted``, ...), per-engine
  ``ServingStats`` counters, and typed ``RequestOutcome`` records;
- ``faults``    — deterministic fault injection: a seedable
  ``FaultInjector`` consulted at named host-side sites, schedules a
  pure function of (seed, site, call index) so chaos runs replay
  bit-for-bit (``tests/L0/run_serving/test_faults.py``);
- ``observe``   — host-side observability hooked the same way: a
  span/event ``Tracer`` on the deterministic tick clock (replay-exact
  streams, Perfetto JSONL dumps), a ``MetricsRegistry`` of counters/
  gauges/latency histograms (``ServingStats`` is a view over it), and
  a ``FlightRecorder`` ring that typed ``ServingError``\\ s attach to
  their payloads;
- ``transfer``  — fault-tolerant cross-replica page handoff: page
  tiles gathered from a prefill replica's pool and scattered into a
  decode replica's, content-addressed by the chained prefix keys,
  checksum-verified (corrupt payloads quarantined, never attended),
  retried under a per-handoff budget with every outcome typed — in
  two tiers sharing that contract: the host-staged ``PageTransfer``
  and the device-to-device spec-to-spec ``PageReshard`` (typed
  ``ReshardFailed`` on exhaustion, degrading back to host staging);
- ``router``    — the disaggregated serving tier: a
  ``DisaggregatedRouter`` (a ``ContinuousBatchingScheduler`` over a
  two-replica composite engine) admitting prompts on a prefill
  replica, shipping their pages across, decoding on a decode replica
  — with per-replica ``ReplicaHealth`` ladders driven by probe faults,
  graceful colocated fallback, and mid-stream failover whose committed
  streams stay bit-identical to colocated serving; and its pool-scale
  generalization ``PoolRouter``: N prefill x M decode replicas behind
  one admission queue, load-based prefill routing, headroom-chosen
  decode placement with N-way failover, per-link-priced reshard
  handoffs, and the same bit-identical stream contract.
- ``tenancy``   — the multi-tenant front-end policy: ``Tenant``
  configs (weight, page quota, priority rung, TTFT/ITL SLO bounds)
  behind a ``TenancyPolicy`` the scheduler consults for stride-clock
  weighted fair share over the tick token budget, page-quota
  reservations charged against the pool's ``QuotaLedger``, and
  priority preemption-by-requeue — reordering WHEN work runs, never
  WHAT commits (streams stay integer-identical to the untenanted
  scheduler);
- ``streaming`` — per-token delivery: a ``TokenStream`` per request
  fed by a ``StreamMux`` the scheduler flushes once per tick (1..k+1
  tokens per speculative commit), with a ``stream_emit`` fault site
  and a strict-prefix contract on failure — delivery is host-side
  fan-out, never part of the committed stream.
"""

from apex_tpu.serving.cache import (  # noqa: F401
    HybridKVCache, LatentKVCache, PagedKVCache, WindowKVCache,
    audit_block_tables, init_hybrid_cache, init_latent_cache,
    init_paged_cache, init_window_cache, paged_cache_partition_specs,
)
from apex_tpu.serving.decode import (  # noqa: F401
    make_copy_page_fn, make_paged_chunk_prefill_fn, make_paged_decode_fn,
    make_paged_prefill_fn, make_paged_tree_verify_fn,
    make_paged_verify_fn, make_tp_paged_chunk_prefill_fn,
    make_tp_paged_decode_fn, make_tp_paged_prefill_fn,
    make_tp_paged_tree_verify_fn, make_tp_paged_verify_fn,
)
from apex_tpu.serving.draft import ngram_draft, tree_arrays  # noqa: F401
from apex_tpu.serving.draft_model import DraftModel  # noqa: F401
from apex_tpu.serving.faults import (  # noqa: F401
    SITES, FaultInjector, InjectedFault, fault_draw,
)
from apex_tpu.serving.health import (  # noqa: F401
    FINISH_REASONS, HEALTH_STATES, AdmissionRejected, DeadlineExceeded,
    LivelockError, NonFiniteLogits, PoolExhausted, PoolInvariantError,
    PromoteFailed, QuotaExhausted, ReplicaHealth, ReplicaUnavailable,
    RequestOutcome, ReshardFailed, RetryBudgetExhausted, ServingError,
    ServingStats, SloViolation, SpillFailed, StreamFailed,
    TransferCorrupt, TransferFailed,
)
from apex_tpu.serving.observe import (  # noqa: F401
    FlightRecorder, MetricsRegistry, TraceEvent, Tracer,
)
from apex_tpu.serving.paging import (  # noqa: F401
    PAGE_KEY_VERSION, SPILL_DTYPE_TAGS, PagePool, PrefixRegistry,
    QuotaLedger, SpillRecord, decode_spill_header, encode_spill_header,
    prefix_page_keys, spill_checksum,
)
from apex_tpu.serving.router import (  # noqa: F401
    DisaggregatedRouter, PoolRouter,
)
from apex_tpu.serving.sampling import (  # noqa: F401
    finite_rows, sample_stream, sample_stream_checked, sample_stream_grid,
    sample_stream_grid_checked, sample_token_grid, sample_tokens,
    speculative_accept, stream_keys, tree_speculative_accept,
)
from apex_tpu.serving.scheduler import (  # noqa: F401
    ContinuousBatchingScheduler, PagedDecodeEngine, Request,
)
from apex_tpu.serving.streaming import (  # noqa: F401
    StreamMux, TokenStream,
)
from apex_tpu.serving.tenancy import (  # noqa: F401
    DEFAULT_TENANT, Tenant, TenancyPolicy,
)
from apex_tpu.serving.transfer import (  # noqa: F401
    PageReshard, PageTransfer, make_extract_pages_fn,
    make_extract_pages_quant_fn, make_insert_pages_fn,
    make_insert_pages_quant_fn, make_reshard_extract_fn,
    make_tile_transfer_fns, transfer_checksum,
)
