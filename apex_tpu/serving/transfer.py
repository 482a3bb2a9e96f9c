"""Fault-tolerant cross-replica page handoff for disaggregated serving.

The disaggregated tier (``serving.router``) runs prefill and decode on
separate engines; what moves between them is the prompt's completed KV
pages — page-sized ``(layers, page_size, heads * head_dim)`` tiles
gathered from the prefill replica's pool and scattered into pages the
decode replica's :class:`~apex_tpu.serving.paging.PagePool` allocated.
This module owns that channel, and its design goal is the robustness
contract, not the copy itself:

- **content addressing** — every shipped batch is identified by the
  prompt's chained sha256 prefix keys
  (:func:`~apex_tpu.serving.paging.prefix_page_keys`, canonical
  ``struct.pack`` encoding). The receiver already holding a key's page
  skips the bytes entirely (cross-replica dedup — the same sharing the
  local prefix cache provides), and the final chain key is folded into
  the transfer checksum so a payload can never be installed under the
  wrong prompt.
- **integrity** — the sender checksums the staged tile bytes plus the
  chain key (sha256); the receiver recomputes before installing.
  A mismatch (the ``page_recv`` fault site flips one staged byte,
  payload-selected) QUARANTINES the payload: the tiles are discarded
  without touching the receiving cache, so corrupt KV rows are never
  attended. Typed: :class:`~apex_tpu.serving.health.TransferCorrupt`.
- **retry budget** — each handoff gets ``max_retries`` re-attempts
  (``page_send`` drops count too); exhaustion raises
  :class:`~apex_tpu.serving.health.TransferFailed` /
  ``TransferCorrupt`` and the router serves the admission colocated.
  Every outcome is also an observation for the remote replica's
  :class:`~apex_tpu.serving.health.ReplicaHealth` ladder.
- **observability** — one ``page_transfer`` tracer span per handoff
  (retries inside the span), per-replica labeled counters
  (``serving_transfer_src_bytes_total`` etc.), and the
  ``serving_transfer_ticks`` histogram of the deterministic tick cost
  the router charges per handoff.

Device mechanics: the jitted :func:`make_extract_pages_fn` /
:func:`make_insert_pages_fn` pair gathers/scatters tiles by page id
(one executable per distinct page count — prompts are bucketed, so the
count set is small), staged through the host. On a real two-slice
topology the staging hop is the ``device_get``/``device_put`` pair of
``partition.rules.make_shard_and_gather_fns`` over the two sub-meshes
of ``partition.mesh.make_mesh`` — :func:`make_tile_transfer_fns` builds
exactly that pair from the pool's TP layout (heads over ``model``);
the single-device default degenerates to a host round-trip, which is
also what keeps CPU chaos tests byte-faithful.

Two channel tiers share that contract (same chain keys, same checksum,
same quarantine, same retry discipline — only the link and the fault
sites differ):

- :class:`PageTransfer` — the HOST-STAGED bounce (gather to host,
  checksum, place on the destination), priced by the router at
  ``handoff_ticks_per_page``. Fault sites ``page_send``/``page_recv``.
- :class:`PageReshard` — the DEVICE-TO-DEVICE spec-to-spec reshard
  (the alpa-style ShardingSpec-to-ShardingSpec transfer of SNIPPETS.md
  [3]): page tiles move between the source and destination engines'
  sub-meshes without the host bounce, priced per link
  (``ici_ticks_per_page`` within a slice, ``dcn_ticks_per_page``
  across slices — both cheaper than the host staging they replace).
  Fault sites ``reshard_send``/``reshard_recv``; budget exhaustion
  raises the typed :class:`~apex_tpu.serving.health.ReshardFailed`
  and the pool router re-ships the same pages host-staged — the
  reshard tier may lose performance, never a request.
  :func:`make_reshard_extract_fn` is its traced sender half: a
  ``shard_map`` whose explicit ``all_gather`` materializes the wire
  tile from the TP-sharded pool, so the APX511 per-rank simulator and
  the APX6xx cost interpreter see (and budget) the collective volume
  the reshard moves (``gpt_page_reshard_medium``).

The :class:`PageTransfer` object itself is host state (attempt
counters, metric handles) — APX401 registers this module accordingly;
the jitted extract/insert closures touch none of it.
"""

import hashlib
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.serving.faults import FaultInjector
from apex_tpu.serving.health import (ReshardFailed, ServingStats,
                                     TransferCorrupt, TransferFailed)
from apex_tpu.serving.observe import Tracer

#: ``serving_transfer_ticks`` histogram buckets: handoffs are charged
#: a handful of decode-step equivalents, not hundreds.
TRANSFER_TICK_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0,
                         24.0, 32.0)


def make_extract_pages_fn() -> Callable:
    """Jitted ``(cache, page_ids) -> (k_tile, v_tile)``: gather the
    identified pages out of a paged cache's pool — the sender half of
    the handoff. Tiles are ``(layers, n_pages, page_size, heads *
    head_dim)`` in the pool dtype. Read-only (no donation): the source
    cache keeps serving its own slots. A latent pool
    (``serving.cache.LatentKVCache``) has no ``v``: its pages travel as the
    same pair with a ``v_tile`` of width 0, so the wire format, the checksum
    and the receiver stay as they are."""

    def extract(cache, page_ids):
        k_tile = cache.k[:, page_ids]
        return k_tile, (k_tile[..., :0] if cache.v is None
                        else cache.v[:, page_ids])

    return jax.jit(extract)


def make_insert_pages_fn() -> Callable:
    """Jitted ``(cache, page_ids, k_tile, v_tile) -> cache``: scatter
    received tiles into the identified pages of the receiving pool —
    the receiver half of the handoff, and the cost-tier entry that
    prices the handoff bytes (``gpt_page_handoff_medium``). The cache
    is donated: the scatter is an in-place page write, exactly like a
    decode step's row append."""

    def insert(cache, page_ids, k_tile, v_tile):
        new = cache._replace(k=cache.k.at[:, page_ids].set(k_tile))
        if cache.v is None:         # a latent pool: the width-0 tile is no
            return new              # pool's
        return new._replace(v=cache.v.at[:, page_ids].set(v_tile))

    return jax.jit(insert, donate_argnums=(0,))


def make_extract_pages_quant_fn() -> Callable:
    """:func:`make_extract_pages_fn` for the int8 pool: gathers the
    per-page-per-head fp32 scale planes ``(layers, n_pages, heads)``
    TOGETHER with the int8 tiles — ``(cache, page_ids) -> (k_tile,
    v_tile, k_scale, v_scale)``. A page's rows are meaningless without
    the scales they were quantized against, so the spill/promote wire
    payload always carries all four (and still comes out at roughly
    half a bf16 payload's bytes — the capacity argument for the int8
    host tier)."""

    def extract(cache, page_ids):
        return (cache.k[:, page_ids], cache.v[:, page_ids],
                cache.k_scale[:, page_ids], cache.v_scale[:, page_ids])

    return jax.jit(extract)


def make_insert_pages_quant_fn() -> Callable:
    """:func:`make_insert_pages_fn` for the int8 pool: scatters int8
    tiles AND their fp32 scale planes into the identified pages —
    ``(cache, page_ids, k_tile, v_tile, k_scale, v_scale) -> cache``,
    cache donated (in-place page writes, like a decode step's row
    append). The promoted page is bit-identical to the spilled one:
    same int8 rows, same scales — the quantized analogue of the COW
    clone guarantee."""

    def insert(cache, page_ids, k_tile, v_tile, k_scale, v_scale):
        return cache._replace(
            k=cache.k.at[:, page_ids].set(k_tile),
            v=cache.v.at[:, page_ids].set(v_tile),
            k_scale=cache.k_scale.at[:, page_ids].set(k_scale),
            v_scale=cache.v_scale.at[:, page_ids].set(v_scale))

    return jax.jit(insert, donate_argnums=(0,))


def make_tile_transfer_fns(mesh=None, rules=None) -> Tuple[Callable,
                                                           Callable]:
    """``(gather_fn, shard_fn)`` for page tiles on a real multi-device
    topology: ``gather_fn`` pulls a (possibly TP-sharded) tile pair to
    replicated host arrays on the source sub-mesh, ``shard_fn`` places
    host tiles under the pool's TP spec (heads over ``model``) on the
    destination sub-mesh — the ``make_shard_and_gather_fns`` device_put
    /device_get pair from the partition engine, applied to the tile's
    head axis (the last, same as the pool's). Build one pair per sub-mesh
    of ``partition.mesh.make_mesh`` and hand them to
    :class:`PageTransfer`; without them the transfer stages through
    ``np.asarray`` — correct on any topology, optimal on one device."""
    from jax.sharding import PartitionSpec

    from apex_tpu.partition.rules import make_shard_and_gather_fns

    del rules  # the tile layout is fixed by the pool's: heads sharded
    spec = PartitionSpec(None, None, None, "model")
    shard_fns, gather_fns = make_shard_and_gather_fns(
        {"k": spec, "v": spec}, mesh)

    def gather_fn(k_tile, v_tile):
        return (np.asarray(gather_fns["k"](k_tile)),
                np.asarray(gather_fns["v"](v_tile)))

    def shard_fn(k_tile, v_tile):
        return shard_fns["k"](k_tile), shard_fns["v"](v_tile)

    return gather_fn, shard_fn


def make_reshard_extract_fn(mesh=None) -> Callable:
    """The traced sender half of a device-to-device reshard:
    ``jit(shard_map((cache, page_ids) -> (k_tile, v_tile)))`` over the
    source sub-mesh, where the pool's head axis shards over ``model``
    and an explicit ``all_gather`` (tiled, rank order — the same order
    the pool lays heads out in) materializes the full replicated wire
    tile from the local head shards. Functionally this equals
    :func:`make_extract_pages_fn` on the gathered cache — the reshard
    stays bitwise-faithful — but tracing the collective explicitly is
    the point: the APX511 per-rank simulator verifies every rank runs
    the same gather, and the cost tier's ``gpt_page_reshard_medium``
    budgets the collective volume the reshard puts on the ICI/DCN wire
    (per rank: (tp-1)/tp of the tile bytes, vs the host bounce's full
    gather + re-placement)."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.serving.cache import paged_cache_partition_specs
    from apex_tpu.transformer import parallel_state as ps

    cspecs = paged_cache_partition_specs()

    def extract(cache, page_ids):
        k = jax.lax.all_gather(cache.k[:, page_ids], "model", axis=3,
                               tiled=True)
        v = jax.lax.all_gather(cache.v[:, page_ids], "model", axis=3,
                               tiled=True)
        return k, v

    sharded = ps.shard_map(extract, mesh=mesh,
                           in_specs=(cspecs, P()),
                           out_specs=(P(), P()))
    return jax.jit(sharded)


def _default_gather(k_tile, v_tile):
    return np.asarray(k_tile), np.asarray(v_tile)


def _default_shard(k_tile, v_tile):
    return k_tile, v_tile


def transfer_checksum(k_tile: np.ndarray, v_tile: np.ndarray,
                      chain_key: bytes) -> bytes:
    """sha256 over the staged tile bytes plus the prompt's final
    chained page key: integrity (bit flips) and identity (a payload
    can only verify against the prompt whose pages it carries) in one
    digest."""
    h = hashlib.sha256()
    h.update(chain_key)
    h.update(np.ascontiguousarray(k_tile).tobytes())
    h.update(np.ascontiguousarray(v_tile).tobytes())
    return h.digest()


class PageTransfer:
    """The fault-tolerant handoff channel (see module doc). One
    instance per router; both replicas' engines share its injector and
    tracer, so fault draws and spans land in a single deterministic
    sequence.

    ``max_retries`` bounds RE-attempts per handoff (total attempts =
    ``max_retries + 1``). ``gather_fn``/``shard_fn`` override the host
    staging hop for real two-mesh topologies
    (:func:`make_tile_transfer_fns`).

    The class attributes below are the channel's identity — the fault
    sites it draws, the tracer span it opens, the stat/metric families
    it bumps, and the typed errors budget exhaustion raises.
    :class:`PageReshard` overrides exactly these to become the
    device-to-device tier; the ``ship`` loop (extract → checksum →
    quarantine → retry) is shared verbatim, which is what keeps the
    two tiers' robustness contracts identical."""

    #: fault sites drawn per attempt (drop before bytes move / corrupt
    #: the staged payload in flight)
    send_site = "page_send"
    recv_site = "page_recv"
    #: tracer span name, one per handoff (retries inside the span)
    span = "page_transfer"
    #: ``ServingStats`` field family: <prefix>_retries / _corrupt /
    #: _failures, plus ``delivered_stat`` for verified deliveries
    stat_prefix = "transfer"
    delivered_stat = "transfers"
    #: per-replica labeled metric family in the registry
    metric_prefix = "serving_transfer"

    def __init__(self, injector: Optional[FaultInjector] = None,
                 tracer: Optional[Tracer] = None,
                 stats: Optional[ServingStats] = None,
                 max_retries: int = 2,
                 gather_fn: Callable = _default_gather,
                 shard_fn: Callable = _default_shard):
        if max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0, got {max_retries}")
        self.injector = injector or FaultInjector()
        self.tracer = tracer if tracer is not None \
            else Tracer(enabled=False)
        self.stats = stats if stats is not None \
            else ServingStats(registry=self.tracer.registry)
        self.max_retries = max_retries
        self.gather_fn = gather_fn
        self.shard_fn = shard_fn
        self._extract = make_extract_pages_fn()
        self._hot = {}

    # -- per-replica labeled metrics ------------------------------------

    def _counters(self, replica: str):
        c = self._hot.get(replica)
        if c is None:
            r = self.tracer.registry
            p = self.metric_prefix
            labels = {"replica": replica}
            c = self._hot[replica] = (
                r.counter(f"{p}_src_bytes_total",
                          help="page-handoff bytes shipped from this "
                               "replica (verified payloads only)",
                          labels=labels),
                r.counter(f"{p}_src_retries_total",
                          help="handoff attempts retried against this "
                               "replica", labels=labels),
                r.counter(f"{p}_src_failures_total",
                          help="handoffs abandoned against this "
                               "replica (budget exhausted)",
                          labels=labels),
                r.histogram(f"{p}_ticks",
                            buckets=TRANSFER_TICK_BUCKETS,
                            help="deterministic tick cost charged per "
                                 "delivered handoff",
                            labels=labels),
            )
        return c

    def _bump(self, field: str, n: int = 1) -> None:
        """Increment one of the channel's ``ServingStats`` fields
        (``<stat_prefix>_retries`` etc. — the view resolves to the
        shared registry counter)."""
        name = f"{self.stat_prefix}_{field}"
        setattr(self.stats, name, getattr(self.stats, name) + n)

    def observe_ticks(self, replica: str, ticks: int) -> None:
        """Record the tick cost the router charged for a delivered
        handoff (the clock side lives in the router — transfer only
        prices it)."""
        self._counters(replica)[3].observe(ticks)

    # -- the handoff ----------------------------------------------------

    def ship(self, src_engine, tokens: Sequence[int],
             src_pages: Sequence[int], *, replica: str = "remote",
             health=None) -> Tuple[Optional[np.ndarray],
                                   Optional[np.ndarray], int]:
        """Move ``src_pages`` (page ids in the SOURCE pool, in prompt
        order) of the prompt ``tokens`` out of ``src_engine``'s cache,
        verified: returns host ``(k_tile, v_tile, attempts)`` with the
        tiles ready for :func:`make_insert_pages_fn` on the receiver
        (``(None, None, attempts)`` for an empty batch — a fully-
        deduped handoff still runs the control round-trip, so it can
        still fault). ``attempts`` > 1 means retries happened; the
        router prices each as one backoff tick on its work-charged
        clock (deterministic backoff — no wall-clock sleeps in a
        replay-exact scheduler). Raises :class:`TransferFailed` /
        :class:`TransferCorrupt` when the retry budget is gone; every
        attempt outcome feeds ``health`` (the remote replica's ladder)
        when given."""
        from apex_tpu.serving.paging import prefix_page_keys

        inj = self.injector
        trc = self.tracer
        c_bytes, c_retries, c_failures, _ = self._counters(replica)
        chain_key = prefix_page_keys(
            [int(t) for t in tokens], src_engine.page_size)[-1]
        n_pages = len(src_pages)
        trc.begin(self.span, pages=n_pages, replica=replica)
        corrupt_last = False
        for attempt in range(self.max_retries + 1):
            if attempt:
                self._bump("retries")
                c_retries.inc()
            if inj.fire(self.send_site):
                # the send was dropped before any bytes moved
                if health is not None:
                    health.probe(False)
                continue
            if n_pages:
                k_tile, v_tile = self.gather_fn(*self._extract(
                    src_engine.cache, jnp.asarray(src_pages, jnp.int32)))
                digest = transfer_checksum(k_tile, v_tile, chain_key)
                fired, payload = inj.draw(self.recv_site)
                if fired:
                    # in-flight corruption: flip one staged byte, the
                    # payload picks which — deterministic per (seed,
                    # site, index)
                    k_tile = np.array(k_tile, copy=True)
                    flat = k_tile.reshape(-1).view(np.uint8)
                    flat[payload % flat.size] ^= 0xFF
                if transfer_checksum(k_tile, v_tile,
                                     chain_key) != digest:
                    # quarantine: the tiles never reach the receiving
                    # cache; retry re-extracts from the source of truth
                    self._bump("corrupt")
                    corrupt_last = True
                    if health is not None:
                        health.probe(False)
                    continue
                corrupt_last = False
            else:
                k_tile = v_tile = None
                inj.draw(self.recv_site)  # handshake keeps draw order
            setattr(self.stats, self.delivered_stat,
                    getattr(self.stats, self.delivered_stat) + 1)
            if n_pages:
                c_bytes.inc(int(k_tile.nbytes) + int(v_tile.nbytes))
            if health is not None:
                health.probe(True)
            trc.end(self.span, attempts=attempt + 1)
            return k_tile, v_tile, attempt + 1
        self._bump("failures")
        c_failures.inc()
        trc.end(self.span, attempts=self.max_retries + 1, failed=True)
        err = self._budget_error(replica, self.max_retries + 1, n_pages,
                                 corrupt_last)
        raise self.tracer.attach(err) if trc.enabled else err

    def _budget_error(self, replica: str, attempts: int, n_pages: int,
                      corrupt_last: bool):
        """The typed error a lost budget raises — the one seam the
        reshard tier's taxonomy differs on."""
        cls = TransferCorrupt if corrupt_last else TransferFailed
        return cls(
            f"page handoff from replica {replica!r} lost all "
            f"{attempts} attempts ({n_pages} pages"
            f"{'; last payload corrupt' if corrupt_last else ''})",
            attempts=attempts, pages=n_pages)


class PageReshard(PageTransfer):
    """The device-to-device handoff tier: the same verified channel as
    :class:`PageTransfer` but over the spec-to-spec ICI/DCN link
    instead of the host bounce. Pass the source/destination sub-meshes
    (``partition.mesh.make_mesh`` slices) and the tile pair moves
    through :func:`make_tile_transfer_fns` on each side — gather under
    the source mesh's TP spec, place under the destination's; on the
    single-process rig both default to the degenerate host round-trip,
    which keeps CPU chaos tests byte-faithful while exercising every
    fault path. Budget exhaustion raises the typed
    :class:`~apex_tpu.serving.health.ReshardFailed` (corrupt or
    dropped — ``corrupt`` tells which); the pool router catches it and
    re-ships the same pages through its host-staged
    :class:`PageTransfer`, so the reshard tier degrades to the r15
    contract instead of failing a request."""

    send_site = "reshard_send"
    recv_site = "reshard_recv"
    span = "reshard"
    stat_prefix = "reshard"
    delivered_stat = "reshards"
    metric_prefix = "serving_reshard"

    def __init__(self, injector: Optional[FaultInjector] = None,
                 tracer: Optional[Tracer] = None,
                 stats: Optional[ServingStats] = None,
                 max_retries: int = 2,
                 src_mesh=None, dst_mesh=None):
        gather_fn, shard_fn = _default_gather, _default_shard
        if src_mesh is not None:
            gather_fn, _ = make_tile_transfer_fns(src_mesh)
        if dst_mesh is not None:
            _, shard_fn = make_tile_transfer_fns(dst_mesh)
        super().__init__(injector=injector, tracer=tracer, stats=stats,
                         max_retries=max_retries, gather_fn=gather_fn,
                         shard_fn=shard_fn)

    def _budget_error(self, replica: str, attempts: int, n_pages: int,
                      corrupt_last: bool):
        return ReshardFailed(
            f"device-to-device reshard from replica {replica!r} lost "
            f"all {attempts} attempts ({n_pages} pages"
            f"{'; last payload corrupt' if corrupt_last else ''}) — "
            "degrading to host-staged handoff",
            attempts=attempts, pages=n_pages, corrupt=corrupt_last)
