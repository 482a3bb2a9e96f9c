"""Disaggregated prefill/decode serving: two engines, one scheduler.

Prefill is MXU-bound and decode is HBM-bound, so the
"millions of users" topology runs them on SEPARATE replicas — prompt
forwards on a prefill engine, decode ticks on a decode engine — with
the finished prompt pages shipped between them by the fault-tolerant
:class:`~apex_tpu.serving.transfer.PageTransfer` channel, keyed and
deduped by the chained content hashes of
:func:`~apex_tpu.serving.paging.prefix_page_keys`.

The design reuses the whole serving stack instead of forking it: the
:class:`DisaggregatedRouter` IS a
:class:`~apex_tpu.serving.scheduler.ContinuousBatchingScheduler` whose
engine is a composite (:class:`_DisaggEngine`) presenting what the
scheduler calls of ``PagedDecodeEngine``. Every decode-path method delegates to the
ACTIVE replica (the one backing the slots); only ``prefill`` routes:

1. remote replica ``routable`` → run the prompt forward there, ship
   the non-shared pages across, install them into pages the active
   pool allocated (same order a local prefill would), register the
   prefix chain, return the logits. The slot's cache row ends up
   BITWISE identical to a colocated prefill — same jitted program,
   same inputs, pages copied verbatim — which is why fault-free
   disaggregated streams are integer-identical to the colocated
   scheduler's.
2. remote down, transfer budget exhausted, payload quarantined, or
   the remote pool refused the prompt → typed error
   (:class:`~apex_tpu.serving.health.TransferFailed` /
   :class:`~apex_tpu.serving.health.TransferCorrupt` /
   :class:`~apex_tpu.serving.health.ReplicaUnavailable`), caught here,
   and the admission is served COLOCATED on the active engine — the
   request never observes the degradation (graceful ladder: remote →
   colocated → scheduler retry budget → typed outcome).

Health and failover: the router draws the ``replica_health`` fault
site once per replica per tick (fixed order — replay-exact) and folds
the probes into each replica's
:class:`~apex_tpu.serving.health.ReplicaHealth` ladder alongside real
transfer/prefill outcomes. A DOWN remote just stops receiving
prefills. A DOWN *active* replica triggers mid-stream failover: every
occupied slot is drained back to the queue front (the preemption
resume path — re-prefill from prompt + generated, sampling keys fold
``(seed, n_generated)``, so committed streams stay bit-identical) and
the replicas swap roles; the recovered ex-active replica later rejoins
as the remote prefill target. Admission, deadlines, retry budgets, the
progress watchdog, and flight-recorder attachment all come from the
base scheduler unchanged — a dead replica produces typed outcomes,
never a hang.

Clock accounting: a remote prefill runs CONCURRENTLY with the active
replica's decode ticks, so the router does not charge its sequential
depth to the work-charged tick clock the way colocated admission does
— it charges the deterministic handoff cost instead
(``handoff_ticks_per_page`` per shipped page, plus one backoff tick
per retry attempt, observed in the ``serving_transfer_ticks``
histogram). That unblocked-decode gap is a p99 ITL win on the tick clock (not
measured on the chip: no benchmark cell runs a router); sampling keys
never see the clock, so streams are unaffected.

Scope: both replicas must be engines with identical model
config/geometry and SHARED injector+tracer (one deterministic fault
and event sequence). Chunked prefill, model drafters/tree speculation,
and int8 page pools stay colocated-only for now — the constructor
refuses them typed.

Pool scale: :class:`PoolRouter` generalizes the pair to N prefill x M
decode replicas behind the same single admission queue (the DistServe
/ Mooncake production shape — PAPERS.md). Prefill admissions route by
measured load (health rung, link ticks already routed this pass,
pages-free headroom, fixed order — the ``pool_route`` fault site can
degrade the pick to fixed order, never the stream); ONE decode replica
backs the scheduler slots while its siblings are failover targets
chosen by pages-free headroom, with the ladder decode sibling →
borrowed prefill replica → last-replica-standing, and a ``rebalance``
move home once a decode replica recovers. Handoffs default to the
device-to-device :class:`~apex_tpu.serving.transfer.PageReshard`
(spec-to-spec over the replica pair's mesh placement, priced
``ici_ticks_per_page`` within a slice / ``dcn_ticks_per_page`` across,
both cheaper than ``handoff_ticks_per_page``), degrading to the
host-staged channel on
:class:`~apex_tpu.serving.health.ReshardFailed`. The admission clock
uses a link-overlap model: handoffs routed to distinct prefill
replicas within one pass are charged the busy-horizon increase, not
the sum — with one prefill replica this reduces exactly to the pair's
serial charge, and with several it is a goodput win on the tick clock (not measured
on the chip). The validation contract
(``_validate_replicas``) applies pairwise across ALL N+M replicas,
and the shared-``PrefixRegistry``-or-none rule is pool-wide.

This module is host state (router bookkeeping, health ladders) —
APX401 registers it like ``serving.health``/``serving.faults``.
"""

from typing import Dict, List, Mapping, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from apex_tpu.serving.cache import MODEL_POOLS, max_pages_per_slot
from apex_tpu.serving.faults import FaultInjector, InjectedFault
from apex_tpu.serving.health import (HEALTH_STATES, PoolExhausted,
                                     ReplicaHealth, ReplicaUnavailable,
                                     ReshardFailed, TransferCorrupt,
                                     TransferFailed)
from apex_tpu.serving.paging import prefix_page_keys
from apex_tpu.serving.scheduler import ContinuousBatchingScheduler
from apex_tpu.serving.transfer import (PageReshard, PageTransfer,
                                       make_insert_pages_fn)

#: The remote replica prefills every admission into this slot, then
#: frees it once the pages have shipped — admissions are sequential,
#: so one staging slot suffices and the remote pool's prefix registry
#: (not its slots) carries its cross-request dedup.
_STAGING_SLOT = 0

#: Fixed health-probe order per tick (initial role names — replay
#: depends on draw ORDER, not on which replica currently serves).
_REPLICA_ORDER = ("prefill", "decode")


#: Engine attributes every replica in a pool must agree on: the page
#: geometry the handoff relies on, plus everything that shapes a
#: committed stream (a mixed pool could route the same request to a
#: replica that samples differently).
_PAIRED_ATTRS = ("cfg", "num_slots", "max_len", "page_size", "buckets",
                 "spec_k", "top_k", "top_p", "adaptive_spec",
                 "prefix_sharing")


def _as_pool(engines) -> List:
    """Normalize an engine-or-sequence argument to a list (the 1x1
    router passes bare engines; the pool router passes sequences)."""
    if isinstance(engines, (list, tuple)):
        return list(engines)
    return [engines]


def _pool_names(n_prefill: int, n_decode: int):
    """Replica names by role and pool index. The 1x1 pair keeps the
    historical bare names (``prefill``/``decode`` — metric labels and
    chaos replays depend on them); pools index (``prefill0``...)."""
    if n_prefill == 1 and n_decode == 1:
        return ("prefill",), ("decode",)
    return (tuple(f"prefill{i}" for i in range(n_prefill)),
            tuple(f"decode{i}" for i in range(n_decode)))


def _validate_replicas(prefill_engines, decode_engines) -> None:
    """The pool pairing contract, applied pairwise across ALL N+M
    replicas (the 1x1 pair is the degenerate case): every replica is a
    distinct engine, every geometry/sampling attribute matches
    the first replica's (transitively: pairwise), and the host tier /
    injector / tracer are each ONE shared instance pool-wide — a
    per-pair check would admit a 2x2 pool whose halves fork the prefix
    namespace or the fault-draw sequence."""
    prefills = _as_pool(prefill_engines)
    decodes = _as_pool(decode_engines)
    if not prefills or not decodes:
        raise ValueError(
            "a replica pool needs at least one prefill and one decode "
            "engine")
    pnames, dnames = _pool_names(len(prefills), len(decodes))
    named = list(zip(pnames, prefills)) + list(zip(dnames, decodes))
    engines = [e for _, e in named]
    if len({id(e) for e in engines}) != len(engines):
        raise ValueError(
            "disaggregation needs two engine instances per pair: every "
            "pool replica must be a DISTINCT engine (a shared instance "
            "would alias slots and page pools)")
    for _, eng in named:
        if getattr(eng.cfg, "indexed", False):
            raise ValueError(
                "page transfer is not offered over an indexed pool "
                f"({type(eng.cfg).__name__}): the handoff ships the pages a "
                "block table names, and each page's index keys would have "
                "to travel with it; such a model stays colocated")
        if getattr(eng.cfg, "recurrent", False):
            raise ValueError(
                "page transfer is not offered for a model with recurrent "
                f"layers ({type(eng.cfg).__name__}): the handoff ships K/V "
                "pages, and the slot's recurrent state and convolution "
                "tails would have to travel with them; such a model "
                "stays colocated")
        if getattr(eng.cfg, "window", 0):
            raise ValueError(
                f"page transfer is not offered over "
                f"{MODEL_POOLS[eng.cfg.pools][1]} "
                f"({type(eng.cfg).__name__}): the handoff ships the "
                "pages a block table names, and the slot's cycle of window "
                "pages would have to travel with them; such a model stays "
                "colocated")
        if getattr(eng.cache, "k_scale", None) is not None:
            raise ValueError(
                "disaggregated serving is not offered over the int8 "
                "page pool: shipped pages would carry page-local "
                "scales quantized against the SENDER's amax sweep; "
                "kv8 keeps colocated serving")
        if eng.draft_model is not None or eng.tree_spec:
            raise ValueError(
                "model drafters / tree speculation stay colocated: "
                "the drafter's lockstep cache would need its own "
                "cross-replica handoff (n-gram spec_k works "
                "disaggregated)")
    ref_name, ref = named[0]
    for attr in _PAIRED_ATTRS:
        for name, eng in named[1:]:
            va, vb = getattr(ref, attr), getattr(eng, attr)
            if va != vb:
                raise ValueError(
                    f"disaggregated replicas must agree on {attr}: "
                    f"{ref_name}={va!r} vs {name}={vb!r}")
    if len({id(eng.host_tier) for eng in engines}) > 1:
        raise ValueError(
            "all replicas must share ONE PrefixRegistry host tier "
            "(or none of them): the registry is the global content-"
            "addressed map — split tiers would fork the prefix "
            "namespace (construct every engine with the same "
            "host_tier=)")
    if len({id(eng.injector) for eng in engines}) > 1:
        raise ValueError(
            "all replicas must share ONE FaultInjector: fault draws "
            "form a single deterministic sequence (construct every "
            "engine with the same injector=)")
    if len({id(eng.tracer) for eng in engines}) > 1:
        raise ValueError(
            "all replicas must share ONE Tracer: events, metrics and "
            "the stats view live in a single registry (construct "
            "every engine with the same tracer=)")


class _DisaggEngine:
    """The composite engine behind :class:`DisaggregatedRouter`:
    presents what the scheduler calls of ``PagedDecodeEngine`` over two
    replicas.
    Attribute/method access falls through to the ACTIVE replica (the
    one whose slots the scheduler drives); ``prefill`` routes per the
    module doc. Swappable: :meth:`switch_active` exchanges the roles
    on failover."""

    def __init__(self, prefill_engine, decode_engine,
                 transfer: PageTransfer,
                 health: Dict[str, ReplicaHealth],
                 handoff_ticks_per_page: float,
                 backoff_ticks: int):
        # set the delegation table FIRST: __getattr__ consults it
        self._replicas = {"prefill": prefill_engine,
                          "decode": decode_engine}
        self._active_name = "decode"
        self._remote_name = "prefill"
        self._order = _REPLICA_ORDER
        self.transfer = transfer
        self.health = health
        self.handoff_ticks_per_page = float(handoff_ticks_per_page)
        self.backoff_ticks = int(backoff_ticks)
        self.injector = decode_engine.injector
        self.tracer = decode_engine.tracer
        self.stats = decode_engine.stats
        self._insert = make_insert_pages_fn()
        self._admit_charge: Optional[int] = None

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.__dict__["_replicas"][
            self.__dict__["_active_name"]], name)

    @property
    def active(self):
        return self._replicas[self._active_name]

    @property
    def remote(self):
        return self._replicas[self._remote_name]

    @property
    def active_name(self) -> str:
        return self._active_name

    @property
    def remote_name(self) -> str:
        return self._remote_name

    # -- health / failover ----------------------------------------------

    def health_tick(self) -> None:
        """One ``replica_health`` probe per replica, fixed order
        (``self._order`` — all prefill names then all decode names,
        never the current role assignment) — the router calls this at
        the top of every admission pass, so probe draw indices are a
        pure function of the tick count and the POOL SHAPE, not of
        which replica currently serves."""
        for name in self._order:
            fired, _ = self.injector.draw("replica_health")
            self.health[name].probe(not fired)

    @property
    def active_down(self) -> bool:
        return not self.health[self._active_name].routable

    @property
    def remote_routable(self) -> bool:
        return self.health[self._remote_name].routable

    def switch_active(self) -> None:
        self._active_name, self._remote_name = (self._remote_name,
                                                self._active_name)

    # -- admission-charge handshake with the router ---------------------

    def pop_admit_charge(self, default: int) -> int:
        # a remote prefill staged its handoff (+ promote) cost here; a
        # colocated one staged on the active engine — delegate so its
        # host-tier repricing (suffix depth + promote ticks) survives
        charge, self._admit_charge = self._admit_charge, None
        if charge is not None:
            return charge
        return self.active.pop_admit_charge(default)

    # -- routed prefill -------------------------------------------------

    def prefill(self, slot: int, prompt: Sequence[int]):
        trc = self.tracer
        if self.remote_routable:
            try:
                return self._remote_prefill(slot, prompt,
                                            self._remote_name)
            except (TransferFailed, TransferCorrupt,
                    ReplicaUnavailable) as e:
                # degrade, don't fail: the admission is served
                # colocated on the active engine; the request never
                # sees the transfer/replica fault
                if trc.enabled:
                    trc.instant("failover", slot=slot,
                                cause=type(e).__name__,
                                replica=self._remote_name)
        self.stats.colocated_prefills += 1
        return self.active.prefill(slot, prompt)

    def _remote_prefill(self, slot: int, prompt: Sequence[int],
                        rname: str):
        act, rem = self.active, self._replicas[rname]
        rhealth = self.health[rname]
        toks = [int(t) for t in prompt]
        try:
            logits = rem.prefill(_STAGING_SLOT, toks)
        except PoolExhausted as e:
            # remote CAPACITY, not remote failure: no health demerit,
            # but the admission cannot be staged there right now
            raise ReplicaUnavailable(
                f"remote replica {rname!r} page pool "
                f"refused the prompt: {e}",
                replica=rname) from e
        except InjectedFault:
            # a transient device fault on the remote replica: the
            # remote engine rolled its page references back; propagate
            # so the scheduler charges the retry budget exactly like a
            # colocated prefill fault — and let repeated faults walk
            # the replica down the ladder toward colocated routing
            rhealth.probe(False)
            raise
        # the remote prefill staged its OWN admission repricing (it may
        # carry a host tier); the router charges handoff ticks instead
        rem.pop_admit_charge(0)
        # allocate the destination pages in the SAME order a colocated
        # prefill would: longest registered prefix run shared, host-
        # tier promotions extending it, the remainder fresh from the
        # active pool
        keys = prefix_page_keys(toks, act.page_size)
        n_pages = max_pages_per_slot(len(toks), act.page_size)
        shared = act.pool.match_prefix(keys) if act.prefix_sharing \
            else []
        promoted: List[int] = []
        promote_ticks = 0
        if act.host_tier is not None and act.prefix_sharing \
                and len(shared) < n_pages:
            promoted, promote_ticks = act._promote_chain(
                keys, len(shared))
        covered = len(shared) + len(promoted)
        private: List[int] = []
        for _ in range(n_pages - covered):
            p = act.pool.alloc()
            if p is None:
                for q in shared + promoted + private:
                    act.pool.release(q)
                rem.free_slot(_STAGING_SLOT)
                raise PoolExhausted(
                    f"prompt needs {n_pages} pages; pool has "
                    f"{act.pool.num_free} free and nothing left to "
                    "evict", need=n_pages, free=act.pool.num_free,
                    cached=act.pool.num_cached)
            private.append(p)
        src_pages = rem._slot_pages[_STAGING_SLOT][covered:n_pages]
        self.stats.transfer_pages_deduped += covered
        try:
            k_tile, v_tile, attempts, tpp, tier = self._ship_pages(
                rem, toks, src_pages, rname, rhealth)
        except (TransferFailed, TransferCorrupt):
            for q in shared + promoted + private:
                act.pool.release(q)
            rem.free_slot(_STAGING_SLOT)
            raise
        pages = shared + promoted + private
        # install: page list, block-table row + true prompt length
        # (exactly what the jitted colocated prefill leaves; the row
        # through the engine, which owns the table and uploads it before
        # the next step), then scatter the verified tiles into the
        # private pages
        act.install_slot(slot, pages, len(toks))
        if private:
            k_dev, v_dev = tier.shard_fn(k_tile, v_tile)
            act.cache = self._insert(
                act.cache, jnp.asarray(private, jnp.int32), k_dev,
                v_dev)
        if act.prefix_sharing:
            act.pool.register_prefix(keys, pages)
        rem.free_slot(_STAGING_SLOT)
        self.stats.remote_prefills += 1
        ticks = self._handoff_ticks(len(private), attempts, tpp)
        self._stage_charge(ticks, promote_ticks, rname)
        tier.observe_ticks(rname, ticks + promote_ticks)
        # the logits hop replicas with the pages (a 1 x vocab row —
        # noise next to the tiles); values survive the host round-trip
        # bit-for-bit
        return jnp.asarray(np.asarray(logits))

    def _ship_pages(self, rem, toks, src_pages, rname: str, rhealth):
        """Move the private pages over the channel and return
        ``(k_tile, v_tile, attempts, ticks_per_page, tier)`` — the
        pool engine overrides this to try the device-to-device reshard
        first and degrade to this host-staged path on
        :class:`ReshardFailed`."""
        k_tile, v_tile, attempts = self.transfer.ship(
            rem, toks, src_pages, replica=rname, health=rhealth)
        return (k_tile, v_tile, attempts, self.handoff_ticks_per_page,
                self.transfer)

    def _handoff_ticks(self, shipped_pages: int, attempts: int,
                       tpp: Optional[float] = None) -> int:
        """Deterministic clock cost of a delivered handoff: the shipped
        bytes at ``tpp`` ticks per page (the link's rate —
        ``handoff_ticks_per_page`` for the host bounce; the pool's
        per-link ICI/DCN rates are cheaper; a page is a small fraction
        of a decode step's HBM read and the cost-tier entries pin the
        ratios), floored at one control tick, plus one backoff tick per
        failed attempt."""
        if tpp is None:
            tpp = self.handoff_ticks_per_page
        moved = int(np.ceil(shipped_pages * tpp))
        return max(1, moved) + (attempts - 1) * self.backoff_ticks

    def _stage_charge(self, ticks: int, promote_ticks: int,
                      rname: str) -> None:
        """Stage the admission's deterministic clock charge for the
        router's ``pop_admit_charge`` handshake. The pair charges the
        handoff serially; the pool engine overrides this with the
        link-overlap model (concurrent handoffs on distinct links
        share the same wall ticks)."""
        self._admit_charge = ticks + promote_ticks

    # -- audit / diagnostics over BOTH replicas -------------------------

    def check_invariants(self) -> bool:
        self.active.check_invariants()
        self.remote.check_invariants()
        return True

    def pool_snapshot(self) -> Dict:
        return {"active": {"replica": self._active_name,
                           **self.active.pool_snapshot()},
                "remote": {"replica": self._remote_name,
                           **self.remote.pool_snapshot()}}


class _PoolEngine(_DisaggEngine):
    """The N x M composite behind :class:`PoolRouter`: the pair
    engine's machinery generalized to per-role replica pools. One
    decode replica is ACTIVE (its slots back the scheduler); the other
    decode replicas are idle failover targets chosen by pages-free
    headroom; prefill admissions route across the prefill pool by
    measured load. Handoffs try the device-to-device
    :class:`~apex_tpu.serving.transfer.PageReshard` first (per-link
    ICI/DCN tick pricing from the replica pair's mesh placement) and
    degrade to the host-staged :class:`PageTransfer` on
    :class:`ReshardFailed`. The admission clock uses the link-overlap
    model: handoffs routed to DISTINCT prefill replicas within one
    admission pass overlap on the wall clock, so the pass is charged
    the horizon increase, not the sum — with one prefill replica this
    reduces exactly to the pair's serial charge."""

    def __init__(self, prefills: Sequence, decodes: Sequence,
                 transfer: PageTransfer,
                 reshard: Optional[PageReshard],
                 handoff_ticks_per_page: float,
                 ici_ticks_per_page: float,
                 dcn_ticks_per_page: float,
                 backoff_ticks: int,
                 recover_after: int,
                 placement: Optional[Mapping[str, int]]):
        # delegation table FIRST (__getattr__ consults it)
        pnames, dnames = _pool_names(len(prefills), len(decodes))
        self._replicas = dict(zip(pnames + dnames,
                                  list(prefills) + list(decodes)))
        self.prefill_names = pnames
        self.decode_names = dnames
        self._order = pnames + dnames
        self._active_name = dnames[0]
        self._remote_name = pnames[0]  # base-class seam; pool routing
        self.transfer = transfer       # picks per admission instead
        self.reshard = reshard
        self.handoff_ticks_per_page = float(handoff_ticks_per_page)
        self.ici_ticks_per_page = float(ici_ticks_per_page)
        self.dcn_ticks_per_page = float(dcn_ticks_per_page)
        self.backoff_ticks = int(backoff_ticks)
        self.placement = dict(placement or {})
        eng0 = self._replicas[self._active_name]
        self.injector = eng0.injector
        self.tracer = eng0.tracer
        self.stats = eng0.stats
        self.health = {
            name: ReplicaHealth(name, registry=self.tracer.registry,
                                recover_after=recover_after)
            for name in self._order}
        self._insert = make_insert_pages_fn()
        self._admit_charge: Optional[int] = None
        self._pass_busy: Dict[str, int] = {}
        self._route_hot: Dict[str, object] = {}
        self._load_hot: Dict[str, object] = {}
        # tenancy threading: last prefill replica routed per tenant —
        # a deterministic affinity tiebreak in the routing score
        # (prefix locality for a tenant's traffic), consulted only
        # when the scheduler stamps admission_tenant (tenancy mode)
        self._tenant_affinity: Dict[str, str] = {}

    # -- pool observability ---------------------------------------------

    def _route_mark(self, reason: str) -> None:
        c = self._route_hot.get(reason)
        if c is None:
            c = self._route_hot[reason] = self.tracer.registry.counter(
                "serving_pool_routing_total",
                help="prefill routing decisions by reason (load = "
                     "least-loaded pick, fallback = pool_route fault "
                     "degraded to fixed order, colocated = no "
                     "routable prefill replica, degraded = "
                     "transfer/replica fault forced colocated)",
                labels={"reason": reason})
        c.inc()

    def _load_gauge(self, name: str):
        g = self._load_hot.get(name)
        if g is None:
            g = self._load_hot[name] = self.tracer.registry.gauge(
                "serving_pool_replica_load",
                help="link ticks routed to this prefill replica in "
                     "the current admission pass (the routing score's "
                     "queue-depth term)",
                labels={"replica": name})
        return g

    # -- admission pass state -------------------------------------------

    def begin_admission_pass(self) -> None:
        """Reset the per-pass link-busy horizon — the router calls
        this at the top of every admission pass (tick), before the
        health probes, so charge staging is replay-exact."""
        self._pass_busy.clear()
        for name in self.prefill_names:
            self._load_gauge(name).set(0.0)

    # -- load-based prefill routing -------------------------------------

    def _load_key(self, name: str):
        """Routing score, lower is better: health rung first (healthy
        before degraded), then link ticks already routed to the
        replica this pass (queue depth), then the admitting tenant's
        replica affinity (the replica that last served the tenant —
        prefix locality; a constant when tenancy is off, so the
        untenanted key is unchanged), then pages-free headroom, then
        fixed pool order. Placement may shift with tenancy, streams
        may not: committed tokens are placement-invariant."""
        tenant = self.admission_tenant
        affine = 0 if (tenant is not None
                       and self._tenant_affinity.get(tenant) == name) \
            else 1
        return (-HEALTH_STATES.index(self.health[name].state),
                self._pass_busy.get(name, 0),
                affine,
                -self._replicas[name].pool.num_free,
                self._order.index(name))

    def _note_route(self, name: str) -> str:
        """Record the pick as the admitting tenant's affinity replica
        for the next admission's tiebreak; returns the pick."""
        tenant = self.admission_tenant
        if tenant is not None:
            self._tenant_affinity[tenant] = name
        return name

    def _route_prefill(self) -> Optional[str]:
        """Pick the prefill replica for one remote admission, or None
        to serve colocated. Draws the ``pool_route`` fault site once
        per remote admission: a fired draw degrades the pick to the
        FIRST routable replica in fixed pool order (a routing-policy
        fault can shift placement, never a stream)."""
        cands = [n for n in self.prefill_names
                 if n != self._active_name and self.health[n].routable]
        if not cands:
            self._route_mark("colocated")
            return None
        for n in cands:
            self._load_gauge(n).set(self._pass_busy.get(n, 0))
        fired, _ = self.injector.draw("pool_route")
        if fired:
            self.stats.route_fallbacks += 1
            self._route_mark("fallback")
            return self._note_route(cands[0])
        self._route_mark("load")
        return self._note_route(min(cands, key=self._load_key))

    def prefill(self, slot: int, prompt: Sequence[int]):
        trc = self.tracer
        rname = self._route_prefill()
        if rname is not None:
            try:
                return self._remote_prefill(slot, prompt, rname)
            except (TransferFailed, TransferCorrupt,
                    ReplicaUnavailable) as e:
                # degrade, don't fail — exactly the pair's ladder
                if trc.enabled:
                    trc.instant("failover", slot=slot,
                                cause=type(e).__name__, replica=rname)
                self._route_mark("degraded")
        self.stats.colocated_prefills += 1
        return self.active.prefill(slot, prompt)

    # -- two-tier handoff -----------------------------------------------

    def _link_tpp(self, rname: str) -> float:
        """Ticks per page for the (source, active) link, from mesh
        placement: same slice id rides the ICI rate, different slices
        the DCN rate. No reshard channel -> the host-staged rate."""
        if self.reshard is None:
            return self.handoff_ticks_per_page
        src = self.placement.get(rname, 0)
        dst = self.placement.get(self._active_name, 0)
        return (self.ici_ticks_per_page if src == dst
                else self.dcn_ticks_per_page)

    def _ship_pages(self, rem, toks, src_pages, rname: str, rhealth):
        if self.reshard is None:
            return super()._ship_pages(rem, toks, src_pages, rname,
                                       rhealth)
        try:
            k_tile, v_tile, attempts = self.reshard.ship(
                rem, toks, src_pages, replica=rname, health=rhealth)
            return (k_tile, v_tile, attempts, self._link_tpp(rname),
                    self.reshard)
        except ReshardFailed as e:
            # the d2d link lost its whole budget: degrade to the
            # host-staged tier, carrying the burned attempts into the
            # backoff charge (each failed reshard attempt cost real
            # wall time). A host-tier exhaustion after this propagates
            # and the admission falls back colocated as usual.
            if self.tracer.enabled:
                self.tracer.instant("failover", cause="ReshardFailed",
                                    replica=rname, tier="host_staged",
                                    corrupt=e.corrupt)
            burned = e.attempts
        k_tile, v_tile, attempts = self.transfer.ship(
            rem, toks, src_pages, replica=rname, health=rhealth)
        return (k_tile, v_tile, burned + attempts,
                self.handoff_ticks_per_page, self.transfer)

    # -- link-overlap clock charging ------------------------------------

    def _stage_charge(self, ticks: int, promote_ticks: int,
                      rname: str) -> None:
        """Charge this admission the HORIZON INCREASE of the per-pass
        link-busy model, not the serial handoff cost: handoffs routed
        to distinct prefill replicas in one pass overlap on the wall
        clock (distinct source links), so only the pass's critical
        path costs ticks. Floored at one control tick per admission;
        promote ticks are active-engine work and stay serial. With a
        single prefill replica every handoff extends the same link, so
        the charge is exactly the pair router's."""
        old_h = max(self._pass_busy.values(), default=0)
        self._pass_busy[rname] = self._pass_busy.get(rname, 0) + ticks
        new_h = max(self._pass_busy.values())
        self._admit_charge = max(1, new_h - old_h) + promote_ticks
        self._load_gauge(rname).set(self._pass_busy[rname])

    # -- N-way failover / placement -------------------------------------

    @property
    def active_borrowed(self) -> bool:
        """True when a prefill replica is serving as the active decode
        engine (the last rung of the failover ladder before
        last-replica-standing)."""
        return self._active_name in self.prefill_names

    def pick_active_target(self) -> Optional[str]:
        """Where the slots should move when the active replica goes
        down: the routable replica with the most pages-free headroom,
        decode siblings before prefill borrows, fixed order breaking
        ties. None = nobody routable — last replica standing keeps
        serving on the incumbent."""
        cands = [n for n in self._order
                 if n != self._active_name and self.health[n].routable]
        if not cands:
            return None
        return max(cands, key=lambda n: (n in self.decode_names,
                                         self._replicas[n].pool.num_free,
                                         -self._order.index(n)))

    def pick_home_decode(self) -> Optional[str]:
        """The decode replica to rebalance back onto once one is
        routable again (only consulted while the active is a borrowed
        prefill replica)."""
        cands = [n for n in self.decode_names
                 if n != self._active_name and self.health[n].routable]
        if not cands:
            return None
        return max(cands, key=lambda n: (self._replicas[n].pool.num_free,
                                         -self._order.index(n)))

    def set_active(self, name: str) -> None:
        """Move the decode placement (the router drained the slots
        first) — every move emits the ``rebalance`` lifecycle instant
        and counts in ``stats.rebalances``."""
        old = self._active_name
        self._active_name = name
        self.stats.rebalances += 1
        if self.tracer.enabled:
            self.tracer.instant("rebalance", replica=old, target=name)

    # -- audit over the WHOLE pool --------------------------------------

    def check_invariants(self) -> bool:
        for eng in self._replicas.values():
            eng.check_invariants()
        return True

    def pool_snapshot(self) -> Dict:
        return {name: {"active": name == self._active_name,
                       **eng.pool_snapshot()}
                for name, eng in self._replicas.items()}


def _preempt_drain(router, cause: str) -> int:
    """Drain every occupied slot back to the queue FRONT in submission
    order (the preemption resume path — re-prefill from prompt +
    generated, sampling keys fold ``(seed, n_generated)``, so committed
    streams stay bit-identical) and free the slots on the CURRENT
    active replica. Shared by the pair's failover and the pool's
    failover/rebalance moves; returns the drained slot count."""
    eng = router.engine
    trc = router.tracer
    old = eng.active
    occupied = [(i, s) for i, s in enumerate(router._slots)
                if s is not None]
    for i, s in sorted(occupied, key=lambda t: t[1].request_id,
                       reverse=True):
        if trc.enabled:
            trc.instant("preempted", request_id=s.request_id,
                        slot=i, cause=cause)
        router._queue.appendleft((s.request_id, s.request,
                                  list(s.generated)))
        router._slots[i] = None
        old.free_slot(i)
    return len(occupied)


class DisaggregatedRouter(ContinuousBatchingScheduler):
    """The two-replica serving tier (see module doc): a
    ``ContinuousBatchingScheduler`` over a :class:`_DisaggEngine`
    composite, plus per-tick health probes and mid-stream failover.

    ``transfer_max_retries`` bounds re-attempts per page handoff;
    ``handoff_ticks_per_page`` / ``backoff_ticks`` set the
    deterministic clock cost of a delivered handoff (see
    ``_handoff_ticks``); ``recover_after`` is each replica's
    consecutive-success hysteresis on the way back up the health
    ladder. All remaining keywords are the base scheduler's
    (``chunk_tokens`` excepted — chunked prefill stays colocated)."""

    def __init__(self, prefill_engine, decode_engine, eos_id: int, *,
                 transfer_max_retries: int = 2,
                 handoff_ticks_per_page: float = 0.125,
                 backoff_ticks: int = 1,
                 recover_after: int = 2,
                 transfer: Optional[PageTransfer] = None,
                 **kwargs):
        _validate_replicas(prefill_engine, decode_engine)
        if kwargs.get("chunk_tokens") is not None:
            raise ValueError(
                "chunked prefill stays colocated: the disaggregated "
                "router runs monolithic admission prefill on the "
                "remote replica (the chunks would serialize against "
                "the very decode ticks disaggregation unblocks)")
        tracer = decode_engine.tracer
        registry = tracer.registry
        health = {name: ReplicaHealth(name, registry=registry,
                                      recover_after=recover_after)
                  for name in _REPLICA_ORDER}
        if transfer is None:
            transfer = PageTransfer(injector=decode_engine.injector,
                                    tracer=tracer,
                                    stats=decode_engine.stats,
                                    max_retries=transfer_max_retries)
        engine = _DisaggEngine(prefill_engine, decode_engine, transfer,
                               health, handoff_ticks_per_page,
                               backoff_ticks)
        super().__init__(engine, eos_id, **kwargs)

    @property
    def health(self) -> Dict[str, ReplicaHealth]:
        return self.engine.health

    def _admit(self) -> None:
        eng = self.engine
        eng.health_tick()
        if eng.active_down and eng.remote_routable:
            self._failover()
        super()._admit()

    def _failover(self) -> None:
        """The ACTIVE replica went down mid-stream: drain every
        occupied slot back to the queue FRONT in submission order (the
        preemption resume path — bit-identical continuation) and swap
        roles; admission continues this same tick on the survivor.
        When BOTH replicas are down the router keeps serving on the
        incumbent instead (last replica standing: health gates
        routing, not survival)."""
        eng = self.engine
        trc = self.tracer
        if trc.enabled:
            trc.instant("failover",
                        slots=sum(s is not None for s in self._slots),
                        replica=eng.active_name)
        _preempt_drain(self, "failover")
        eng.switch_active()
        self.stats.failovers += 1


class PoolRouter(ContinuousBatchingScheduler):
    """The pool-scale serving tier: N prefill x M decode replicas
    behind ONE admission queue (see module doc) — a
    ``ContinuousBatchingScheduler`` over a :class:`_PoolEngine`
    composite. Prefill admissions route by measured load (health rung,
    per-pass link busy, pages-free headroom); one decode replica backs
    the slots and its siblings are failover targets picked by
    pages-free headroom; page handoffs ride the device-to-device
    :class:`~apex_tpu.serving.transfer.PageReshard` by default, priced
    per link from ``placement`` (same slice id -> ``ici_ticks_per_page``,
    different -> ``dcn_ticks_per_page``), degrading to the host-staged
    :class:`~apex_tpu.serving.transfer.PageTransfer` at
    ``handoff_ticks_per_page`` on :class:`ReshardFailed`.

    ``prefill_engines`` / ``decode_engines`` are sequences of paged
    engines (a bare engine works too — the 1x1 pool); ALL replicas
    must share one injector, one tracer, and one PrefixRegistry host
    tier (or none), with identical geometry — validated pairwise
    across the whole pool. ``placement`` maps replica name
    (``prefill0``.. / ``decode0``..; the 1x1 pool keeps the bare
    ``prefill``/``decode`` names) to a mesh slice id; unmapped
    replicas sit on slice 0. ``use_reshard=False`` (or
    ``reshard=None`` with it) pins the pool to host staging.

    Committed streams are bit-identical to the 1x1
    :class:`DisaggregatedRouter` (and to colocated) through every
    routing, resharding, failover, and fault path: placement never
    touches sampling keys, drains resume bit-exactly, and fault
    ladders only ever degrade WHERE work runs, never what commits."""

    def __init__(self, prefill_engines, decode_engines, eos_id: int, *,
                 transfer_max_retries: int = 2,
                 handoff_ticks_per_page: float = 0.125,
                 ici_ticks_per_page: float = 0.03125,
                 dcn_ticks_per_page: float = 0.0625,
                 backoff_ticks: int = 1,
                 recover_after: int = 2,
                 placement: Optional[Mapping[str, int]] = None,
                 transfer: Optional[PageTransfer] = None,
                 reshard: Optional[PageReshard] = None,
                 use_reshard: bool = True,
                 **kwargs):
        prefills = _as_pool(prefill_engines)
        decodes = _as_pool(decode_engines)
        _validate_replicas(prefills, decodes)
        if kwargs.get("chunk_tokens") is not None:
            raise ValueError(
                "chunked prefill stays colocated: the pool router "
                "runs monolithic admission prefill on the remote "
                "replicas (the chunks would serialize against the "
                "very decode ticks disaggregation unblocks)")
        known = set(_pool_names(len(prefills), len(decodes))[0]) \
            | set(_pool_names(len(prefills), len(decodes))[1])
        unknown = set(placement or {}) - known
        if unknown:
            raise ValueError(
                f"placement names unknown replicas {sorted(unknown)}; "
                f"pool replicas are {sorted(known)}")
        eng0 = decodes[0]
        if transfer is None:
            transfer = PageTransfer(injector=eng0.injector,
                                    tracer=eng0.tracer,
                                    stats=eng0.stats,
                                    max_retries=transfer_max_retries)
        if reshard is None and use_reshard:
            reshard = PageReshard(injector=eng0.injector,
                                  tracer=eng0.tracer,
                                  stats=eng0.stats,
                                  max_retries=transfer_max_retries)
        if not use_reshard:
            reshard = None
        engine = _PoolEngine(prefills, decodes, transfer, reshard,
                             handoff_ticks_per_page,
                             ici_ticks_per_page, dcn_ticks_per_page,
                             backoff_ticks, recover_after, placement)
        super().__init__(engine, eos_id, **kwargs)

    @property
    def health(self) -> Dict[str, ReplicaHealth]:
        return self.engine.health

    def _admit(self) -> None:
        eng = self.engine
        eng.begin_admission_pass()
        eng.health_tick()
        if eng.active_down:
            target = eng.pick_active_target()
            if target is not None:
                self._move_active(target, cause="failover")
                self.stats.failovers += 1
            # else: last replica standing — keep serving on the
            # incumbent (health gates routing, not survival)
        elif eng.active_borrowed:
            target = eng.pick_home_decode()
            if target is not None:
                # a decode replica recovered: move the slots home so
                # the borrowed prefill replica rejoins its pool
                self._move_active(target, cause="rebalance")
        super()._admit()

    def _move_active(self, target: str, cause: str) -> None:
        """Drain the occupied slots (bit-identical preempt-resume) and
        move the decode placement to ``target``; admission continues
        this same tick on the new active replica."""
        eng = self.engine
        trc = self.tracer
        if trc.enabled and cause == "failover":
            trc.instant("failover",
                        slots=sum(s is not None for s in self._slots),
                        replica=eng.active_name, target=target)
        _preempt_drain(self, cause)
        eng.set_active(target)
