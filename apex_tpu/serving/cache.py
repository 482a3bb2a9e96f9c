"""KV cache: a pool of fixed-size pages, block tables, slot lengths.

ONE layout for every family: ``k``/``v`` are a pool of pages stacked on a
leading layer axis to match the model's stacked-layer ``lax.scan``
(:class:`PagedKVCache`; the families that bring their own cores keep
further leaves beside it: :class:`HybridKVCache`, :class:`LatentKVCache`,
:class:`WindowKVCache`), ``block_tables`` maps each slot's logical pages
to physical ones, and ``lengths`` is ``(num_slots,)`` int32 — how many
positions of each slot hold real tokens; it is simultaneously the next
write offset and the attention-mask bound (decode attends ``s <= pos``
with the new row at ``pos``, so stale rows past the length are
unreachable).

The cache is updated inside a jit whose cache argument is DONATED: XLA
reuses the input buffer for the output and a decode step is one in-place
row scatter, not a fresh copy of the pool. The trace-tier linter (APX512)
pins the donation — see ``apex_tpu/lint/traced/aliases.py`` and the
``gpt_paged_decode_step`` registry entries.

dtype: bf16 halves cache HBM and decode is score-bound, not
precision-bound (scores/softmax stay fp32 in the attention bodies of
``models/gpt.py``); fp32 is for parity tests. Under TP the pool's last
axis (whole heads side by side) shards over the ``model`` mesh axis —
each rank holds its local heads' rows, matching the head-major qkv
column shard.
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import GPTConfig


# Physical page ids below this are reserved and never allocated:
NULL_PAGE = 0     # parks unmapped block-table entries; never written
SCRATCH_PAGE = 1  # write dump for redirected rows; never attended
RESERVED_PAGES = 2


class PagedKVCache(NamedTuple):
    """Paged layout: ``k``/``v`` hold a POOL of fixed-size pages shared
    by every slot — ``(L, num_pages, page_size, kv_heads * head_dim)``
    (``kv_heads`` = ``num_heads`` for the GPT family),
    a page's rows holding all K/V heads side by side (head-major), so one
    page of one layer is one contiguous run of whole (sublane, 128-lane)
    tiles that the decode kernel fetches by DMA — and ``block_tables``
    (``(num_slots, max_pages)`` int32) maps each slot's logical page
    index to a physical page. HBM for K/V history
    scales with pages actually allocated (Σ ceil(len/page_size)), not
    ``slots x S_max``; the host-side allocator
    (:class:`apex_tpu.serving.paging.PagePool`) owns which pages are
    live, shared (prefix caching) or free. Heads (the last axis, in
    whole heads) shard over ``model`` under TP; lengths and block tables
    are replicated.

    ``kv_dtype=int8`` mode: the pool stores round-to-nearest symmetric
    int8 with PER-PAGE-PER-HEAD fp32 scales in the trailing
    ``k_scale``/``v_scale`` leaves (``(L, num_pages, num_heads)``,
    amax/127 of each head's page — ``apex_tpu.quant.kv_quantize``).
    The scales ride the same donated cache tuple as the block tables
    (6 alias pairs instead of 4, pinned by APX512), shard their head
    axis over ``model`` like the pool, and are cloned together with
    their pages on copy-on-write. bf16/fp32 caches leave both fields
    ``None`` — an optional trailing NamedTuple field vanishes from the
    pytree, so every existing 4-leaf construction and donation site is
    unchanged.
    """
    k: jax.Array             # (L, num_pages, page_size, num_heads * hd)
    v: jax.Array             # (L, num_pages, page_size, num_heads * hd)
    lengths: jax.Array       # (num_slots,) int32, valid positions
    block_tables: jax.Array  # (num_slots, max_pages) int32 page ids
    k_scale: Optional[jax.Array] = None  # (L, num_pages, num_heads) f32
    v_scale: Optional[jax.Array] = None  # (L, num_pages, num_heads) f32


class HybridKVCache(NamedTuple):
    """The cache of a model with recurrent layers (``cfg.recurrent``:
    ``apex_tpu.models.hybrid``, ``apex_tpu.models.nemotron_h``): two kinds of
    state in one donated tuple.
    ``k`` / ``v`` / ``lengths`` / ``block_tables`` are :class:`PagedKVCache`'s,
    the pool's ``L`` counting the full-attention layers only, and the host
    side (``PagePool``, block tables, the page copy) treats them alike.
    ``state`` (one matrix per linear layer, slot and head) and ``conv``
    (the last ``w - 1`` inputs of each linear layer's convolution) are PER
    SLOT and outside the pages: whole at every moment, they cannot be
    paged, shared by prefix or rolled back by rewriting rows; a slot's
    prefill overwrites them and nothing else resets them. ``counters`` (a
    dict of int32 arrays, or nothing) are what a model's decode program
    counts on the device (``cfg.counter_shapes``): they ride the donated
    tuple so that no tick gains a read-back, and are read on request
    (``PagedDecodeEngine.read_counters``).

    The pool beside the state may be a LATENT one (``cfg.recurrent`` and
    ``cfg.latent`` both: ``apex_tpu.models.bailing_hybrid``): ``k`` is then
    the only pool, rows that are key and value at once as in
    :class:`LatentKVCache`, and ``v`` is ``None`` (a ``None`` leaf vanishes
    from the pytree: 5 donated leaves and the counters instead of 6).

    Where the attention PICKS the rows it reads (``cfg.indexed``:
    ``apex_tpu.models.glm_next``, an indexer over pooled keys), ``index`` is
    the indexer's cache, else ``None``: ``rows`` ``[L_attn, num_pages,
    page_size / index_kpool, index_head_dim]``, one pooled key for every
    ``index_kpool`` positions, a page's keys one entry of the SAME block
    table and page ids as ``k`` (a page of latents and its index keys live
    and die together: no second allocator, no second table), in the pool's
    dtype; and ``tail`` ``[L_attn, slots, index_kpool - 1, index_head_dim]``
    float32, the keys of each slot's group that is not whole yet, a ring as
    ``conv`` is (position ``t`` in row ``t % index_kpool``)."""
    k: jax.Array             # (L_attn, num_pages, page_size, kv_heads * hd)
    v: Optional[jax.Array]   # the same, or None beside a latent pool
    lengths: jax.Array       # (num_slots,) int32
    block_tables: jax.Array  # (num_slots, max_pages) int32
    state: jax.Array         # (L_rec, slots, heads, ...) float32
    conv: jax.Array          # (L_rec, slots, w - 1, channels) float32
    counters: Optional[dict] = None
    index: Optional[dict] = None    # {"rows", "tail"} of an indexed pool

    # no quantized pool beside recurrent state (the engine refuses it)
    k_scale = None
    v_scale = None


class LatentKVCache(NamedTuple):
    """The cache of a model whose attention reads ONE row per token and layer
    (``cfg.latent``: ``apex_tpu.models.deepseek``, multi-head latent
    attention): ``k`` is the only pool, ``[L, num_pages, page_size,
    kv_row_width]``, a row holding the normed compressed latent and the roped
    shared key side by side (padded with zeros to whole 128-lane tiles); key
    and value of every head are read out of that one row, so there is no
    ``v``. ``lengths`` / ``block_tables`` are :class:`PagedKVCache`'s, and the
    host side (``PagePool``, block tables, prefix sharing, copy-on-write,
    preemption, page transfer) treats a latent page as any page: there is no
    per-slot state beside the pool. ``counters`` as in
    :class:`HybridKVCache`."""
    k: jax.Array             # (L, num_pages, page_size, kv_row_width)
    lengths: jax.Array       # (num_slots,) int32
    block_tables: jax.Array  # (num_slots, max_pages) int32
    counters: Optional[dict] = None

    # one pool: what reads ``cache.v`` finds nothing to copy, write or ship
    v = None
    # no quantized latent pool (the engine refuses it)
    k_scale = None
    v_scale = None


def ring_pages(window: int, page_size: int) -> int:
    """Pages of a slot's cyclic table: the ``window`` positions ``pos -
    window + 1 .. pos`` touch at most ``cdiv(window + page_size - 1,
    page_size)`` logical pages (9 at a window of 128 and pages of 16), so
    with that many physical pages no two of them share one."""
    return -(-(window + page_size - 1) // page_size)


class WindowKVCache(NamedTuple):
    """The cache of a model some of whose attention layers see only the last
    ``cfg.window`` positions (``apex_tpu.models.exaone_moe``): TWO pools in
    one donated tuple. ``k`` / ``v`` / ``lengths`` / ``block_tables`` are
    :class:`PagedKVCache`'s, the pool's ``L`` counting the FULL-attention
    layers only, and the host side (``PagePool``, block tables, prefix
    sharing, copy-on-write, preemption) treats them alike. ``wk`` / ``wv``
    are the window layers' pool, ``[L_win, RESERVED_PAGES + slots * R,
    page_size, width]`` with ``R`` = :func:`ring_pages`, whose table is
    STATIC and a cycle: logical page ``j`` of slot ``i`` is physical page
    ``RESERVED_PAGES + i * R + j % R`` (:func:`ring_page`). No array holds
    that table: both programs compute what they need of it from ``lengths``
    and the slot's number, so the host never writes or uploads it, a ring
    page is private by construction (never shared, copied or freed), and a
    window layer's bytes a slot are ``R * page_size`` rows at every context
    length. ``counters`` as in :class:`HybridKVCache`."""
    k: jax.Array             # (L_full, num_pages, page_size, width)
    v: jax.Array
    lengths: jax.Array       # (num_slots,) int32
    block_tables: jax.Array  # (num_slots, max_pages) int32
    wk: jax.Array            # (L_win, 2 + slots * R, page_size, width)
    wv: jax.Array
    counters: Optional[dict] = None

    # no quantized pool beside the window pool (the engine refuses it)
    k_scale = None
    v_scale = None

    @property
    def ring(self) -> int:
        return (self.wk.shape[1] - RESERVED_PAGES) // self.lengths.shape[0]

    def window_view(self, window: int):
        """What a window layer's decode attention walks, for every slot at
        its length ``pos``: ``(table (slots, R), pos', start')``. The table names the slot's live pages in logical order
        from the page that holds the window's first position ``max(pos -
        window + 1, 0)``; ``pos'`` and ``start'`` count from that page's
        first row, so ``start'`` lies in the first page and ``pos'`` within
        the ``R`` pages."""
        pos = self.lengths
        page_size, r = self.wk.shape[2], self.ring
        start = jnp.maximum(pos - (window - 1), 0)
        first = start // page_size
        slot = jnp.arange(pos.shape[0], dtype=jnp.int32)
        table = ring_page(slot[:, None], first[:, None] + jnp.arange(r), r)
        return table, pos - first * page_size, start - first * page_size


def ring_page(slot, logical, ring: int):
    """The physical page of logical page ``logical`` of ``slot`` in the
    window pool: the static cyclic table."""
    return RESERVED_PAGES + slot * ring + logical % ring


def max_pages_per_slot(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def _check_pool_sizes(num_slots: int, max_len: int, num_pages: int,
                      page_size: int) -> None:
    if max_len < 1 or num_slots < 1 or page_size < 1:
        raise ValueError(
            f"need positive num_slots/max_len/page_size, got "
            f"{num_slots}/{max_len}/{page_size}")
    if num_pages <= RESERVED_PAGES:
        raise ValueError(
            f"num_pages {num_pages} must exceed the {RESERVED_PAGES} "
            f"reserved pages (null + scratch)")


def init_paged_cache(cfg: GPTConfig, num_slots: int, max_len: int,
                     num_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    """Zero page pool + block tables parked on ``SCRATCH_PAGE`` (writes
    of unoccupied slots land in scratch, reads of it are masked)."""
    _check_pool_sizes(num_slots, max_len, num_pages, page_size)
    if not cfg.use_rope and max_len > cfg.max_position_embeddings:
        raise ValueError(
            f"max_len {max_len} exceeds the learned position table "
            f"({cfg.max_position_embeddings}); raise "
            "max_position_embeddings or use rope")
    shape = (cfg.num_layers, num_pages, page_size,
             cfg.num_heads * cfg.head_dim)
    scales = {}
    if jnp.dtype(dtype) == jnp.int8:
        # quantized pool: zero int8 pages + zero fp32 scales (a
        # 0-scale page dequantizes to exact zeros, so NULL stays
        # pristine before its first real write)
        sscale = (cfg.num_layers, num_pages, cfg.num_heads)
        scales = dict(k_scale=jnp.zeros(sscale, jnp.float32),
                      v_scale=jnp.zeros(sscale, jnp.float32))
    return PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        block_tables=_parked_tables(num_slots, max_len, page_size), **scales)


def _parked_tables(num_slots: int, max_len: int, page_size: int):
    """Block tables with every row parked on ``SCRATCH_PAGE``."""
    return jnp.full((num_slots, max_pages_per_slot(max_len, page_size)),
                    SCRATCH_PAGE, jnp.int32)


def _zero_counters(cfg):
    """The zeroed int32 counters a model's decode program keeps in its cache
    (``cfg.counter_shapes``), or nothing."""
    shapes = getattr(cfg, "counter_shapes", lambda: None)()
    return shapes and {name: jnp.zeros(shape, jnp.int32)
                       for name, shape in shapes.items()}


def init_hybrid_cache(cfg, num_slots: int, max_len: int, num_pages: int,
                      page_size: int, dtype=jnp.bfloat16) -> HybridKVCache:
    """The two kinds of state of a model with recurrent layers (what
    ``cfg`` states: ``serving.decode``, "the seam"): a page pool over the
    attention layers only, rows of ``kv_row_width`` (ONE pool of them where
    the model's attention is latent, ``cfg.latent``: ``v`` is left out), and
    zeroed per-slot recurrent state and convolution tails (float32 both,
    whatever the pool's ``dtype``) for the recurrent layers; zeroed counters
    where the model keeps any; and the indexer's cache (``cfg.indexed``:
    ``cfg.index_shapes``), zeroed, where the attention picks its rows."""
    _check_pool_sizes(num_slots, max_len, num_pages, page_size)
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("no int8 pool beside recurrent state")
    shape = (cfg.kv_layers, num_pages, page_size, cfg.kv_row_width)
    state, conv = cfg.state_shapes(num_slots)
    index = None
    if getattr(cfg, "indexed", False):
        rows, tail = cfg.index_shapes(num_slots, num_pages, page_size)
        index = {"rows": jnp.zeros(rows, dtype),
                 "tail": jnp.zeros(tail, jnp.float32)}
    return HybridKVCache(
        k=jnp.zeros(shape, dtype),
        v=None if getattr(cfg, "latent", False) else jnp.zeros(shape, dtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        block_tables=_parked_tables(num_slots, max_len, page_size),
        state=jnp.zeros(state, jnp.float32),
        conv=jnp.zeros(conv, jnp.float32),
        counters=_zero_counters(cfg), index=index)


def init_latent_cache(cfg, num_slots: int, max_len: int, num_pages: int,
                      page_size: int, dtype=jnp.bfloat16) -> LatentKVCache:
    """The one pool of a model with latent attention (what ``cfg`` states:
    ``serving.decode``, "the seam"), rows of ``kv_row_width``, block tables
    parked on ``SCRATCH_PAGE``; zeroed counters where the model keeps any."""
    _check_pool_sizes(num_slots, max_len, num_pages, page_size)
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("no int8 latent pool")
    return LatentKVCache(
        k=jnp.zeros((cfg.kv_layers, num_pages, page_size, cfg.kv_row_width),
                    dtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        block_tables=_parked_tables(num_slots, max_len, page_size),
        counters=_zero_counters(cfg))


def init_window_cache(cfg, num_slots: int, max_len: int, num_pages: int,
                      page_size: int, dtype=jnp.bfloat16) -> WindowKVCache:
    """The two pools of a model with window layers (what ``cfg`` states:
    ``serving.decode``, "the seam"): the full layers' pool as
    :func:`init_latent_cache` makes one, with ``v``, and the window layers'
    ``RESERVED_PAGES + num_slots * ring_pages(cfg.window, page_size)`` pages,
    zeroed; zeroed counters where the model keeps any."""
    _check_pool_sizes(num_slots, max_len, num_pages, page_size)
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("no int8 pool beside a window pool")
    full = (cfg.kv_layers, num_pages, page_size, cfg.kv_row_width)
    ring = (cfg.window_layers, RESERVED_PAGES
            + num_slots * ring_pages(cfg.window, page_size), page_size,
            cfg.kv_row_width)
    return WindowKVCache(
        k=jnp.zeros(full, dtype), v=jnp.zeros(full, dtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        block_tables=_parked_tables(num_slots, max_len, page_size),
        wk=jnp.zeros(ring, dtype), wv=jnp.zeros(ring, dtype),
        counters=_zero_counters(cfg))


#: What the cores of a model that keeps no per-slot state read (``cfg.pools``:
#: ``serving.decode``, "the seam"): the cache that holds it, and the words a
#: refusal names it by. A further family of pools is one row here.
MODEL_POOLS = {
    "latent": (init_latent_cache, "a latent pool"),
    "window": (init_window_cache, "a full pool and a window pool"),
}


def audit_block_tables(block_tables, slot_pages) -> bool:
    """Cross-check the DEVICE block tables against the HOST allocator's
    per-slot page lists: row ``i`` must map exactly ``slot_pages[i]``
    followed by a NULL/SCRATCH-parked tail. This is the device half of
    the pool invariant audit (``PagePool.check_invariants`` covers the
    host half); a divergence means a ``prepare_decode``/``free_slot``
    path updated one side and not the other. Raises
    :class:`~apex_tpu.serving.health.PoolInvariantError`."""
    import numpy as np

    from apex_tpu.serving.health import PoolInvariantError

    bt = np.asarray(block_tables)
    if bt.shape[0] != len(slot_pages):
        raise PoolInvariantError(
            f"block table has {bt.shape[0]} rows but the host tracks "
            f"{len(slot_pages)} slots")
    for i, pages in enumerate(slot_pages):
        if len(pages) > bt.shape[1]:
            raise PoolInvariantError(
                f"slot {i}: host maps {len(pages)} pages but the table "
                f"row holds {bt.shape[1]}")
        mapped = bt[i, :len(pages)].tolist()
        if mapped != list(pages):
            raise PoolInvariantError(
                f"slot {i}: device row maps {mapped}, host allocator "
                f"says {list(pages)}")
        tail = bt[i, len(pages):]
        stray = tail[(tail != NULL_PAGE) & (tail != SCRATCH_PAGE)]
        if stray.size:
            raise PoolInvariantError(
                f"slot {i}: unmapped tail holds live page ids "
                f"{sorted(set(stray.tolist()))} (must be NULL/SCRATCH)")
    return True


def paged_cache_partition_specs(rules=None,
                                quantized: bool = False) -> PagedKVCache:
    """TP layout, derived from the partition-rule table
    (``partition.paged_kv_cache_rules`` by default, or any table covering
    the cache's paths), so serving stays consistent with whatever table
    shards the model — APX702 checks the head axis against the qkv
    weights' ``tp`` axis: the pool's
    last axis (whole heads side by side) shards over ``model``; lengths
    AND block tables are replicated — every rank walks the same
    logical-to-physical mapping over its local heads. With
    ``quantized`` the template grows the ``k_scale``/``v_scale``
    leaves, matched against ``kv_cache_quant_rules()`` (head axis —
    axis 2 of the 3-d scales — sharded over ``model`` like the pool's)."""
    from apex_tpu.partition import (
        match_partition_rules, paged_kv_cache_rules,
    )

    if rules is None:
        if quantized:
            from apex_tpu.partition import kv_cache_quant_rules

            rules = kv_cache_quant_rules()
        else:
            rules = paged_kv_cache_rules()
    template = PagedKVCache(
        k=jax.ShapeDtypeStruct((1,) * 4, "bfloat16"),
        v=jax.ShapeDtypeStruct((1,) * 4, "bfloat16"),
        lengths=jax.ShapeDtypeStruct((1,), "int32"),
        block_tables=jax.ShapeDtypeStruct((1, 1), "int32"))
    if quantized:
        template = template._replace(
            k_scale=jax.ShapeDtypeStruct((1, 1, 1), "float32"),
            v_scale=jax.ShapeDtypeStruct((1, 1, 1), "float32"))
    return match_partition_rules(rules, template)
