"""Prefill + single-token decode steps over the paged KV cache.

Two execution paths from one body (the ``models/gpt.py`` discipline):
``make_paged_prefill_fn``/``make_paged_decode_fn`` are plain-jnp on full
params (the golden single-chip path);
``make_tp_paged_prefill_fn``/``make_tp_paged_decode_fn`` run the same
body inside ``parallel_state.shard_map`` with the Megatron TP layers —
heads (and the pool's head axis) shard over the ``model`` mesh axis, and
logits leave through the existing ``_tied_lm_logits`` vocab-sharded head
followed by a rank-order gather, so every rank returns the full
``(b, V)`` row.

Contracts:

- **prefill** runs the full forward ONCE over a (bucket-padded) prompt
  for one slot, writes that slot's K/V pages (+ the slot length and its
  block-table row), and
  returns the logits at the LAST REAL token — the first sampling input.
  The pad tail is masked out of attention (`key_mask`) and zeroed
  before entering the cache, so pad K/V can never be attended to, now
  or after later in-place writes.
- **decode** advances every slot one token: writes the new K/V row at
  ``pos = lengths`` and attends with an ``s <= pos`` mask. Its logits
  must match a full-sequence forward at the same positions to fp32
  tolerance (the headline serving contract; see
  ``tests/L0/run_serving``). Over the paged bf16/f32 pool the attention
  is the Pallas kernel ``apex_paged_decode_fwd``, which reads the
  mapped pages of the whole pool in place and takes the new row as an
  operand; the layers' rows are written by one scatter after the layer
  scan (:func:`_paged_decode_core`).
- **verify** (speculative decoding) advances every slot over k+1
  candidate positions at once — the last committed token plus k
  drafted candidates — returning exact per-position logits
  ``(B, k+1, V)``. K/V rows for ALL candidates are written before
  attending (per-query ``s <= pos + j`` masks keep causality exact);
  slot lengths are NOT advanced in-step — the host commits the
  accepted prefix afterwards (``PagedDecodeEngine.commit``), so a
  rejected candidate's row is simply never admitted by any later mask
  before the next step re-writes it. That is the whole rollback
  contract, and it is pinned by bit-identity tests.
- **chunk prefill** runs the prompt forward INCREMENTALLY: one chunk of
  ``chunk_tokens`` positions per call, write-then-attend against the
  live cache at absolute positions (the verify mechanics applied to
  prefill, per Sarathi-Serve). Each call writes the chunk's K/V rows
  and advances the slot length to the chunk's end; the last call's
  logits row (at the last REAL token — the final chunk is the only
  padded one) is the first sampling input. One jitted, donated
  executable per (chunk bucket, cache shape) — every chunk pads to the
  same ``chunk_tokens`` bucket. Chunks are whole pages, so the write is
  the same page-granular scatter as monolithic prefill; the attend
  gathers through a ``gather_row`` passed
  separately from the ``store_row`` the core installs, because the
  scheduler keeps the stored row parked on ``SCRATCH_PAGE`` until the
  final chunk (co-tenant decode/verify steps write a row for EVERY
  slot each tick — mid-prefill those garbage writes must land on
  scratch, never on a prefix-shared page). Refused for the int8 pool:
  chunk queries would re-read earlier chunks' k/v dequantized while
  monolithic prefill attends them fresh in bf16, so first-token logits
  could drift from the synchronous path beyond the bit-identity
  contract.
- **tree verify** generalizes verify to a draft TREE per slot: node j
  (topological order, node 0 = the pending token) writes its K/V at
  physical row ``pos + j`` but attends at position ``pos + depth[j]``
  under an ancestor-matrix mask, so logits row j is the exact
  teacher-forced distribution over j's root-to-node token path — one
  forward scores every branch (SpecInfer-style). Lengths are NOT
  advanced; the host walks the accepted path
  (``sampling.tree_speculative_accept``) and advances only the
  row-CONTIGUOUS committed prefix, re-sending any committed token
  whose row landed off the leftmost chain (the forced-prefix rule) —
  the same write-then-attend rollback, no compaction pass.
- both jitted steps DONATE the cache: the update lowers to an in-place
  buffer write instead of a fresh copy of the pool per token.
  APX512 (trace tier) verifies the donation survives into the jaxpr.
"""

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.gpt import (
    GPTConfig, GPTModel, _block_chunk_prefill_paged, _block_decode_paged,
    _block_decode_paged_q8, _block_prefill, _block_tree_verify_paged,
    _block_verify_paged, _block_verify_paged_q8, _ln, _pages_to_tiles,
    _rope_or_none, _tied_lm_logits, _tiles_to_pages,
)
from apex_tpu.serving.cache import (
    SCRATCH_PAGE, PagedKVCache, paged_cache_partition_specs, ring_page,
)
from apex_tpu.utils.profiler import region


# ---------------------------------------------------------------------------
# shared cores (parameterized by the linear/embedding/logits impls)
# ---------------------------------------------------------------------------

def _final_ln(params, cfg: GPTConfig, x):
    with region("head"):
        return _ln(params["final_ln"], x, cfg.layer_norm_eps)


def _self_rewrite(x):
    """Rewrite row 0 of ``x`` with itself. Numerically a no-op, but it
    gives XLA an update op to land the donated buffer in — an output
    that IS an invar gives the donation nothing to alias, and APX512
    flags the dropped pair (the paged decode core's block-table idiom,
    shared by the verify steps whose lengths pass through unchanged)."""
    first = lax.dynamic_slice(x, (0,) * x.ndim, (1,) + x.shape[1:])
    return lax.dynamic_update_slice(x, first, (0,) * x.ndim)


# ---------------------------------------------------------------------------
# paged cores — same forwards, block-table indirection into the pool
# ---------------------------------------------------------------------------

def _paged_prefill_core(params, cfg: GPTConfig, cache: PagedKVCache, ids,
                        mask, slot, write_pages, table_row, *, embed_fn,
                        dense_fns, logits_fn):
    """Bucketed prefill into the page pool: ids (1, s_bucket) already
    bucket-padded; mask (s_bucket,) int32 with 1 = real token
    (``utils.seqlen.pad_to_bucket``'s convention); slot: scalar int32. The
    forward is flash attention over the padded prompt; the stacked per-layer
    k/v tiles, their pad tail zeroed (the cache's contents are then
    independent of pad ids outright), are cut into whole pages and scattered
    to ``write_pages`` (one physical page per bucket page — the host
    redirects prefix-shared pages and the pad tail to ``SCRATCH_PAGE``, so
    shared pages are never rewritten), and ``table_row`` ((max_pages,)
    int32, NULL-padded) becomes the slot's block-table row. One compiled
    executable per bucket, independent of how many pages are shared. Returns
    (cache', logits (1, V)) at the last real token."""
    if ids.ndim != 2 or ids.shape[0] != 1:
        raise ValueError(f"prefill takes one slot's (1, s) ids, got "
                         f"{ids.shape}")
    s = ids.shape[1]
    page_size = cache.k.shape[2]
    if s % page_size:
        raise ValueError(f"prompt bucket {s} is not a multiple of "
                         f"page_size {page_size}")
    n_bucket_pages = s // page_size
    if write_pages.shape != (n_bucket_pages,):
        raise ValueError(f"write_pages {write_pages.shape} != one page "
                         f"per bucket page ({n_bucket_pages},)")
    if table_row.shape != (cache.block_tables.shape[1],):
        raise ValueError(f"table_row {table_row.shape} != block-table "
                         f"row ({cache.block_tables.shape[1]},)")
    x = embed_fn(params, ids)
    freqs = _rope_or_none(cfg, s)
    key_mask = mask[None, :]

    def body(x, lp):
        x, k, v = _block_prefill(lp, x, cfg, freqs, key_mask, *dense_fns)
        return x, (k, v)

    x, (k, v) = lax.scan(body, x, params["layers"])
    hidden = _final_ln(params, cfg, x)
    length = jnp.sum(mask).astype(jnp.int32)
    h_last = lax.dynamic_slice_in_dim(hidden, length - 1, 1, 1)[:, 0]
    logits = logits_fn(params, h_last)
    mz = mask.astype(k.dtype)[None, None, None, :, None]

    def pages(t):
        # (L, 1, nh, s, hd) -> pages (L, n_bucket_pages, page_size,
        # nh * hd), zero-padded tail included (scratch eats it)
        lyr, _, nh, _, hd = t.shape
        t = (t * mz)[:, 0].transpose(0, 2, 1, 3)
        return t.reshape(lyr, n_bucket_pages, page_size, nh * hd)

    @region("cache_write")
    def write():
        lengths = lax.dynamic_update_slice(cache.lengths, length[None],
                                           (slot,))
        block_tables = lax.dynamic_update_slice(
            cache.block_tables, table_row[None, :], (slot, 0))
        if cache.k_scale is not None:
            # int8 pool: quantize each freshly-written page per head (amax
            # over the page, zeroed pad rows quantize to exact 0) and
            # scatter tiles + scales together — 6 alias pairs
            from apex_tpu.quant.kernels import kv_quantize

            kq, ks = kv_quantize(_pages_to_tiles(pages(k), cfg.head_dim))
            vq, vs = kv_quantize(_pages_to_tiles(pages(v), cfg.head_dim))
            return PagedKVCache(
                k=cache.k.at[:, write_pages].set(_tiles_to_pages(kq)),
                v=cache.v.at[:, write_pages].set(_tiles_to_pages(vq)),
                lengths=lengths, block_tables=block_tables,
                k_scale=cache.k_scale.at[:, write_pages].set(ks),
                v_scale=cache.v_scale.at[:, write_pages].set(vs))
        return PagedKVCache(
            k=cache.k.at[:, write_pages].set(pages(k).astype(cache.k.dtype)),
            v=cache.v.at[:, write_pages].set(pages(v).astype(cache.v.dtype)),
            lengths=lengths, block_tables=block_tables)

    return write(), logits


def _paged_decode_core(params, cfg: GPTConfig, cache: PagedKVCache,
                       tokens, active, *, embed_fn, dense_fns,
                       logits_fn):
    """One token for every slot against the page pool: tokens (B,) int32 —
    each slot's previous token; active (B,) bool gates the length advance
    (freed slots stay parked). Returns (cache', logits (B, V) fp32). The
    host has already made every slot's write target exclusive (page-boundary
    allocation + copy-on-write happen in
    ``PagedDecodeEngine.prepare_decode`` BEFORE this runs). Over the
    bf16/f32 pool each layer's attention is the paged-attention kernel on
    the WHOLE pool and the layer's index
    (``models.gpt._paged_decode_attention``); the int8 pool keeps the
    per-layer write + gather, with the pool as xs/ys of the scan. Block
    tables are host-owned state riding the donated cache tuple; they come
    back numerically unchanged, but through a self-row rewrite rather than
    an invar passthrough — an output that IS the invar gives XLA nothing to
    land the donation in, and APX512 would flag the dropped alias pair."""
    pos = cache.lengths
    bt = cache.block_tables
    x = embed_fn(params, tokens[:, None], pos=pos)
    freqs = _rope_or_none(cfg, bt.shape[1] * cache.k.shape[2])

    if cache.k_scale is not None:
        def body(x, layer_slice):
            lp, kp, vp, ks, vs = layer_slice
            x, kp, vp, ks, vs = _block_decode_paged_q8(
                lp, x, kp, vp, ks, vs, bt, pos, cfg, freqs, *dense_fns)
            return x, (kp, vp, ks, vs)

        x, (k, v, ks, vs) = lax.scan(
            body, x, (params["layers"], cache.k, cache.v,
                      cache.k_scale, cache.v_scale))
        hidden = _final_ln(params, cfg, x)
        logits = logits_fn(params, hidden[:, 0])
        bt = _self_rewrite(bt)
        return PagedKVCache(k, v, jnp.where(active, pos + 1, pos), bt,
                            ks, vs), logits

    # the pool is closed over and only read: it is no xs/ys of the scan
    # (a scan would copy each layer's slice out and back), and the layers'
    # new rows go into the donated pool in ONE in-place scatter after it
    def body(x, layer_slice):
        lp, layer = layer_slice
        x, k_row, v_row = _block_decode_paged(
            lp, x, cache.k, cache.v, layer, bt, pos, cfg, freqs,
            *dense_fns)
        return x, (k_row, v_row)

    layers = cache.k.shape[0]
    x, (k_rows, v_rows) = lax.scan(
        body, x, (params["layers"], jnp.arange(layers, dtype=jnp.int32)))
    hidden = _final_ln(params, cfg, x)
    logits = logits_fn(params, hidden[:, 0])
    k, v = _write_new_rows(cache, k_rows, v_rows)
    bt = _self_rewrite(bt)
    return PagedKVCache(k, v, jnp.where(active, pos + 1, pos), bt), logits


def _write_new_rows(cache, k_rows, v_rows):
    """Every pool layer's new row of every slot (``k_rows`` / ``v_rows``
    ``(L, slots, width)``) written at ``cache.lengths`` through the block
    tables: the pool as a list of rows (a view: its layout is row-major)
    takes the layers * slots rows in ONE in-place row scatter. Inactive
    slots write to the page their NULL/scratch row names. Returns the
    pool's ``(k, v)``; a cache with one pool (``cache.v`` is ``None``: a
    latent pool) takes ``v_rows`` ``None`` and gives ``v`` ``None``."""
    pos, bt = cache.lengths, cache.block_tables
    with region("cache_write"):
        logical = jnp.clip(pos // cache.k.shape[2], 0, bt.shape[1] - 1)
        pages = jnp.take_along_axis(bt, logical[:, None], 1)[:, 0]
        at = _row_numbers(cache.k, pages, pos)
        return _put_rows(cache.k, k_rows, at), \
            None if cache.v is None else _put_rows(cache.v, v_rows, at)


def _write_index_keys(cache, keys, tail, active):
    """The indexer's cache after a decode step (``cfg.indexed``): ``keys``
    ``(L, slots, width)`` is the pooled key of the group each slot's new
    token CLOSES, which goes to that group's place in the page the block
    table names (one scatter; a slot that closes no group, or is not
    ``active``, writes to the scratch page, which no slot reads); ``tail`` the
    slots' rings as the step left them."""
    pos, bt = cache.lengths, cache.block_tables
    rows = cache.index["rows"]
    layers, _, keys_a_page, _ = rows.shape
    page_size = cache.k.shape[2]
    pool = page_size // keys_a_page
    with region("cache_write"):
        logical = jnp.clip(pos // page_size, 0, bt.shape[1] - 1)
        pages = jnp.take_along_axis(bt, logical[:, None], 1)[:, 0]
        closes = active & (pos % pool == pool - 1)
        pages = jnp.where(closes, pages, SCRATCH_PAGE)
        at = (pos % page_size) // pool
        rows = rows.at[jnp.arange(layers)[:, None], pages[None, :],
                       at[None, :]].set(keys.astype(rows.dtype))
    return {"rows": rows, "tail": tail}


def _row_numbers(pool, pages, pos):
    """Where row ``pos`` of every slot's page ``pages`` (slots,) stands in
    every layer of ``pool`` seen as a list of rows: (layers * slots,)."""
    layers, num_pages, page_size, _ = pool.shape
    return ((jnp.arange(layers)[:, None] * num_pages + pages[None, :])
            * page_size + pos[None, :] % page_size).reshape(-1)


def _put_rows(pool, rows, at):
    width = pool.shape[-1]
    flat = pool.reshape(-1, width).at[at].set(rows.reshape(-1, width))
    return flat.reshape(pool.shape)


def _write_window_rows(cache, k_rows, v_rows):
    """:func:`_write_new_rows` for the window layers' pool
    (``serving.cache.WindowKVCache``): every slot's new row goes to its own
    place in its cycle, ``ring_page(slot, pos // page_size)``, whether the
    slot is active or not (the row that stood there is ``ring * page_size``
    positions old, outside any window, and a slot's ring is its own)."""
    pos = cache.lengths
    with region("cache_write"):
        pages = ring_page(jnp.arange(pos.shape[0], dtype=jnp.int32),
                          pos // cache.wk.shape[2], cache.ring)
        at = _row_numbers(cache.wk, pages, pos)
        return _put_rows(cache.wk, k_rows, at), \
            _put_rows(cache.wv, v_rows, at)


def _paged_verify_core(params, cfg: GPTConfig, cache: PagedKVCache,
                       tokens, *, embed_fn, dense_fns, logits_fn):
    """Speculative *verify*: tokens (B, k1) int32 — column 0 is each slot's
    last committed (pending) token, columns 1..k its drafted candidates; row
    j attends at absolute position ``lengths + j``
    (``models.gpt._paged_verify_attention`` has the contract). Returns
    (cache', logits (B, k1, V) fp32). Lengths are NOT advanced — acceptance
    is a host decision (the accepted count is only known after sampling),
    committed via a tiny host-side ``_replace`` on the returned cache. The
    caller guarantees ``lengths + k1 <= max_len`` for every slot (the
    scheduler's headroom guard). The host has already made every one of the
    k1 write targets exclusive (``prepare_decode(..., n_new=k1)`` runs
    boundary allocation + copy-on-write for every page the candidate
    positions touch), so the unrolled scatters never land on a shared page.
    Lengths and block tables ride the donated tuple through the self-row
    rewrite."""
    pos = cache.lengths
    bt = cache.block_tables
    x = embed_fn(params, tokens, pos=pos)
    freqs = _rope_or_none(cfg, bt.shape[1] * cache.k.shape[2])

    if cache.k_scale is not None:
        def body(x, layer_slice):
            lp, kp, vp, ks, vs = layer_slice
            x, kp, vp, ks, vs = _block_verify_paged_q8(
                lp, x, kp, vp, ks, vs, bt, pos, cfg, freqs, *dense_fns)
            return x, (kp, vp, ks, vs)

        x, (k, v, ks, vs) = lax.scan(
            body, x, (params["layers"], cache.k, cache.v,
                      cache.k_scale, cache.v_scale))
        hidden = _final_ln(params, cfg, x)
        logits = logits_fn(params, hidden)
        return PagedKVCache(k, v, _self_rewrite(pos), _self_rewrite(bt),
                            ks, vs), logits

    def body(x, layer_slice):
        lp, kp, vp = layer_slice
        x, kp, vp = _block_verify_paged(lp, x, kp, vp, bt, pos, cfg,
                                        freqs, *dense_fns)
        return x, (kp, vp)

    x, (k, v) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    hidden = _final_ln(params, cfg, x)
    logits = logits_fn(params, hidden)
    return PagedKVCache(k, v, _self_rewrite(pos), _self_rewrite(bt)), \
        logits


def _paged_tree_verify_core(params, cfg: GPTConfig, cache: PagedKVCache,
                            tokens, depth, anc, *, embed_fn, dense_fns,
                            logits_fn):
    """Tree verify: tokens (B, k1) int32 in topological order (column 0 =
    each slot's pending token, the root every branch hangs off); depth (B,
    k1) int32 node depths (depth[0] = 0); anc (B, k1, k1) bool
    ancestor-or-self matrix (anc[i, j]: node i on j's root path, anc[j, j] =
    True; a linear chain is anc[i, j] = i <= j with depth[j] = j, which
    reduces this exactly to :func:`_paged_verify_core`, whose
    ``prepare_decode(..., n_new=k1)`` precondition it shares). Logits row j
    is the teacher-forced distribution following j's root-to-node path.
    Lengths are NOT advanced — the host walks the accepted path and commits
    the contiguous row prefix. Refused for the int8 pool: committing a
    non-leftmost branch would re-round quantized history at branch-dependent
    scales, breaking the kv8 rejected-tail bit-identity contract — the
    engine pins linear spec there."""
    if cache.k_scale is not None:
        raise ValueError("tree verify is not offered over the int8 page "
                         "pool (kv8 keeps linear speculation)")
    pos = cache.lengths
    bt = cache.block_tables
    x = embed_fn(params, tokens, pos=pos[:, None] + depth)
    freqs = _rope_or_none(cfg, bt.shape[1] * cache.k.shape[2])

    def body(x, layer_slice):
        lp, kp, vp = layer_slice
        x, kp, vp = _block_tree_verify_paged(
            lp, x, kp, vp, bt, pos, depth, anc, cfg, freqs, *dense_fns)
        return x, (kp, vp)

    x, (k, v) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    hidden = _final_ln(params, cfg, x)
    logits = logits_fn(params, hidden)
    return PagedKVCache(k, v, _self_rewrite(pos), _self_rewrite(bt)), \
        logits


def _paged_chunk_prefill_core(params, cfg: GPTConfig,
                              cache: PagedKVCache, ids, mask, slot, pos,
                              write_pages, gather_row, store_row, *,
                              embed_fn, dense_fns, logits_fn):
    """Chunked prefill: ids (1, chunk_tokens) — one chunk of one slot's
    prompt, already padded to the chunk bucket; mask (chunk_tokens,) int32
    with 1 = real token (all-ones except the final chunk); slot and pos
    scalar int32 (slot, absolute start position). Runs the verify-style
    write-then-attend forward over the chunk, advances the slot length to
    ``pos + sum(mask)``, and returns (cache', logits (1, V)) at the chunk's
    last REAL token — only the final chunk's row is a sampling input. Chunks
    are whole pages, so the write is the monolithic paged prefill's
    page-granular scatter to ``write_pages`` (prefix-shared pages redirected
    to ``SCRATCH_PAGE`` by the host); the attend gathers through
    ``gather_row`` (the slot's real NULL-padded row) while ``store_row``
    becomes the slot's block-table row — the scheduler passes an all-scratch
    parked row until the final chunk, so co-tenant decode/verify writes
    mid-prefill land on scratch (see the module docstring). Refused for the
    int8 pool: chunk queries would re-read earlier chunks dequantized where
    monolithic prefill attends fresh bf16 values, drifting first-token
    logits off the synchronous path."""
    if cache.k_scale is not None:
        raise ValueError("chunked prefill is not offered over the int8 "
                         "page pool (kv8 keeps monolithic prefill)")
    if ids.ndim != 2 or ids.shape[0] != 1:
        raise ValueError(f"chunk prefill takes one slot's (1, sc) ids, "
                         f"got {ids.shape}")
    sc = ids.shape[1]
    page_size = cache.k.shape[2]
    if sc % page_size:
        raise ValueError(f"chunk bucket {sc} is not a multiple of "
                         f"page_size {page_size}")
    n_chunk_pages = sc // page_size
    if write_pages.shape != (n_chunk_pages,):
        raise ValueError(f"write_pages {write_pages.shape} != one page "
                         f"per chunk page ({n_chunk_pages},)")
    max_pages = cache.block_tables.shape[1]
    for name, row in (("gather_row", gather_row),
                      ("store_row", store_row)):
        if row.shape != (max_pages,):
            raise ValueError(f"{name} {row.shape} != block-table row "
                             f"({max_pages},)")
    x = embed_fn(params, ids, pos=pos[None])
    freqs = _rope_or_none(cfg, max_pages * page_size)
    key_mask = mask[None, :]

    def body(x, layer_slice):
        lp, kp, vp = layer_slice
        x, kp, vp = _block_chunk_prefill_paged(
            lp, x, kp, vp, write_pages, gather_row, pos, cfg, freqs,
            key_mask, *dense_fns)
        return x, (kp, vp)

    x, (k, v) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    hidden = _final_ln(params, cfg, x)
    n_real = jnp.sum(mask).astype(jnp.int32)
    h_last = lax.dynamic_slice_in_dim(hidden, n_real - 1, 1, 1)[:, 0]
    logits = logits_fn(params, h_last)
    lengths = lax.dynamic_update_slice(cache.lengths,
                                       (pos + n_real)[None], (slot,))
    block_tables = lax.dynamic_update_slice(
        cache.block_tables, store_row[None, :], (slot, 0))
    return PagedKVCache(k, v, lengths, block_tables), logits


# ---------------------------------------------------------------------------
# unsharded (single-chip) builders
# ---------------------------------------------------------------------------

def _pos_idx(pos, s):
    """(b, s) absolute position indices from either a (b,) start (the
    decode/verify convention: consecutive from ``pos``) or an explicit
    (b, s) array (tree verify: ``pos + depth``, not consecutive)."""
    if pos.ndim == 2:
        return pos
    return pos[:, None] + jnp.arange(s)[None, :]


def _add_positions(cfg: GPTConfig, params, x, ids, pos):
    """``x`` plus the learned position rows (nothing under RoPE): the
    leading ``s`` rows for a prompt (``pos`` None), else slot b's s tokens
    at absolute positions pos[b], pos[b]+1, ... (s = 1 for decode; tree
    verify passes explicit (b, s) positions)."""
    if cfg.use_rope:
        return x
    ptab = params["embedding"]["position"]["embedding"]
    if pos is None:
        return x + ptab[: ids.shape[1]].astype(x.dtype)[None]
    idx = _pos_idx(pos, ids.shape[1])
    return x + jnp.take(ptab, idx, axis=0).astype(x.dtype)


def _dense(p, x):
    return jnp.dot(x, p["kernel"].astype(x.dtype)) \
        + p["bias"].astype(x.dtype)


def _embed_unsharded(cfg: GPTConfig, compute_dtype):
    def embed(params, ids, pos=None):
        table = params["embedding"]["word"]["embedding"]
        if compute_dtype is not None:
            table = table.astype(compute_dtype)
        return _add_positions(cfg, params, jnp.take(table, ids, axis=0),
                              ids, pos)
    return embed


def _logits_unsharded(params, hidden):
    table = params["embedding"]["word"]["embedding"]
    return jnp.dot(hidden, table.astype(hidden.dtype).T).astype(
        jnp.float32)


def _dense_w8(p, x):
    """Weight-only int8 linear: the dequant-fused Pallas matmul against
    the layer's int8 kernel + per-output-channel fp32 scale."""
    from apex_tpu.quant.kernels import w8_matmul

    return w8_matmul(x, p["kernel"], p["scale"], p["bias"],
                     out_dtype=x.dtype)


def _embed_w8(cfg: GPTConfig, compute_dtype):
    """Embedding lookup from the int8 word table: take rows, dequant
    each against its per-row (per-vocab-entry) scale — the gather is
    O(b·s·h), so the dequant stays plain jnp."""

    def embed(params, ids, pos=None):
        word = params["embedding"]["word"]
        x = jnp.take(word["embedding"], ids, axis=0).astype(jnp.float32) \
            * jnp.take(word["scale"], ids, axis=0)[..., None]
        x = x.astype(jnp.float32 if compute_dtype is None
                     else compute_dtype)
        return _add_positions(cfg, params, x, ids, pos)

    return embed


def _logits_w8(params, hidden):
    """Tied logits head against the output-channel-major int8 word
    table — ``w8_matmul_nk`` contracts without transposing it."""
    from apex_tpu.quant.kernels import w8_matmul_nk

    word = params["embedding"]["word"]
    return w8_matmul_nk(hidden, word["embedding"], word["scale"])


def _unsharded_fns(cfg: GPTConfig, compute_dtype, quantized):
    if quantized:
        return (region("embed")(_embed_w8(cfg, compute_dtype)),
                (_dense_w8,) * 4, region("head")(_logits_w8))
    return (region("embed")(_embed_unsharded(cfg, compute_dtype)),
            (_dense,) * 4, region("head")(_logits_unsharded))


def make_paged_prefill_fn(cfg: GPTConfig, compute_dtype=None,
                          quantized=False):
    """jit(paged prefill), cache DONATED (4 alias pairs: pool k/v,
    lengths, block tables; 6 with an int8 cache's scales). Compiles per
    bucket — call through a bucketing layer (the scheduler does) so
    recompiles are per bucket, never per request. ``quantized`` expects
    the weight-only int8 tree of ``apex_tpu.quant.quantize_params``
    (every builder here does)."""
    embed, dense_fns, logits_fn = _unsharded_fns(cfg, compute_dtype,
                                                 quantized)

    def prefill(params, cache, ids, mask, slot, write_pages, table_row):
        return _paged_prefill_core(params, cfg, cache, ids, mask, slot,
                                   write_pages, table_row,
                                   embed_fn=embed,
                                   dense_fns=dense_fns,
                                   logits_fn=logits_fn)

    return jax.jit(prefill, donate_argnums=1)


def make_paged_decode_fn(cfg: GPTConfig, compute_dtype=None,
                         quantized=False):
    """jit(paged decode), cache DONATED; one executable per pool
    shape."""
    embed, dense_fns, logits_fn = _unsharded_fns(cfg, compute_dtype,
                                                 quantized)

    def decode(params, cache, tokens, active):
        return _paged_decode_core(params, cfg, cache, tokens, active,
                                  embed_fn=embed,
                                  dense_fns=dense_fns,
                                  logits_fn=logits_fn)

    return jax.jit(decode, donate_argnums=1)


def make_paged_verify_fn(cfg: GPTConfig, compute_dtype=None,
                         quantized=False):
    """jit(paged speculative verify), cache DONATED (4 alias pairs; 6
    with an int8 cache's scales); one executable per (pool shape, k1) —
    the scheduler runs a single k1 = spec_k + 1 bucket (shorter drafts
    pad with token 0; the host bounds acceptance by the true draft
    length), so this compiles once."""
    embed, dense_fns, logits_fn = _unsharded_fns(cfg, compute_dtype,
                                                 quantized)

    def verify(params, cache, tokens):
        return _paged_verify_core(params, cfg, cache, tokens,
                                  embed_fn=embed,
                                  dense_fns=dense_fns,
                                  logits_fn=logits_fn)

    return jax.jit(verify, donate_argnums=1)


def make_paged_tree_verify_fn(cfg: GPTConfig, compute_dtype=None,
                              quantized=False):
    """jit(paged tree verify), cache DONATED (4 alias pairs); one
    executable per (pool shape, k1). Takes (params, cache, tokens
    (B, k1), depth (B, k1) int32, anc (B, k1, k1) bool) — see
    :func:`_paged_tree_verify_core` for the node contract. Int8 pools
    are refused there."""
    embed, dense_fns, logits_fn = _unsharded_fns(cfg, compute_dtype,
                                                 quantized)

    def verify(params, cache, tokens, depth, anc):
        return _paged_tree_verify_core(params, cfg, cache, tokens,
                                       depth, anc, embed_fn=embed,
                                       dense_fns=dense_fns,
                                       logits_fn=logits_fn)

    return jax.jit(verify, donate_argnums=1)


def make_paged_chunk_prefill_fn(cfg: GPTConfig, compute_dtype=None,
                                quantized=False):
    """jit(paged chunked prefill), cache DONATED (4 alias pairs: pool
    k/v, lengths, block tables). One compiled executable per (chunk
    bucket, pool shape) — the scheduler pads every chunk to the same
    ``chunk_tokens`` bucket, so this compiles once per engine. Int8
    pools are refused — see :func:`_paged_chunk_prefill_core`."""
    embed, dense_fns, logits_fn = _unsharded_fns(cfg, compute_dtype,
                                                 quantized)

    def chunk_prefill(params, cache, ids, mask, slot, pos, write_pages,
                      gather_row, store_row):
        return _paged_chunk_prefill_core(
            params, cfg, cache, ids, mask, slot, pos, write_pages,
            gather_row, store_row, embed_fn=embed, dense_fns=dense_fns,
            logits_fn=logits_fn)

    return jax.jit(chunk_prefill, donate_argnums=1)


# ---------------------------------------------------------------------------
# a model that brings its own cores: the same two programs, for every family
# ---------------------------------------------------------------------------
#
# The seam (ROADMAP D11). A config with a ``decode_core`` (:func:`model_cores`)
# states, and the engine takes here and nowhere else:
#   ``kv_layers``, ``kv_row_width``        the page pool's leading axis and
#                                          row
#   ``counter_shapes()`` (optional)        int32 counters kept in the cache
#   ``prefill_core(params, ids, mask, kv_dtype)`` -> (x (s, hidden) or the
#       last real token's row alone (1, hidden), states,
#       tails, k, v (kv_layers, s, kv_row_width))
#   ``decode_core(params, cache, tokens, active)`` -> (x (slots, hidden),
#       state', conv', counters', k_rows, v_rows (kv_layers, slots, width))
#   ``logits_of(params, x)``               final norm and head
# and TWO independent facts about its cache (both may hold: recurrent state
# beside ONE latent pool, ``serving.cache.HybridKVCache`` with ``v`` ``None``;
# every refusal of either fact then applies):
#   ``recurrent``   it keeps per-slot state beside the pool, whole at every
#                   moment (``state_shapes(slots)``: recurrent state and
#                   convolution tail, float32; ``state_bytes_per_slot()``:
#                   what a prefill writes besides pages). The pool then
#                   counts the attention layers only, and whatever would need
#                   a snapshot of the state is refused
#                   (``scheduler._refuse_for_recurrent``).
#   ``latent``      its pool is ONE pool of rows that are key and value at
#                   once (``serving.cache.LatentKVCache``): both cores give
#                   ``v`` ``None``, and with no state ``states`` / ``tails`` /
#                   ``state'`` / ``conv'`` ``None`` too. A latent page is
#                   shared, copied, preempted and shipped as any page; what
#                   needs a program the model does not bring is refused
#                   (``scheduler._refuse_without_a_core``).
#   ``indexed``     (beside ``recurrent`` and ``latent``) its attention PICKS
#                   the rows it reads by an indexer whose pooled keys are
#                   cached too (``index_shapes(slots, pages, page_size)``:
#                   ``HybridKVCache.index``, addressed by the pool's own
#                   block table; ``index_bytes_per_page()``). Both cores give
#                   LAST an ``index`` pair: a prefill the prompt's keys page
#                   by page and the slot's tail, a decode step the key each
#                   slot's token closes and the tails. Nothing that moves a
#                   page without its keys is offered over such a pool.
#   ``pools``       (a model with no state) a key of
#                   ``serving.cache.MODEL_POOLS``: which cache holds what its
#                   cores read, and the words a refusal names it by.
#   ``window``      (positions; 0 or absent: none) some of its attention
#                   layers see the last ``window`` positions only. The pool
#                   then counts the FULL layers (``kv_layers``), and
#                   ``window_layers`` more keep ``ring_pages`` pages a slot in
#                   a second pool whose table is a static cycle
#                   (``serving.cache.WindowKVCache``). Both cores give the
#                   window layers' rows ``wk``, ``wv`` ((window_layers, s or
#                   slots, width)) after ``v``. A prefill writes the pages
#                   that hold the prompt's last ``ring_pages`` logical pages
#                   into the slot's cycle; the host never sees that pool.
# ``models.hybrid`` and ``models.nemotron_h`` (recurrent),
# ``models.deepseek`` (latent), ``models.exaone_moe`` (window) and
# ``models.bailing_hybrid`` (recurrent AND latent) and ``models.glm_next``
# (recurrent, latent AND indexed) stand on it.

def model_cores(cfg) -> bool:
    """Does ``cfg`` bring its own prefill and decode cores (the seam)?"""
    return callable(getattr(cfg, "decode_core", None))


def _model_prefill_core(params, cfg, cache, ids, mask, slot, write_pages,
                        table_row):
    """:func:`_paged_prefill_core` for a model that brings its cores: the
    pool layers' rows go to ``write_pages`` exactly as there, and every
    recurrent layer's state and convolution tail (where the model has any),
    as the prompt's last real token left them, overwrite row ``slot`` of
    ``cache.state`` / ``cache.conv``: that write is the slot's only reset."""
    if ids.ndim != 2 or ids.shape[0] != 1:
        raise ValueError(f"prefill takes one slot's (1, s) ids, got "
                         f"{ids.shape}")
    s = ids.shape[1]
    page_size = cache.k.shape[2]
    if s % page_size:
        raise ValueError(f"prompt bucket {s} is not a multiple of "
                         f"page_size {page_size}")
    if write_pages.shape != (s // page_size,):
        raise ValueError(f"write_pages {write_pages.shape} != one page "
                         f"per bucket page ({s // page_size},)")
    x, states, tails, k, v, *windowed = cfg.prefill_core(
        params, ids[0], mask, cache.k.dtype)
    index = windowed.pop() if getattr(cfg, "indexed", False) else None
    length = jnp.sum(mask).astype(jnp.int32)
    # (a core may hand back the last real token's row alone)
    logits = cfg.logits_of(params, x if x.shape[0] == 1 and s > 1 else
                           lax.dynamic_slice_in_dim(x, length - 1, 1, 0))

    def pages(t):
        # (L_full, s, width) -> whole pages, the pad tail zeroed
        t = t * mask.astype(t.dtype)[None, :, None]
        return t.reshape(t.shape[0], -1, page_size, t.shape[-1])

    with region("cache_write"):
        new = {"k": cache.k.at[:, write_pages].set(pages(k))}
        if v is not None:
            new["v"] = cache.v.at[:, write_pages].set(pages(v))
        new["lengths"] = lax.dynamic_update_slice(
            cache.lengths, length[None], (slot,))
        new["block_tables"] = lax.dynamic_update_slice(
            cache.block_tables, table_row[None, :], (slot, 0))
        if states is not None:
            new["state"] = lax.dynamic_update_slice(
                cache.state, states[:, None], (0, slot, 0, 0, 0))
            new["conv"] = lax.dynamic_update_slice(
                cache.conv, tails[:, None], (0, slot, 0, 0))
        if index is not None:
            # the prompt's pooled keys (L, groups, width) page by page beside
            # the latents of the same pages; the keys of its last group, if
            # that is not whole, into the slot's tail
            keys, tail = index
            rows = cache.index["rows"]
            new["index"] = {
                "rows": rows.at[:, write_pages].set(keys.astype(
                    rows.dtype).reshape(rows.shape[0], -1, *rows.shape[2:])),
                "tail": lax.dynamic_update_slice(
                    cache.index["tail"], tail[:, None], (0, slot, 0, 0))}
        if windowed:
            # the prompt's last ``ring`` logical pages (fewer in a bucket
            # that has fewer), from the one that holds its last token back:
            # earlier ones would alias them in the cycle. Rows the window has
            # left behind, and the pad's zeros, ride along and are masked
            ring = min(cache.ring, s // page_size)
            first = jnp.maximum((length - 1) // page_size - (ring - 1), 0)
            to = ring_page(slot, first + jnp.arange(ring), cache.ring)
            for name, rows in zip(("wk", "wv"), windowed):
                tail = lax.dynamic_slice_in_dim(
                    pages(rows), first, ring, axis=1)
                new[name] = getattr(cache, name).at[:, to].set(tail)
        # a prefill counts nothing; the donated leaves still need a write
        new["counters"] = jax.tree.map(_self_rewrite, cache.counters)
    return cache._replace(**new), logits


def _model_decode_core(params, cfg, cache, tokens, active):
    """:func:`_paged_decode_core` for a model that brings its cores: the
    model attends over the pool in place and steps every recurrent layer's
    slice of the stacked state in place (``cfg.decode_core``), and the pool
    layers' new rows go into the pool in one scatter after it. Slots that
    are not ``active`` keep their recurrent state and their length."""
    x, state, conv, counters, k_rows, v_rows, *windowed = cfg.decode_core(
        params, cache, tokens, active)
    index = windowed.pop() if getattr(cfg, "indexed", False) else None
    logits = cfg.logits_of(params, x)
    k, v = _write_new_rows(cache, k_rows, v_rows)
    pos = cache.lengths
    new = {"k": k}
    if v is not None:
        new["v"] = v
    if index is not None:
        new["index"] = _write_index_keys(cache, *index, active)
    if windowed:
        new["wk"], new["wv"] = _write_window_rows(cache, *windowed)
    new["lengths"] = jnp.where(active, pos + 1, pos)
    new["block_tables"] = _self_rewrite(cache.block_tables)
    if state is not None:
        new["state"], new["conv"] = state, conv
    new["counters"] = counters
    return cache._replace(**new), logits


def make_model_prefill_fn(cfg):
    """jit(prefill) for a model that brings its cores, cache DONATED (with
    recurrent layers 6 alias pairs: pool k/v, lengths, block tables,
    recurrent state, convolution tails; with a latent pool 3: the pool,
    lengths, block tables; with both 5; and one per counter); one executable per bucket,
    and the same program name as every other prefill (``jit_prefill``)."""

    def prefill(params, cache, ids, mask, slot, write_pages, table_row):
        return _model_prefill_core(params, cfg, cache, ids, mask, slot,
                                   write_pages, table_row)

    return jax.jit(prefill, donate_argnums=1)


def make_model_decode_fn(cfg):
    """jit(decode) for a model that brings its cores, cache DONATED; one
    executable per cache shape (``jit_decode``)."""

    def decode(params, cache, tokens, active):
        return _model_decode_core(params, cfg, cache, tokens, active)

    return jax.jit(decode, donate_argnums=1)


def make_copy_page_fn():
    """jit(copy one physical page across all layers), cache DONATED —
    the device half of copy-on-write: the host picks ``src``/``dst``
    (``PagePool.needs_copy``), this clones the rows so the shared
    original is never mutated. Scalar page ids keep it one executable
    regardless of which pages diverge. An int8 cache clones the page's
    scale rows together with its tiles — the COW copy of a quantized
    page is bit-identical (same int8 rows, same scales)."""

    def copy(cache, src, dst):
        def clone(pool):
            with region("cache_write"):
                page = lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
                return lax.dynamic_update_slice_in_dim(pool, page, dst,
                                                       axis=1)

        new = cache._replace(k=clone(cache.k))
        if cache.v is not None:         # a latent pool is the one pool
            new = new._replace(v=clone(cache.v))
        if cache.k_scale is not None:
            new = new._replace(k_scale=clone(cache.k_scale),
                               v_scale=clone(cache.v_scale))
        return new

    return jax.jit(copy, donate_argnums=0)


# ---------------------------------------------------------------------------
# TP-sharded builders — heads (and the cache head axis) over ``model``
# ---------------------------------------------------------------------------

def _tp_fns(model: GPTModel):
    from apex_tpu.transformer.tensor_parallel import mappings

    cfg = model.cfg

    def embed(params, ids, pos=None):
        x = model.embed.apply(params["embedding"]["word"], ids)
        return _add_positions(cfg, params, x, ids, pos)

    def logits(params, hidden):
        local = _tied_lm_logits(hidden,
                                params["embedding"]["word"]["embedding"])
        # rank-order gather -> the full vocab row on every rank (the
        # serving head wants a samplable (b, V), unlike training's
        # vocab-parallel CE which keeps logits sharded)
        return mappings.gather_from_tensor_model_parallel_region(local)

    dense_fns = (model.qkv.apply, model.out.apply, model.fc1.apply,
                 model.fc2.apply)
    return region("embed")(embed), dense_fns, region("head")(logits)


def _tp_quant_fns(model: GPTModel):
    """Quantized twins of :func:`_tp_fns`: the same Megatron collective
    structure (Column: copy-in, no gather; Row: local matmul, reduce,
    then the replicated bias; vocab-parallel embed/logits) with the
    local matmuls swapped for the dequant-fused int8 kernels. The
    quantized tree shards exactly like bf16 (kernel paths unchanged,
    scales split with their output channel —
    ``apex_tpu.quant.quant_partition_specs``), so each rank's
    ``w8_matmul`` sees a coherent (local kernel, local scale) pair."""
    from jax import lax

    from apex_tpu.quant.kernels import w8_matmul, w8_matmul_nk
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.tensor_parallel import mappings

    cfg = model.cfg

    def embed(params, ids, pos=None):
        # VocabParallelEmbedding.apply over the int8 row shard: local
        # rows dequant per vocab entry, out-of-range rows zero, psum
        word = params["embedding"]["word"]
        table = word["embedding"]          # (V/p, h) int8 local shard
        per_rank = table.shape[0]
        start = lax.axis_index(ps.TENSOR_AXIS) * per_rank
        local = ids - start
        in_range = (local >= 0) & (local < per_rank)
        safe = jnp.where(in_range, local, 0)
        out = jnp.take(table, safe, axis=0).astype(jnp.float32) \
            * jnp.take(word["scale"], safe, axis=0)[..., None]
        out = jnp.where(in_range[..., None], out, 0.0)
        x = mappings.reduce_from_tensor_model_parallel_region(out)
        return _add_positions(cfg, params, x, ids, pos)

    def column(p, x):
        x = mappings.copy_to_tensor_model_parallel_region(x)
        return w8_matmul(x, p["kernel"], p["scale"], p["bias"],
                         out_dtype=x.dtype)

    def row(p, x):
        # bias AFTER the reduction, replicated — RowParallelLinear's
        # contract (adding it per-rank would add it p times)
        y = w8_matmul(x, p["kernel"], p["scale"], out_dtype=x.dtype)
        y = mappings.reduce_from_tensor_model_parallel_region(y)
        return y + p["bias"].astype(y.dtype)

    def logits(params, hidden):
        word = params["embedding"]["word"]
        hidden = mappings.copy_to_tensor_model_parallel_region(hidden)
        local = w8_matmul_nk(hidden, word["embedding"], word["scale"])
        return mappings.gather_from_tensor_model_parallel_region(local)

    return (region("embed")(embed), (column, row, column, row),
            region("head")(logits))


def _tp_build(model: GPTModel, quantized: bool):
    """(embed/dense/logits fns, param specs) for the TP builders."""
    if quantized:
        from apex_tpu.quant.params import quant_partition_specs

        return _tp_quant_fns(model), quant_partition_specs(model.cfg)
    return _tp_fns(model), model.partition_specs()


def _shard_jit(step, mesh, pspecs, cspecs, n_rest):
    """``jit(shard_map(step))`` over the global mesh, cache donated: params
    by ``pspecs``, the cache in and out by ``cspecs``, the ``n_rest`` host
    arguments behind it and the logits replicated."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer import parallel_state as ps

    sharded = ps.shard_map(
        step, mesh=mesh, in_specs=(pspecs, cspecs) + (P(),) * n_rest,
        out_specs=(cspecs, P()))
    return jax.jit(sharded, donate_argnums=1)


def make_tp_paged_prefill_fn(model: GPTModel, mesh=None, quantized=False,
                             kv_quantized=False):
    """TP paged prefill: ``jit(shard_map(...))`` over the global mesh, cache
    donated. Params use ``model.partition_specs()`` (or the quantized tree's
    ``quant_partition_specs``); the cache uses
    ``paged_cache_partition_specs()``: the pool's head axis shards over
    ``model``; block tables / page ids are replicated host decisions, so
    every rank scatters its local heads' tiles to the same physical pages.
    ``kv_quantized`` switches the cache specs to the int8 pool's (the scales
    shard their head axis over ``model`` too)."""
    cfg = model.cfg
    (embed, dense_fns, logits_fn), pspecs = _tp_build(model, quantized)

    def prefill(params, cache, ids, mask, slot, write_pages, table_row):
        return _paged_prefill_core(params, cfg, cache, ids, mask, slot,
                                   write_pages, table_row,
                                   embed_fn=embed, dense_fns=dense_fns,
                                   logits_fn=logits_fn)

    return _shard_jit(prefill, mesh, pspecs,
                      paged_cache_partition_specs(quantized=kv_quantized), 5)


def make_tp_paged_decode_fn(model: GPTModel, mesh=None, quantized=False,
                            kv_quantized=False):
    cfg = model.cfg
    (embed, dense_fns, logits_fn), pspecs = _tp_build(model, quantized)

    def decode(params, cache, tokens, active):
        return _paged_decode_core(params, cfg, cache, tokens, active,
                                  embed_fn=embed, dense_fns=dense_fns,
                                  logits_fn=logits_fn)

    return _shard_jit(decode, mesh, pspecs,
                      paged_cache_partition_specs(quantized=kv_quantized), 2)


def make_tp_paged_verify_fn(model: GPTModel, mesh=None, quantized=False,
                            kv_quantized=False):
    """TP speculative verify: the (b, k1, V) logits leave through the
    same vocab-sharded head + rank-order gather as decode's."""
    cfg = model.cfg
    (embed, dense_fns, logits_fn), pspecs = _tp_build(model, quantized)

    def verify(params, cache, tokens):
        return _paged_verify_core(params, cfg, cache, tokens,
                                  embed_fn=embed, dense_fns=dense_fns,
                                  logits_fn=logits_fn)

    return _shard_jit(verify, mesh, pspecs,
                      paged_cache_partition_specs(quantized=kv_quantized), 1)


def make_tp_paged_chunk_prefill_fn(model: GPTModel, mesh=None,
                                   quantized=False):
    """TP paged chunked prefill: page ids and both block-table rows are
    replicated host decisions, so every rank scatters its local heads'
    tiles to the same physical pages (int8 pools refused — no
    ``kv_quantized`` switch, as with tree verify)."""
    cfg = model.cfg
    (embed, dense_fns, logits_fn), pspecs = _tp_build(model, quantized)

    def chunk_prefill(params, cache, ids, mask, slot, pos, write_pages,
                      gather_row, store_row):
        return _paged_chunk_prefill_core(
            params, cfg, cache, ids, mask, slot, pos, write_pages,
            gather_row, store_row, embed_fn=embed, dense_fns=dense_fns,
            logits_fn=logits_fn)

    return _shard_jit(chunk_prefill, mesh, pspecs,
                      paged_cache_partition_specs(), 7)


def make_tp_paged_tree_verify_fn(model: GPTModel, mesh=None,
                                 quantized=False):
    """TP paged tree verify: the depth/anc tree descriptors are
    replicated host decisions (like block tables); heads shard over
    ``model`` and the (b, k1, V) logits leave as
    :func:`make_tp_paged_verify_fn`'s (int8 pools refused — linear spec
    only there, so no ``kv_quantized`` switch)."""
    cfg = model.cfg
    (embed, dense_fns, logits_fn), pspecs = _tp_build(model, quantized)

    def verify(params, cache, tokens, depth, anc):
        return _paged_tree_verify_core(params, cfg, cache, tokens,
                                       depth, anc, embed_fn=embed,
                                       dense_fns=dense_fns,
                                       logits_fn=logits_fn)

    return _shard_jit(verify, mesh, pspecs, paged_cache_partition_specs(),
                      3)
