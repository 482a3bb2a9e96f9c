"""Tensor-parallel GPT (decoder-only transformer).

Reference: ``apex/transformer/testing/standalone_gpt.py`` — the in-tree
Megatron-style GPT the reference uses to exercise its tensor/pipeline
parallel stack end-to-end (ColumnParallelLinear qkv/fc1, RowParallelLinear
proj/fc2, VocabParallelEmbedding, vocab-parallel cross entropy, causal
fused softmax). BASELINE config #5 benchmarks exactly this model at TP=8.

TPU-first design choices (vs. the reference's nn.Module stack):

- **Stacked layers + ``lax.scan``**: all transformer-layer params carry a
  leading ``num_layers`` axis and the depth loop is a scan — compile time
  is O(1) in depth and the same stack reshapes to ``(pp, L/pp, ...)`` for
  the collective pipeline schedules with zero re-plumbing.
- **Two execution paths from one weight layout**: ``apply_gpt`` /
  ``gpt_loss`` run INSIDE ``parallel_state.shard_map`` and speak the TP
  collectives (the Megatron path); ``apply_gpt_unsharded`` is plain jnp on
  the same (full) params — the golden model for parity tests and the
  single-chip path (no mesh needed).
- Attention heads are derived from the LOCAL qkv width at trace time, so
  the same code serves any tp degree without threading tp through shapes.
- The LM head ties to the (vocab-sharded) word embedding; logits stay
  vocab-sharded and feed ``vocab_parallel_cross_entropy`` (never a full
  (b, s, V) softmax — the reference's ``parallel_output=True``).
- RoPE (``use_rope=True``) or learned absolute positions; causal masking
  via the flash kernel above the dispatch crossover, the fused
  upper-triangular softmax below it.
"""

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.amp.autocast import cast_args
from apex_tpu.normalization import fused_layer_norm_affine
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.functional import (
    flash_attention,
    fused_apply_rotary_pos_emb_bhsd,
    rope_frequencies,
)
from apex_tpu.utils.profiler import region


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to a multiple of 128
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    ffn_hidden_size: int = 4096
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    use_rope: bool = False           # learned absolute positions otherwise
    rope_base: float = 10000.0
    hidden_dropout: float = 0.1      # applied only when rng given
    # jax.checkpoint each layer block: live activation memory drops from
    # O(layers) full per-op residual sets to one hidden state per layer
    # plus recompute — mandatory at gpt_medium scale on one chip (ref
    # analogue: Megatron's --recompute-granularity)
    remat: bool = False
    # optional jax.checkpoint policy name (an attribute of
    # jax.checkpoint_policies, e.g. "dots_saveable"): the analogue of
    # Megatron's --recompute-granularity=selective — matmul outputs are
    # SAVED and only the cheap elementwise chain (LN, gelu, residuals)
    # is recomputed in backward. Middle ground between full remat's
    # ~33% fwd recompute and no-remat's O(layers · per-op) live set.
    remat_policy: Optional[str] = None
    # Megatron sequence parallelism: activations OUTSIDE the TP regions
    # (LN, residuals, dropout) are sharded along seq over the model axis
    # (seq_dim=1 in this model's (b, s, h) layout); Column gathers /
    # Row reduce-scatters at the region edges. Requires seq % tp == 0.
    sequence_parallel: bool = False
    # Long-context parallelism: the WHOLE model runs on a sequence shard
    # (ids arrive (b, s/cp)) and attention is ring attention over the
    # ``context`` mesh axis — no rank ever holds the full sequence or an
    # (s, s) score tile. Composes with tp (heads still shard over
    # ``model``). Mutually exclusive with sequence_parallel (different
    # axes, different contracts).
    context_parallel: bool = False
    # which long-context attention runs under context_parallel:
    # "ring" rotates k/v shards (O(cp) permutes, any head count) or
    # "ulysses" all-to-alls seq<->heads (O(1) collectives, needs
    # (num_heads/tp) % cp == 0) — both exact, tested for parity
    context_parallel_impl: str = "ring"
    # per-layer fp32 wgrad emission (the gradient_accumulation_fusion
    # analogue, ref fused_weight_gradient_mlp_cuda): with fp32 master
    # weights + bf16 compute, TP linear wgrads leave each layer at fp32
    # with no bf16 round-trip, so microbatch accumulation keeps low bits
    gradient_accumulation_fusion: bool = False

    #: no layer keeps a recurrent state (``models.hybrid`` has some that do)
    recurrent = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def gpt_medium() -> GPTConfig:
    """GPT-2 medium-class — the BASELINE #5 TP benchmark model."""
    return GPTConfig(remat=True)


def gpt_tiny() -> GPTConfig:
    return GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                     num_heads=8, ffn_hidden_size=128,
                     max_position_embeddings=64)


def draft_gpt_tiny() -> GPTConfig:
    """2-layer draft model pairing :func:`gpt_tiny` for speculative
    serving: same vocab (draft tokens must be target tokens), a fraction
    of the width/depth, and RoPE so the draft's reach is never bound by
    a learned position table shorter than the target's."""
    return GPTConfig(vocab_size=512, hidden_size=32, num_layers=2,
                     num_heads=4, ffn_hidden_size=64,
                     max_position_embeddings=128, use_rope=True)


def draft_gpt_medium() -> GPTConfig:
    """Draft model pairing :func:`gpt_medium` — the cost-model config
    behind the ``gpt_draft_forward_step`` budget entry: its per-step HBM
    traffic (params + draft cache) must stay under 3% of the target's
    per-step parameter read, the amortization condition for model-draft
    break-even.

    ``num_heads=4`` (head_dim 32), not 2: the drafter shares the
    target's pod slice, so its KV-cache head axis must divide every
    tensor-parallel size the target is swept over (APX904 fires on
    ``2 % 4`` at tp=4). Param shapes and cache bytes are unchanged —
    qkv width is ``3 * hidden`` either way."""
    return GPTConfig(vocab_size=50304, hidden_size=128, num_layers=2,
                     num_heads=4, ffn_hidden_size=256,
                     max_position_embeddings=1024, use_rope=True)


# ---------------------------------------------------------------------------
# init — full (unsharded) params; stacked on a leading layer axis
# ---------------------------------------------------------------------------

def _stack(key, n, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


def init_gpt(key: jax.Array, cfg: GPTConfig,
             dtype=jnp.float32) -> Dict[str, Any]:
    h, f, L = cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_layers
    k_emb, k_pos, k_layers = jax.random.split(key, 3)

    def dense_init(k, fan_in, shape):
        return jax.random.normal(k, shape, dtype) * math.sqrt(1.0 / fan_in)

    def one_layer(k):
        ks = jax.random.split(k, 4)
        return {
            "ln1": {"weight": jnp.ones((h,), jnp.float32),
                    "bias": jnp.zeros((h,), jnp.float32)},
            "qkv": {"kernel": dense_init(ks[0], h, (h, 3 * h)),
                    "bias": jnp.zeros((3 * h,), dtype)},
            "out": {"kernel": dense_init(ks[1], h, (h, h)),
                    "bias": jnp.zeros((h,), dtype)},
            "ln2": {"weight": jnp.ones((h,), jnp.float32),
                    "bias": jnp.zeros((h,), jnp.float32)},
            "fc1": {"kernel": dense_init(ks[2], h, (h, f)),
                    "bias": jnp.zeros((f,), dtype)},
            "fc2": {"kernel": dense_init(ks[3], f, (f, h)),
                    "bias": jnp.zeros((h,), dtype)},
        }

    params: Dict[str, Any] = {
        "embedding": {"word": {"embedding": jax.random.normal(
            k_emb, (cfg.vocab_size, h), dtype) * 0.02}},
        "layers": _stack(k_layers, L, one_layer),
        "final_ln": {"weight": jnp.ones((h,), jnp.float32),
                     "bias": jnp.zeros((h,), jnp.float32)},
    }
    if not cfg.use_rope:
        params["embedding"]["position"] = {"embedding": jax.random.normal(
            k_pos, (cfg.max_position_embeddings, h), dtype) * 0.02}
    return params


def gpt_partition_specs(cfg: GPTConfig) -> Dict[str, Any]:
    """Megatron TP layout over the ``model`` axis (layer leaves carry the
    leading stacked-layer dim)."""
    from jax.sharding import PartitionSpec as P

    t = ps.TENSOR_AXIS
    specs = {
        "embedding": {"word": {"embedding": P(t, None)}},
        "layers": {
            "ln1": {"weight": P(None), "bias": P(None)},
            "qkv": {"kernel": P(None, None, t), "bias": P(None, t)},
            "out": {"kernel": P(None, t, None), "bias": P(None)},
            "ln2": {"weight": P(None), "bias": P(None)},
            "fc1": {"kernel": P(None, None, t), "bias": P(None, t)},
            "fc2": {"kernel": P(None, t, None), "bias": P(None)},
        },
        "final_ln": {"weight": P(), "bias": P()},
    }
    if not cfg.use_rope:
        specs["embedding"]["position"] = {"embedding": P()}
    return specs


# ---------------------------------------------------------------------------
# shared block math (parameterized by the linear/embedding implementations)
# ---------------------------------------------------------------------------

def _ln(p, x, eps):
    return fused_layer_norm_affine(x, p["weight"], p["bias"],
                                   x.shape[-1], eps).astype(x.dtype)


def _split_qkv(q_k_v: jax.Array, hd: int):
    """(b, s, 3*h_local) head-major -> three (b, nh_local, s, hd)."""
    b, s, w = q_k_v.shape
    nh_local = w // (3 * hd)
    qkv = q_k_v.reshape(b, s, nh_local, 3, hd)
    return (qkv[:, :, :, j].transpose(0, 2, 1, 3) for j in range(3))


def _causal_attention(q_k_v: jax.Array, cfg: GPTConfig,
                      rope_freqs: Optional[jax.Array]) -> jax.Array:
    """(b, s, 3*h_local) -> (b, s, h_local); heads derived from the local
    width so the same code runs at any tp degree.

    qkv column layout is HEAD-MAJOR: ``[head0: q k v | head1: q k v | …]``
    (Megatron's storage order) — a contiguous column shard of the fused
    qkv kernel then holds whole heads, which is what makes plain
    ColumnParallelLinear sharding correct. A ``[Q | K | V]``-major layout
    would hand each rank slices of unrelated heads.
    """
    b, s, _ = q_k_v.shape
    hd = cfg.head_dim
    q, k, v = _split_qkv(q_k_v, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs)
    ctx = flash_attention(q, k, v, causal=True,
                          softmax_scale=1.0 / math.sqrt(hd))
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)


def _ring_causal_attention(q_k_v: jax.Array, cfg: GPTConfig,
                           rope_freqs: Optional[jax.Array]) -> jax.Array:
    """Context-parallel attention: same head-major split, but q/k/v stay
    sequence-sharded and the score/PV work rides the ``context``-axis
    ring (``rope_freqs`` already sliced to this rank's global
    positions)."""
    from apex_tpu.transformer.context_parallel import ring_attention

    return _cp_attention(q_k_v, cfg, rope_freqs, ring_attention)


def _ulysses_causal_attention(q_k_v: jax.Array, cfg: GPTConfig,
                              rope_freqs: Optional[jax.Array]
                              ) -> jax.Array:
    """Context-parallel attention, Ulysses flavor: RoPE is applied on
    the local shard (``rope_freqs`` already globally positioned), then
    one stacked all-to-all gives each rank the FULL sequence for h/cp
    heads (and one brings the context back)."""
    from apex_tpu.transformer.context_parallel import ulysses_attention

    return _cp_attention(q_k_v, cfg, rope_freqs, ulysses_attention)


def _cp_attention(q_k_v, cfg, rope_freqs, attn_fn):
    """Shared context-parallel attention body: split the fused qkv,
    apply RoPE on the local shard, run ``attn_fn``, re-fuse heads."""
    b, s, _ = q_k_v.shape
    hd = cfg.head_dim
    q, k, v = _split_qkv(q_k_v, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs)
    ctx = attn_fn(q, k, v, causal=True,
                  softmax_scale=1.0 / math.sqrt(hd))
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1)


_CP_ATTN = {"ring": _ring_causal_attention,
            "ulysses": _ulysses_causal_attention}


def _block(lp, x, cfg, rope_freqs, qkv_fn, out_fn, fc1_fn, fc2_fn,
           dropout_rng=None, ring=False):
    """Pre-LN transformer block: x + Attn(LN(x)); x + MLP(LN(x)).
    ``ring`` is an execution-path choice, not config: the unsharded
    golden model runs the same cfg with plain attention; True selects
    ``cfg.context_parallel_impl``."""
    attn = _CP_ATTN[cfg.context_parallel_impl] if ring \
        else _causal_attention
    with region("attention"):
        att = attn(qkv_fn(lp["qkv"], _ln(lp["ln1"], x,
                                         cfg.layer_norm_eps)),
                   cfg, rope_freqs)
        att = out_fn(lp["out"], att)
        att = _maybe_dropout(att, cfg.hidden_dropout, dropout_rng, 0)
        x = x + att
    with region("mlp"):
        mlp = fc2_fn(lp["fc2"], jax.nn.gelu(
            fc1_fn(lp["fc1"], _ln(lp["ln2"], x, cfg.layer_norm_eps))))
        mlp = _maybe_dropout(mlp, cfg.hidden_dropout, dropout_rng, 1)
    return x + mlp


# ---------------------------------------------------------------------------
# cache-aware block apply (serving): prefill and single-token decode.
# Parameterized by the same linear fns as _block so the unsharded golden
# path and the TP path share one body (apex_tpu.serving builds both).
# ---------------------------------------------------------------------------

def _prefill_attention(q_k_v: jax.Array, cfg: GPTConfig,
                       rope_freqs: Optional[jax.Array],
                       key_mask: Optional[jax.Array]):
    """Like :func:`_causal_attention` but also returns the (post-RoPE)
    k and raw v tiles so the caller can populate a KV cache, and takes
    an explicit ``key_mask`` ((b, s) int, 1 = real token) so a
    bucket-padded prompt's pad tail is excluded as KEYS. Causality
    already protects real queries from the tail pads (pads sit at the
    END of the bucket), but the mask makes the exclusion unconditional
    — prefill numerics can never depend on pad contents."""
    b, s, _ = q_k_v.shape
    hd = cfg.head_dim
    q, k, v = _split_qkv(q_k_v, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs)
    ctx = flash_attention(q, k, v, key_mask, causal=True,
                          softmax_scale=1.0 / math.sqrt(hd))
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, -1), k, v


def _mlp_residual(lp, x, cfg, fc1_fn, fc2_fn):
    """The MLP half of every serving block: x + MLP(LN(x))."""
    with region("mlp"):
        mlp = fc2_fn(lp["fc2"], jax.nn.gelu(
            fc1_fn(lp["fc1"], _ln(lp["ln2"], x, cfg.layer_norm_eps))))
        return x + mlp


def _block_prefill(lp, x, cfg, rope_freqs, key_mask,
                   qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block` that also emits this layer's (k, v) cache tiles."""
    with region("attention"):
        att, k, v = _prefill_attention(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            cfg, rope_freqs, key_mask)
        x = x + out_fn(lp["out"], att)
    return _mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k, v


def _page_rows(t: jax.Array) -> jax.Array:
    """One position's (b, nh_local, hd) heads as the (b, nh_local * hd)
    row a stored page holds: heads side by side."""
    return t.reshape(t.shape[0], -1)


def _pages_to_tiles(pages: jax.Array, hd: int) -> jax.Array:
    """Stored pages (..., page_size, nh_local * hd) as head-major tiles
    (..., nh_local, page_size, hd)."""
    *lead, page_size, width = pages.shape
    return jnp.moveaxis(
        pages.reshape(*lead, page_size, width // hd, hd), -2, -3)


def _tiles_to_pages(tiles: jax.Array) -> jax.Array:
    """Head-major tiles (..., nh_local, page_size, hd) as stored pages
    (..., page_size, nh_local * hd)."""
    *lead, nh, page_size, hd = tiles.shape
    return jnp.moveaxis(tiles, -3, -2).reshape(*lead, page_size, nh * hd)


def _gather_pages(pages: jax.Array, table: jax.Array, hd: int) -> jax.Array:
    """Every table row's pages of one layer's pool (num_pages, page_size,
    nh_local * hd) as (b, nh_local, S, hd), S = max_pages * page_size
    logical positions: the gather path of verify / tree verify / chunked
    prefill (decode reads the mapped pages in place instead)."""
    g = pages[table]
    b, max_pages, page_size, width = g.shape
    return g.reshape(b, max_pages * page_size, width // hd,
                     hd).transpose(0, 2, 1, 3)


def _paged_decode_attention(q_k_v: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, layer: jax.Array,
                            block_tables: jax.Array, pos: jax.Array,
                            cfg: GPTConfig,
                            rope_freqs: Optional[jax.Array]):
    """Single-query attention against the PAGED KV pool, read in place.

    ``q_k_v`` is (b, 1, 3*h_local); ``k_pool``/``v_pool`` are the WHOLE
    stacked pool (L, num_pages, page_size, nh_local * hd) and ``layer``
    the scalar index of the layer attending; ``block_tables`` (b,
    max_pages) int32 maps each slot's logical page index to a physical
    page; ``pos`` (b,) int32 is each slot's current length. The Pallas
    kernel ``apex_paged_decode_fwd``
    (:mod:`apex_tpu.transformer.functional.paged_attention`) brings only
    the pages at or below ``pos`` from HBM and runs the float32 online
    softmax over them; the pool is read, never written here.

    Write-new-row-then-attend: the new token's K/V row is rounded
    to the pool's dtype and attended to AT position ``pos`` as an operand
    of the kernel, and returned ((b, nh_local * hd) each) for the caller to
    write into page ``block_tables[b, pos // page_size]`` at row ``pos %
    page_size`` — one scatter for all layers after the layer scan
    (``serving.decode._paged_decode_core``), so the pool is no per-layer
    carry of the scan.

    Placement invariance: rows at or past ``pos`` are masked in the scores
    and zeroed in the values, and pages past ``pos`` are never fetched, so
    garbage beyond ``pos`` — stale rows, NaN, other requests' pages —
    cannot reach the context. Active-slot logits are bit-identical for any
    physical page assignment of the same logical contents (the serving
    contract ``tests/L0/run_serving`` pins). Returns (ctx (b, 1, h_local),
    k_row, v_row).
    """
    from apex_tpu.transformer.functional.paged_attention import (
        paged_decode_attention,
    )

    hd = cfg.head_dim
    q, k, v = _split_qkv(q_k_v, hd)            # (b, nh_local, 1, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs, positions=pos)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs, positions=pos)
    k_row = _page_rows(k).astype(k_pool.dtype)
    v_row = _page_rows(v).astype(v_pool.dtype)
    ctx = paged_decode_attention(
        _page_rows(q)[:, None], k_row[:, None], v_row[:, None], k_pool,
        v_pool, block_tables, pos, layer, heads=q.shape[1])
    return ctx.astype(q_k_v.dtype), k_row, v_row


def _block_decode_paged(lp, x, k_pool, v_pool, layer, block_tables, pos,
                        cfg, rope_freqs, qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block` against the paged pool: x is the (b, 1, h)
    new-token hidden; returns (x', k_row, v_row), the layer's new rows
    for the caller to write."""
    with region("attention"):
        att, k_row, v_row = _paged_decode_attention(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            k_pool, v_pool, layer, block_tables, pos, cfg, rope_freqs)
        x = x + out_fn(lp["out"], att)
    return _mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k_row, v_row


def _paged_verify_attention(q_k_v: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_tables: jax.Array,
                            pos: jax.Array, cfg: GPTConfig,
                            rope_freqs: Optional[jax.Array]):
    """Multi-query (speculative *verify*) attention against the PAGED
    pool — the k+1 generalization of :func:`_paged_decode_attention`.

    ``q_k_v`` is (b, k1, 3*h_local) — the last committed token plus k
    drafted candidates, projected together; ``pos`` (b,) int32 is each
    slot's committed length, so query j sits at absolute position
    ``pos + j`` (RoPE rotates consecutive positions from ``pos``, the
    same ``positions=`` contract the single-token path uses). All k1
    new k/v rows are written BEFORE attending: k1 is static, so the
    scatter is k1 unrolled single-row updates of the donated pool —
    each position lands in page ``block_tables[b, (pos+j) //
    page_size]`` at row ``(pos+j) % page_size``. The per-query mask
    ``s <= pos + j`` then admits exactly the committed history plus the
    candidate's own prefix — write-then-attend, so every admitted row
    holds a real value and logits row j equals a teacher-forced forward
    at position ``pos + j``. Rows beyond the accepted prefix are never
    admitted by any later mask before being re-written (positions are
    monotone), which is the whole cache-rollback contract: rejection
    needs no cleanup pass. Callers must hold pages allocated for all k1
    positions (the scheduler's ``prepare_decode(..., n_new=k1)``; a
    position past the table is clamped onto the row's last page).
    Scores/softmax run in fp32; returns (ctx (b, k1, h_local), k_pages,
    v_pages).
    """
    b, k1, _ = q_k_v.shape
    hd = cfg.head_dim
    page_size = k_pages.shape[1]
    q, k, v = _split_qkv(q_k_v, hd)            # (b, nh_local, k1, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs, positions=pos)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs, positions=pos)
    for j in range(k1):
        p = pos + j
        logical = jnp.clip(p // page_size, 0, block_tables.shape[1] - 1)
        pages = jnp.take_along_axis(
            block_tables, logical[:, None], 1)[:, 0]
        rows = p % page_size
        k_pages = k_pages.at[pages, rows].set(
            _page_rows(k[:, :, j]).astype(k_pages.dtype))
        v_pages = v_pages.at[pages, rows].set(
            _page_rows(v[:, :, j]).astype(v_pages.dtype))
    kg = _gather_pages(k_pages, block_tables, hd)
    vg = _gather_pages(v_pages, block_tables, hd)
    s_max = kg.shape[2]
    scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / math.sqrt(hd)
    qpos = pos[:, None] + jnp.arange(k1)[None, :]        # (b, k1)
    valid = jnp.arange(s_max)[None, None, None, :] \
        <= qpos[:, None, :, None]
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bhsd->bhqd", probs,
                     vg.astype(jnp.float32)).astype(q_k_v.dtype)
    return ctx.transpose(0, 2, 1, 3).reshape(b, k1, -1), k_pages, v_pages


def _block_verify_paged(lp, x, k_pages, v_pages, block_tables, pos, cfg,
                        rope_freqs, qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block_decode_paged` over k1 candidate positions at once
    (one layer's pool in, the same pool with the k1 rows written out)."""
    with region("attention"):
        att, k_pages, v_pages = _paged_verify_attention(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            k_pages, v_pages, block_tables, pos, cfg, rope_freqs)
        x = x + out_fn(lp["out"], att)
    return _mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k_pages, v_pages


def _paged_chunk_prefill_attention(q_k_v: jax.Array, k_pages: jax.Array,
                                   v_pages: jax.Array,
                                   write_pages: jax.Array,
                                   gather_row: jax.Array, pos: jax.Array,
                                   cfg: GPTConfig,
                                   rope_freqs: Optional[jax.Array],
                                   key_mask: jax.Array):
    """Chunked-prefill attention for ONE slot against the PAGED pool:
    the prompt-sized generalization of :func:`_paged_verify_attention`.

    ``q_k_v`` is (1, sc, 3*h_local) — one chunk of one slot's prompt,
    projected together; ``pos`` is the chunk's absolute start position
    (scalar int32), so token j sits at ``pos + j``; ``key_mask`` (1, sc)
    int32 marks real tokens (the final chunk of a prompt is
    bucket-padded at the tail). The chunk's k/v rows are zero-masked
    and written BEFORE attending — write-then-attend, so the per-query
    ``s <= pos + j`` mask admits exactly the previously-written chunks
    plus the token's own prefix, and logits at row j equal a
    teacher-forced forward at position ``pos + j``. Pad queries (mask
    0) attend only zeroed rows beyond every real query's mask, so their
    garbage context is unreachable from any real row's output.
    Scores/softmax run in fp32.

    Chunks are whole pages (sc a multiple of page_size), so the write is
    the monolithic paged prefill's page-granular scatter: the chunk's
    zero-masked k/v rows are cut into page tiles and scattered to
    ``write_pages`` ((sc // page_size,) int32 — the host redirects
    prefix-shared pages to ``SCRATCH_PAGE``, so shared pages are never
    rewritten). The attend gathers through ``gather_row`` ((max_pages,)
    int32, the slot's real NULL-padded block-table row) — it is passed
    SEPARATELY from the row the core stores, because the scheduler
    parks the stored row on scratch until the final chunk (mid-prefill
    decode/verify writes by co-tenant steps must land on scratch, not
    on a shared page). Exact-zero masking keeps the result placement-
    invariant, as in :func:`_paged_decode_attention`."""
    _, sc, _ = q_k_v.shape
    hd = cfg.head_dim
    page_size = k_pages.shape[1]
    n_chunk_pages = sc // page_size
    q, k, v = _split_qkv(q_k_v, hd)            # (1, nh_local, sc, hd)
    p1 = pos[None]
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs, positions=p1)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs, positions=p1)
    mz = key_mask.astype(k.dtype)[:, None, :, None]

    def tiles(t, dtype):
        # (1, nh, sc, hd) -> pages (n_chunk_pages, page, nh * hd),
        # zero-masked pad rows included (scratch eats redirected pages)
        t = (t * mz)[0].transpose(1, 0, 2)
        return t.reshape(n_chunk_pages, page_size, -1).astype(dtype)

    k_pages = k_pages.at[write_pages].set(tiles(k, k_pages.dtype))
    v_pages = v_pages.at[write_pages].set(tiles(v, v_pages.dtype))
    kg = _gather_pages(k_pages, gather_row[None], hd)
    vg = _gather_pages(v_pages, gather_row[None], hd)
    s_max = kg.shape[2]
    scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / math.sqrt(hd)
    qpos = p1[:, None] + jnp.arange(sc)[None, :]         # (1, sc)
    valid = jnp.arange(s_max)[None, None, None, :] \
        <= qpos[:, None, :, None]
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bhsd->bhqd", probs,
                     vg.astype(jnp.float32)).astype(q_k_v.dtype)
    return ctx.transpose(0, 2, 1, 3).reshape(1, sc, -1), k_pages, v_pages


def _block_chunk_prefill_paged(lp, x, k_pages, v_pages, write_pages,
                               gather_row, pos, cfg, rope_freqs,
                               key_mask, qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block_verify_paged` for one slot's prompt chunk."""
    with region("attention"):
        att, k_pages, v_pages = _paged_chunk_prefill_attention(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            k_pages, v_pages, write_pages, gather_row, pos, cfg, rope_freqs,
            key_mask)
        x = x + out_fn(lp["out"], att)
    return _mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k_pages, v_pages


# ---------------------------------------------------------------------------
# tree verify: one forward scores a whole draft TREE (SpecInfer-style).
# The linear `s <= pos + j` mask generalizes to an ancestor matrix: key
# node i is visible to query node j iff i is an ancestor-of-or-equal-to
# j in the draft tree, so logits row j equal a teacher-forced forward
# over exactly j's root-to-node token path. The linear chain is the
# special case anc[i, j] = (i <= j), depth[j] = j.
# ---------------------------------------------------------------------------

def _tree_score_mask(pos, anc, s_max):
    """(b, 1, k1, k1) tree visibility lifted to the (b, 1, q=k1, s=s_max)
    score layout: key position ``s`` is admitted for query node ``j``
    iff ``s < pos`` (committed history — every node sees all of it) or
    ``s`` holds window node ``i = s - pos`` with ``anc[b, i, j]`` set
    (ancestor-or-self). ``anc`` is (b, k1, k1) bool with anc[j, j]
    required True; rows beyond the window stay masked exactly like the
    linear verify mask, preserving the rollback contract."""
    b, k1, _ = anc.shape
    s_idx = jnp.arange(s_max)
    committed = s_idx[None, :] < pos[:, None]            # (b, s)
    rel = s_idx[None, :] - pos[:, None]                  # (b, s)
    in_win = (rel >= 0) & (rel < k1)
    relc = jnp.clip(rel, 0, k1 - 1)
    vis = jnp.take_along_axis(                           # (b, s, k1)
        anc, jnp.broadcast_to(relc[:, :, None], (b, s_max, k1)), axis=1)
    vis = committed[:, :, None] | (in_win[:, :, None] & vis)
    return vis.transpose(0, 2, 1)[:, None]               # (b, 1, q, s)


def _paged_tree_verify_attention(q_k_v: jax.Array, k_pages: jax.Array,
                                 v_pages: jax.Array,
                                 block_tables: jax.Array, pos: jax.Array,
                                 depth: jax.Array, anc: jax.Array,
                                 cfg: GPTConfig,
                                 rope_freqs: Optional[jax.Array]):
    """Tree-mask verify attention against the PAGED pool.

    ``q_k_v`` is (b, k1, 3*h_local) — the grid nodes' fused projection
    in topological order (node 0 = the pending committed token, the
    root every branch hangs off); ``depth`` (b, k1) int32 is each
    node's depth below the committed history, so node j's ATTENTION /
    RoPE position is ``pos + depth[j]`` while its PHYSICAL row stays
    ``pos + j`` (distinct rows per node — siblings at one tree depth
    share a position but must not share a row): the k1 unrolled row
    scatters of :func:`_paged_verify_attention`. ``anc`` (b, k1, k1)
    bool is the ancestor-or-self matrix consumed by
    :func:`_tree_score_mask`. Same write-then-attend rollback contract
    as :func:`_paged_verify_attention`: all k1 rows are written before
    any mask admits them, and the host re-sends any committed token
    whose row did not land contiguously (the forced-prefix rule in
    ``scheduler._tree_tick``), so rejected branch rows are overwritten
    before they are ever attended. Not offered for the int8 pool: an accepted
    non-leftmost branch would require compacting quantized rows across
    pages, re-rounding committed history at branch-dependent scales —
    the engine pins linear spec for kv8 instead."""
    b, k1, _ = q_k_v.shape
    hd = cfg.head_dim
    page_size = k_pages.shape[1]
    q, k, v = _split_qkv(q_k_v, hd)            # (b, nh_local, k1, hd)
    if rope_freqs is not None:
        tpos = pos[:, None] + depth                      # (b, k1)
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs, positions=tpos)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs, positions=tpos)
    for j in range(k1):
        p = pos + j
        logical = jnp.clip(p // page_size, 0, block_tables.shape[1] - 1)
        pages = jnp.take_along_axis(
            block_tables, logical[:, None], 1)[:, 0]
        rows = p % page_size
        k_pages = k_pages.at[pages, rows].set(
            _page_rows(k[:, :, j]).astype(k_pages.dtype))
        v_pages = v_pages.at[pages, rows].set(
            _page_rows(v[:, :, j]).astype(v_pages.dtype))
    kg = _gather_pages(k_pages, block_tables, hd)
    vg = _gather_pages(v_pages, block_tables, hd)
    s_max = kg.shape[2]
    scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / math.sqrt(hd)
    valid = _tree_score_mask(pos, anc, s_max)
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bhsd->bhqd", probs,
                     vg.astype(jnp.float32)).astype(q_k_v.dtype)
    return ctx.transpose(0, 2, 1, 3).reshape(b, k1, -1), k_pages, v_pages


def _block_tree_verify_paged(lp, x, k_pages, v_pages, block_tables, pos,
                             depth, anc, cfg, rope_freqs,
                             qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block_verify_paged` under the tree-attention mask."""
    with region("attention"):
        att, k_pages, v_pages = _paged_tree_verify_attention(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            k_pages, v_pages, block_tables, pos, depth, anc, cfg, rope_freqs)
        x = x + out_fn(lp["out"], att)
    return _mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k_pages, v_pages


# ---------------------------------------------------------------------------
# int8-quantized paged attention: RMW whole-page requant on write,
# dequant inside the gather
# ---------------------------------------------------------------------------

def _q8_page_insert(pool, scale, pages, rows, new_row, rescale=True,
                    zero_dead=False):
    """Insert ``new_row`` (b, nh, hd) fp32 into the int8 page ``pages``
    of each slot at row ``rows`` by a whole-page READ-MODIFY-WRITE
    requant: gather page + scale, dequantize, set the exact new row,
    recompute the per-head amax scale over the whole page, round-requant
    and scatter page + scale back.

    Whole-page RMW is the correctness-bearing choice: quantizing only
    the new row against a RUNNING scale would silently corrupt history
    rows quantized at the old scale. Re-quantizing existing rows at a
    fixed scale is round-to-nearest idempotent, so untouched-amax pages
    come back bit-identical; an amax-raising row re-rounds the history
    at the new scale, which the teacher-forced tolerance gate covers.

    The VERIFY path passes ``zero_dead=True``: every row strictly
    beyond the insert is zeroed before the amax (rows past the insert
    point are stale/speculative garbage by the write-then-attend
    contract, never admitted by any mask), making the new scale a pure
    function of LIVE rows. That is what upgrades the kv8 spec stream
    from tolerance-gated to bit-identical across rejected-tail
    differences (two runs that committed the same tokens but drafted
    different rejected tails requantize every page at identical
    scales). The single-token decode step keeps the whole-tile amax —
    its beyond-rows are zeros, stale-owner garbage (never attended,
    about to be overwritten), or a rejected tail the next verify
    window rewrites before any rescale — preserving r12's plain-tick
    bit pattern exactly.

    ``rescale=False`` (the speculative verify columns j >= 1) pins the
    page's existing scale instead: the new row quantizes (clipped)
    against it and every other row re-rounds at its own scale, which is
    round-to-nearest idempotent — so a SPECULATIVE row can never
    re-round committed history at a scale influenced by other (possibly
    rejected) candidates. A row landing at page row 0 always resets the
    scale (the page holds nothing live below it), which keeps fresh
    pages usable mid-draft and is wiped by the next tick's writes if
    the candidate is rejected. Duplicate scatter targets only arise
    when several inactive slots park on SCRATCH_PAGE — never attended,
    and a 0-or-positive scale always dequantizes finite, so the
    nondeterminism can't escape."""
    from apex_tpu.quant.kernels import kv_dequantize, kv_quantize

    b = pages.shape[0]
    old = scale[pages]                                 # (b, nh)
    tile = kv_dequantize(_pages_to_tiles(pool[pages], new_row.shape[-1]),
                         old)                          # (b, nh, page, hd)
    tile = tile.at[jnp.arange(b), :, rows].set(new_row)
    if zero_dead:
        ridx = jnp.arange(tile.shape[2])
        live = ridx[None, None, :, None] <= rows[:, None, None, None]
        tile = jnp.where(live, tile, 0.0)
    nq, ns = kv_quantize(tile)
    if not rescale:
        keep = (rows > 0)[:, None]                     # (b, 1) over heads
        sel = jnp.where(keep, old, ns)
        safe = jnp.where(sel > 0, sel, 1.0)[..., None, None]
        qk = jnp.clip(jnp.round(tile / safe), -127, 127).astype(pool.dtype)
        nq = jnp.where(keep[..., None, None], qk, nq)
        ns = sel
    return (pool.at[pages].set(_tiles_to_pages(nq)),
            scale.at[pages].set(ns))


def _q8_gather(pool, scale, block_tables, b, hd):
    """Dequantized (b, nh, S, hd) fp32 view of each slot's table row."""
    from apex_tpu.quant.kernels import kv_dequantize

    g = kv_dequantize(_pages_to_tiles(pool[block_tables], hd),
                      scale[block_tables])
    g = g.transpose(0, 2, 1, 3, 4)
    return g.reshape(b, g.shape[1], g.shape[2] * g.shape[3], hd)


def _paged_decode_attention_q8(q_k_v, k_pages, v_pages, k_scale, v_scale,
                               block_tables, pos, cfg: GPTConfig,
                               rope_freqs):
    """:func:`_paged_decode_attention` over an INT8 page pool with
    per-page-per-head fp32 scales. Same write-then-attend and exact-zero
    masking contracts; the write is the whole-page RMW requant of
    :func:`_q8_page_insert` and the gather dequantizes against the
    scatter-updated scales, so the attended history is exactly what the
    pool stores. Placement independence survives: the RMW is a pure
    function of page content, and masked probabilities are exactly
    zero."""
    b = q_k_v.shape[0]
    hd = cfg.head_dim
    page_size = k_pages.shape[1]
    q, k, v = _split_qkv(q_k_v, hd)            # (b, nh_local, 1, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs, positions=pos)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs, positions=pos)
    logical = jnp.clip(pos // page_size, 0, block_tables.shape[1] - 1)
    pages = jnp.take_along_axis(block_tables, logical[:, None], 1)[:, 0]
    rows = pos % page_size
    k_pages, k_scale = _q8_page_insert(
        k_pages, k_scale, pages, rows, k[:, :, 0].astype(jnp.float32))
    v_pages, v_scale = _q8_page_insert(
        v_pages, v_scale, pages, rows, v[:, :, 0].astype(jnp.float32))
    kg = _q8_gather(k_pages, k_scale, block_tables, b, hd)
    vg = _q8_gather(v_pages, v_scale, block_tables, b, hd)
    s_max = kg.shape[2]
    scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                        kg) / math.sqrt(hd)
    valid = jnp.arange(s_max)[None, None, None, :] \
        <= pos[:, None, None, None]
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bhsd->bhqd", probs, vg).astype(q_k_v.dtype)
    return (ctx.transpose(0, 2, 1, 3).reshape(b, 1, -1),
            k_pages, v_pages, k_scale, v_scale)


def _block_decode_paged_q8(lp, x, k_pages, v_pages, k_scale, v_scale,
                           block_tables, pos, cfg, rope_freqs,
                           qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block_decode_paged` over the int8 pool + scales."""
    with region("attention"):
        att, k_pages, v_pages, k_scale, v_scale = _paged_decode_attention_q8(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            k_pages, v_pages, k_scale, v_scale, block_tables, pos, cfg,
            rope_freqs)
        x = x + out_fn(lp["out"], att)
    return (_mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k_pages, v_pages,
            k_scale, v_scale)


def _paged_verify_attention_q8(q_k_v, k_pages, v_pages, k_scale, v_scale,
                               block_tables, pos, cfg: GPTConfig,
                               rope_freqs):
    """:func:`_paged_verify_attention` over the int8 pool: k1 unrolled
    whole-page RMW requants (consecutive candidates re-read the latest
    page state, so same-page candidates compose), then the dequantized
    gather with the per-query ``s <= pos + j`` masks. Column 0 is the
    pending COMMITTED token, so it may rescale its page (the amax runs
    over live rows only — :func:`_q8_page_insert` zeroes the dead
    tail); columns j >= 1 are speculative and write with
    ``rescale=False``, pinning the page scale so rejected candidates
    can never re-round committed history. Together these make later
    logits on the int8 cache bit-identical across runs that differ
    only in rejected draft tails (the kv8 spec-stream contract pinned
    by ``test_quant.py::test_kv8_rejected_tails_do_not_perturb``);
    spec-vs-PLAIN kv8 streams remain tolerance-gated, since plain
    decode rescales at every step where verify pins mid-draft.
    """
    b, k1, _ = q_k_v.shape
    hd = cfg.head_dim
    page_size = k_pages.shape[1]
    q, k, v = _split_qkv(q_k_v, hd)            # (b, nh_local, k1, hd)
    if rope_freqs is not None:
        q = fused_apply_rotary_pos_emb_bhsd(q, rope_freqs, positions=pos)
        k = fused_apply_rotary_pos_emb_bhsd(k, rope_freqs, positions=pos)
    for j in range(k1):
        p = pos + j
        logical = jnp.clip(p // page_size, 0, block_tables.shape[1] - 1)
        pages = jnp.take_along_axis(
            block_tables, logical[:, None], 1)[:, 0]
        rows = p % page_size
        k_pages, k_scale = _q8_page_insert(
            k_pages, k_scale, pages, rows,
            k[:, :, j].astype(jnp.float32), rescale=(j == 0),
            zero_dead=True)
        v_pages, v_scale = _q8_page_insert(
            v_pages, v_scale, pages, rows,
            v[:, :, j].astype(jnp.float32), rescale=(j == 0),
            zero_dead=True)
    kg = _q8_gather(k_pages, k_scale, block_tables, b, hd)
    vg = _q8_gather(v_pages, v_scale, block_tables, b, hd)
    s_max = kg.shape[2]
    scores = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                        kg) / math.sqrt(hd)
    qpos = pos[:, None] + jnp.arange(k1)[None, :]        # (b, k1)
    valid = jnp.arange(s_max)[None, None, None, :] \
        <= qpos[:, None, :, None]
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bhsd->bhqd", probs, vg).astype(q_k_v.dtype)
    return (ctx.transpose(0, 2, 1, 3).reshape(b, k1, -1),
            k_pages, v_pages, k_scale, v_scale)


def _block_verify_paged_q8(lp, x, k_pages, v_pages, k_scale, v_scale,
                           block_tables, pos, cfg, rope_freqs,
                           qkv_fn, out_fn, fc1_fn, fc2_fn):
    """:func:`_block_verify_paged` over the int8 pool + scales."""
    with region("attention"):
        att, k_pages, v_pages, k_scale, v_scale = _paged_verify_attention_q8(
            qkv_fn(lp["qkv"], _ln(lp["ln1"], x, cfg.layer_norm_eps)),
            k_pages, v_pages, k_scale, v_scale, block_tables, pos, cfg,
            rope_freqs)
        x = x + out_fn(lp["out"], att)
    return (_mlp_residual(lp, x, cfg, fc1_fn, fc2_fn), k_pages, v_pages,
            k_scale, v_scale)


def _maybe_dropout(x, rate, rng, salt):
    if rng is None or rate <= 0:
        return x
    keep = jax.random.bernoulli(jax.random.fold_in(rng, salt),
                                1 - rate, x.shape)
    return x * keep / (1 - rate)


def _rope_or_none(cfg: GPTConfig, s: int):
    if not cfg.use_rope:
        return None
    return rope_frequencies(cfg.head_dim, s, cfg.rope_base)


# The vetted ZERO-ARG members of jax.checkpoint_policies — directly
# usable as jax.checkpoint(policy=...). Everything else in that
# namespace is a factory (verified by signature inspection: the
# save_*_names / save_from_both_policies / offload_* entries all take
# arguments and return a policy).
_REMAT_POLICIES = frozenset((
    "checkpoint_dots",
    "checkpoint_dots_with_no_batch_dims",
    "dots_saveable",
    "dots_with_no_batch_dims_saveable",
    "everything_saveable",
    "nothing_saveable",
))


def _scan_layers(x, layers, cfg, freqs, qkv_fn, out_fn, fc1_fn, fc2_fn,
                 dropout_rng, ring=False):
    """Depth loop: lax.scan over the stacked layer leaves, optionally
    rematerialized per layer (``cfg.remat``)."""
    def block(lp, x, rng):
        return _block(lp, x, cfg, freqs, qkv_fn, out_fn, fc1_fn, fc2_fn,
                      dropout_rng=rng, ring=ring)

    if cfg.remat:
        pol = None
        if cfg.remat_policy:
            # allowlist of the ZERO-ARG policies: callability alone
            # also admits the factory entries (save_only_these_names,
            # save_and_offload_only_these_names, ...) which ARE callable
            # but take names/policies, not residuals — jax.checkpoint
            # would then fail deep inside the scan trace (or worse,
            # treat the factory as an accept-everything predicate)
            # instead of at config time
            if cfg.remat_policy not in _REMAT_POLICIES:
                raise ValueError(
                    f"remat_policy {cfg.remat_policy!r} is not a "
                    "zero-arg jax.checkpoint_policies policy; pick one "
                    f"of {sorted(_REMAT_POLICIES)} (factories like "
                    "'save_only_these_names' need arguments and are "
                    "not usable here)")
            pol = getattr(jax.checkpoint_policies, cfg.remat_policy)
        block = jax.checkpoint(block, policy=pol)
    if dropout_rng is None:
        x, _ = lax.scan(lambda x, lp: (block(lp, x, None), None),
                        x, layers)
    else:
        x, _ = lax.scan(
            lambda x, sl: (block(sl[0], x, sl[1]), None), x,
            (layers, jax.random.split(dropout_rng, cfg.num_layers)))
    return x


# ---------------------------------------------------------------------------
# tensor-parallel path — call inside parallel_state.shard_map
# ---------------------------------------------------------------------------

def _tied_lm_logits(hidden: jax.Array, table_local: jax.Array) -> jax.Array:
    """hidden (replicated) @ local-vocab-shard.T — a ColumnParallelLinear
    in disguise: the input must pass through copy_to_region so the
    BACKWARD all-reduces dhidden across TP ranks (each rank's dlogits @
    table_local is only its vocab slice's partial sum). Forward is the
    identity."""
    from apex_tpu.transformer.tensor_parallel import mappings

    hidden = mappings.copy_to_tensor_model_parallel_region(hidden)
    return jnp.dot(hidden, table_local.astype(hidden.dtype).T).astype(
        jnp.float32)


class GPTModel:
    """Bundles the TP layer objects (Column/Row/VocabParallel) for one
    config. ``apply``/``loss`` run inside shard_map; ``init`` and
    ``partition_specs`` describe the full params."""

    def __init__(self, cfg: GPTConfig, tp_size: Optional[int] = None):
        self.cfg = cfg
        h, f = cfg.hidden_size, cfg.ffn_hidden_size
        t = tp_size if tp_size is not None else \
            ps.get_tensor_model_parallel_world_size()
        if cfg.num_heads % t:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by tp {t} "
                "(attention heads shard over the model axis)")
        if cfg.sequence_parallel and cfg.context_parallel:
            raise ValueError(
                "sequence_parallel and context_parallel are mutually "
                "exclusive (different axes, different activation "
                "contracts)")
        if cfg.context_parallel_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"context_parallel_impl must be 'ring' or 'ulysses', "
                f"got {cfg.context_parallel_impl!r}")
        if cfg.context_parallel and cfg.context_parallel_impl == "ulysses":
            cp = ps.get_context_parallel_world_size()
            if (cfg.num_heads // t) % cp:
                raise ValueError(
                    f"ulysses context parallelism needs local heads "
                    f"({cfg.num_heads}//tp{t}) divisible by cp={cp}")
        sp = dict(sequence_parallel_enabled=cfg.sequence_parallel,
                  sequence_parallel_seq_dim=1,  # (b, s, h) layout
                  gradient_accumulation_fusion=
                  cfg.gradient_accumulation_fusion)
        self.qkv = tp.ColumnParallelLinear(h, 3 * h, gather_output=False,
                                           tp_size=tp_size, **sp)
        self.out = tp.RowParallelLinear(h, h, input_is_parallel=True,
                                        tp_size=tp_size, **sp)
        self.fc1 = tp.ColumnParallelLinear(h, f, gather_output=False,
                                           tp_size=tp_size, **sp)
        self.fc2 = tp.RowParallelLinear(f, h, input_is_parallel=True,
                                        tp_size=tp_size, **sp)
        self.embed = tp.VocabParallelEmbedding(cfg.vocab_size, h,
                                               tp_size=tp_size)

    def init(self, key: jax.Array, dtype=jnp.float32) -> Dict[str, Any]:
        return init_gpt(key, self.cfg, dtype)

    def partition_specs(self) -> Dict[str, Any]:
        return gpt_partition_specs(self.cfg)

    def apply(self, params: Dict[str, Any], input_ids: jax.Array,
              *, dropout_rng: Optional[jax.Array] = None,
              compute_dtype=None) -> jax.Array:
        """ids (b, s) -> hidden (b, s, h). Inside shard_map over the
        ``model`` axis (tp=1 mesh is fine)."""
        from apex_tpu.transformer.tensor_parallel import mappings

        cfg = self.cfg
        b, s = input_ids.shape
        with region("embed"):
            x = self.embed.apply(params["embedding"]["word"], input_ids)
            if compute_dtype is not None:
                x = x.astype(compute_dtype)
        if cfg.context_parallel:
            # ids arrived (b, s/cp): positions and rotary angles are the
            # GLOBAL ones for this rank's shard
            cp_rank = lax.axis_index(ps.CONTEXT_AXIS)
            if not cfg.use_rope:
                with region("embed"):
                    pos = lax.dynamic_slice_in_dim(
                        params["embedding"]["position"]["embedding"],
                        cp_rank * s, s, 0)
                    x = x + pos.astype(x.dtype)[None]
            freqs = _rope_or_none(
                cfg, s * lax.axis_size(ps.CONTEXT_AXIS))
            if freqs is not None:
                freqs = lax.dynamic_slice_in_dim(freqs, cp_rank * s, s, 0)
            if dropout_rng is not None:
                dropout_rng = jax.random.fold_in(dropout_rng, cp_rank)
            x = _scan_layers(x, params["layers"], cfg, freqs,
                             self.qkv.apply, self.out.apply,
                             self.fc1.apply, self.fc2.apply, dropout_rng,
                             ring=True)
            with region("head"):
                return _ln(params["final_ln"], x, cfg.layer_norm_eps)
        if not cfg.use_rope:
            with region("embed"):
                pos = params["embedding"]["position"]["embedding"][:s]
                x = x + pos.astype(x.dtype)[None]
        freqs = _rope_or_none(cfg, s)
        if cfg.sequence_parallel:
            # enter the SP region: shard seq over the model axis; the
            # attention itself still sees the full sequence (Column
            # gathers it back). Decorrelate per-rank dropout streams —
            # ranks hold different tokens.
            x = mappings.scatter_to_sequence_parallel_region(x, 1)
            if dropout_rng is not None:
                dropout_rng = jax.random.fold_in(
                    dropout_rng, lax.axis_index(ps.TENSOR_AXIS))
        x = _scan_layers(x, params["layers"], cfg, freqs,
                         self.qkv.apply, self.out.apply,
                         self.fc1.apply, self.fc2.apply, dropout_rng)
        with region("head"):
            return _ln(params["final_ln"], x, cfg.layer_norm_eps)

    def logits_local(self, params: Dict[str, Any],
                     hidden: jax.Array) -> jax.Array:
        """Tied LM head: (b, s, h) -> vocab-SHARDED logits (b, s, V/tp),
        in rank order (the ``parallel_output=True`` convention)."""
        table = params["embedding"]["word"]["embedding"]
        with region("head"):
            return _tied_lm_logits(hidden, table)

    def allreduce_sequence_parallel_grads(self, grads: Dict[str, Any]
                                          ) -> Dict[str, Any]:
        """SP closure (ref: Megatron's
        ``allreduce_sequence_parallel_gradients`` step, which the
        training loop runs after backward): params that live in the
        sequence-parallel region — the layer norms and the Row-parallel
        biases — see only the local tokens' grads on each rank; psum
        them over the model axis. No-op when SP is off."""
        if not self.cfg.sequence_parallel:
            return grads

        def fix(path, g):
            keys = "/".join(str(getattr(k, "key", k)) for k in path)
            if ("ln1" in keys or "ln2" in keys or "final_ln" in keys
                    or ("out" in keys and "bias" in keys)
                    or ("fc2" in keys and "bias" in keys)):
                return lax.psum(g, ps.TENSOR_AXIS)
            return g

        return jax.tree_util.tree_map_with_path(fix, grads)

    def loss(self, params: Dict[str, Any], input_ids: jax.Array,
             labels: jax.Array, *,
             dropout_rng: Optional[jax.Array] = None,
             compute_dtype=None) -> jax.Array:
        """Mean next-token loss via vocab-parallel CE (labels = targets,
        NOT shifted here — shift upstream, reference convention)."""
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        from apex_tpu.transformer.tensor_parallel import mappings

        hidden = self.apply(params, input_ids, dropout_rng=dropout_rng,
                            compute_dtype=compute_dtype)
        if self.cfg.sequence_parallel:
            # leave the SP region for the LM head; the gather's backward
            # reduce-scatters dhidden — the SP dual of copy_to_region, so
            # the head dots the gathered hidden directly
            with region("head"):
                hidden = mappings.gather_from_sequence_parallel_region(
                    hidden, True, 1)
                table = params["embedding"]["word"]["embedding"]
                logits = jnp.dot(hidden,
                                 table.astype(hidden.dtype).T).astype(
                    jnp.float32)
        else:
            logits = self.logits_local(params, hidden)
        with region("loss"):
            loss = vocab_parallel_cross_entropy(logits, labels).mean()
            if self.cfg.context_parallel:
                # per-token losses live on seq shards of equal size: the
                # global mean is the mean of rank means. NOTE the trainer's
                # closure: like DDP over the batch, each rank's AD yields
                # d(local token mean)/dp — pmean the GRADS over the context
                # axis after backward (see test_context_parallel_*).
                loss = lax.pmean(loss, ps.CONTEXT_AXIS)
        return loss


# ---------------------------------------------------------------------------
# unsharded golden path — plain jnp, no mesh
# ---------------------------------------------------------------------------

def apply_gpt_unsharded(params: Dict[str, Any], cfg: GPTConfig,
                        input_ids: jax.Array,
                        *, dropout_rng: Optional[jax.Array] = None,
                        compute_dtype=None) -> jax.Array:
    b, s = input_ids.shape
    with region("embed"):
        table = params["embedding"]["word"]["embedding"]
        if compute_dtype is not None:
            table = table.astype(compute_dtype)
        x = jnp.take(table, input_ids, axis=0)
        if not cfg.use_rope:
            pos = params["embedding"]["position"]["embedding"][:s]
            x = x + pos.astype(x.dtype)[None]
    freqs = _rope_or_none(cfg, s)

    def dense(p, x):
        return jnp.dot(x, p["kernel"].astype(x.dtype)) \
            + p["bias"].astype(x.dtype)

    x = _scan_layers(x, params["layers"], cfg, freqs,
                     dense, dense, dense, dense, dropout_rng)
    with region("head"):
        return _ln(params["final_ln"], x, cfg.layer_norm_eps)


def gpt_loss_unsharded(params: Dict[str, Any], cfg: GPTConfig,
                       input_ids: jax.Array, labels: jax.Array,
                       *, dropout_rng: Optional[jax.Array] = None,
                       compute_dtype=None) -> jax.Array:
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    hidden = apply_gpt_unsharded(params, cfg, input_ids,
                                 dropout_rng=dropout_rng,
                                 compute_dtype=compute_dtype)
    with region("head"):
        table = params["embedding"]["word"]["embedding"]
        hidden, table_t = cast_args("matmul", hidden,
                                    table.astype(hidden.dtype).T)
        logits = jnp.dot(hidden, table_t)
    # fused xentropy (ref apex/contrib/xentropy): fp32 logsumexp inside
    # the kernel, no (b, s, V) log-softmax ever materialized — at
    # V=50304 that tensor dominated the unsharded step's HBM footprint
    with region("loss"):
        v = logits.shape[-1]
        nll = softmax_cross_entropy_loss(logits.reshape(-1, v),
                                         labels.reshape(-1))
        return nll.mean()


# ---------------------------------------------------------------------------
# pipeline adapter — {"embed", "stages", "head"} layout for the schedules
# ---------------------------------------------------------------------------

def gpt_to_pipeline_params(params: Dict[str, Any], cfg: GPTConfig,
                           pp: int, vpp: Optional[int] = None
                           ) -> Dict[str, Any]:
    """Reshape the stacked ``(L, ...)`` layer leaves into the schedules'
    stage stack: ``(pp, L/pp, ...)``, or ``(vpp, pp, L/(pp*vpp), ...)``
    with the reference's round-robin chunk order (chunk c on device
    c % pp, lane c // pp)."""
    L = cfg.num_layers
    chunks = pp * (vpp or 1)
    if L % chunks:
        raise ValueError(f"num_layers {L} not divisible by {chunks}")
    per = L // chunks

    def reshape(a):
        if vpp is None:
            return a.reshape((pp, per) + a.shape[1:])
        # layer l -> chunk l // per; chunk c -> (lane c // pp, dev c % pp)
        c_first = a.reshape((chunks, per) + a.shape[1:])
        return c_first.reshape((vpp, pp, per) + a.shape[1:])

    return {
        "embed": params["embedding"],
        "stages": jax.tree.map(reshape, params["layers"]),
        "head": {"final_ln": params["final_ln"],
                 "word": params["embedding"]["word"]},
    }


def gpt_pipeline_partition_specs(cfg: GPTConfig,
                                 vpp: Optional[int] = None):
    """PartitionSpecs matching ``gpt_to_pipeline_params``: stage leaves
    gain a leading ``pipe``-sharded stage dim (``(vpp, pp, per, ...)``
    with vpp) while keeping their Megatron TP shardings; the tied word
    table stays vocab-sharded over the model axis in BOTH its embed and
    head copies (a replicated table would make vocab-parallel CE
    double-count sum_exp — the forward is wrong, not just slow)."""
    from jax.sharding import PartitionSpec as P

    base = gpt_partition_specs(cfg)

    def stage_spec(p: P) -> P:
        tail = tuple(p)[1:]  # drop the stacked-L dim's entry
        if vpp is None:
            return P(ps.PIPE_AXIS, None, *tail)
        return P(None, ps.PIPE_AXIS, None, *tail)

    return {
        "embed": base["embedding"],
        "stages": jax.tree.map(stage_spec, base["layers"],
                               is_leaf=lambda x: isinstance(x, P)),
        "head": {"final_ln": base["final_ln"],
                 "word": base["embedding"]["word"]},
    }


def accumulate_tied_word_grads(grads: Dict[str, Any]) -> Dict[str, Any]:
    """Sum the two pipeline-layout copies of the tied word-table grad
    (embed lookup + LM head) into BOTH slots so the copies take
    identical updates and stay tied — Megatron's shared-embedding
    allreduce (ref: ``megatron/model/language_model.py ::
    Embedding`` shared-word-embeddings grad allreduce). Call after the
    pipeline schedule (which already psums embed/head grads over pipe)
    and before the optimizer step."""
    grads = dict(grads)
    tied = jax.tree.map(jnp.add, grads["embed"]["word"],
                        grads["head"]["word"])
    grads["embed"] = dict(grads["embed"], word=tied)
    grads["head"] = dict(grads["head"], word=tied)
    return grads


def gpt_pipeline_model(model: GPTModel) -> "PipelineModel":
    """A ``PipelineModel`` over the TP block — runs inside shard_map over
    BOTH the pipe and model axes (tp×pp)."""
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        PipelineModel,
    )
    from apex_tpu.transformer.tensor_parallel import (
        vocab_parallel_cross_entropy,
    )

    cfg = model.cfg

    def embed_fn(embed_params, mb):
        from apex_tpu.transformer.tensor_parallel import mappings

        ids = mb["input_ids"]
        x = model.embed.apply(embed_params["word"], ids)
        if not cfg.use_rope:
            pos = embed_params["position"]["embedding"][:ids.shape[1]]
            x = x + pos.astype(x.dtype)[None]
        if cfg.sequence_parallel:
            # hidden states travel the pipe seq-sharded; each stage's
            # Column layers gather / Row layers re-scatter internally
            x = mappings.scatter_to_sequence_parallel_region(x, 1)
        return x

    def stage_fn(stage_params, x):
        # under SP the hidden travels seq-sharded (s/tp): rotary angles
        # must span the GLOBAL sequence the Column gather reassembles
        s = x.shape[1]
        if cfg.sequence_parallel:
            s *= ps.get_tensor_model_parallel_world_size()
        freqs = _rope_or_none(cfg, s)

        def body(x, lp):
            return _block(lp, x, cfg, freqs,
                          model.qkv.apply, model.out.apply,
                          model.fc1.apply, model.fc2.apply), None

        x, _ = lax.scan(body, x, stage_params)
        return x

    def loss_fn(head_params, hidden, mb):
        from apex_tpu.transformer.tensor_parallel import mappings

        hidden = _ln(head_params["final_ln"], hidden, cfg.layer_norm_eps)
        if cfg.sequence_parallel:
            hidden = mappings.gather_from_sequence_parallel_region(
                hidden, True, 1)
            table = head_params["word"]["embedding"]
            logits = jnp.dot(hidden,
                             table.astype(hidden.dtype).T).astype(
                jnp.float32)
        else:
            logits = _tied_lm_logits(hidden,
                                     head_params["word"]["embedding"])
        return vocab_parallel_cross_entropy(logits, mb["labels"]).mean()

    return PipelineModel(embed_fn, stage_fn, loss_fn)
