"""Default partition-rule tables (GPT / serving KV cache).

One table per model family covers everything that family shards: the
parameter tree, the optimizer moments/master weights derived from it
(see :func:`apex_tpu.partition.rules.optimizer_state_specs`), and — for
GPT — the serving KV cache
(:func:`apex_tpu.serving.cache.paged_cache_partition_specs` matches its
``PagedKVCache`` template against the same table). The tables are written
OVERLAP-FREE: every leaf matches exactly one rule, which APX701
enforces for each registered tree, and the layouts reproduce the
hand-maintained reference (``models.gpt.gpt_partition_specs``) that
APX702 cross-checks them against.

Layout recap (Megatron over the ``model`` mesh axis):

- vocab-sharded word embeddings ``P(model, None)``; position /
  token-type tables replicated;
- Column-parallel qkv/fc1: output dim sharded (kernel last dim, bias);
- Row-parallel out/fc2: input dim sharded, bias replicated (added
  after the psum);
- layer norms replicated;
- GPT layer leaves carry a leading stacked-``num_layers`` dim (the
  ``lax.scan`` depth loop), hence the extra leading ``None``;
- KV cache: heads (the last axis of the pool ``(L, pages, page,
  heads * d)``, whose rows hold whole heads side by side) shard over
  ``model`` — each rank caches
  exactly the heads its head-major qkv column shard produces; slot
  lengths and block tables are replicated.
"""

from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state as ps

# KV-cache rules: the paths are the ``PagedKVCache`` namedtuple fields,
# matched at end-of-path so a model param ending differently can never
# collide: the pool ``(L, pages, page, heads * d)`` plus the block
# tables, which replicate (every rank indexes the same mapping).
_PAGED_KV_CACHE_RULES = (
    (r"(^|/)(k|v)$", P(None, None, None, ps.TENSOR_AXIS)),
    (r"(^|/)lengths$", P()),
    (r"(^|/)block_tables$", P()),
)


def paged_kv_cache_rules():
    """The paged serving-cache rules (``PagedKVCache``)."""
    return _PAGED_KV_CACHE_RULES


def kv_cache_quant_rules():
    """KV-cache rules for the INT8 paged pool: the paged rules plus the
    per-page-per-head fp32 scales ``(L, pages, heads)`` — heads (axis 2)
    shard over ``model`` like the pool's, so each rank's scale shard
    dequantizes exactly its local heads' pages."""
    return _PAGED_KV_CACHE_RULES + (
        (r"(^|/)(k|v)_scale$", P(None, None, ps.TENSOR_AXIS)),
    )


def gpt_quant_rules():
    """Rule table for the weight-only int8 GPT tree
    (``apex_tpu.quant.quantize_params``) plus the int8 paged cache.
    Kernel leaves keep their bf16 paths and specs (int8 swaps the
    dtype, never the layout); each ``scale`` rule is the kernel's spec
    with the CONTRACTED axis dropped — Column (qkv/fc1) scales follow
    their output channels onto ``model`` like the bias, Row (out/fc2)
    scales replicate, the word-table scale rides the vocab shard.
    Overlap-free against APX701 like the base table (the scale paths
    end differently from every kernel/bias path)."""
    t = ps.TENSOR_AXIS
    return (
        ("embedding/word/embedding", P(t, None)),
        ("embedding/word/scale", P(t)),
        ("embedding/position/embedding", P()),
        ("layers/(ln1|ln2)/(weight|bias)", P(None)),
        ("layers/qkv/kernel", P(None, None, t)),
        ("layers/qkv/(bias|scale)", P(None, t)),
        ("layers/out/kernel", P(None, t, None)),
        ("layers/out/(bias|scale)", P(None)),
        ("layers/fc1/kernel", P(None, None, t)),
        ("layers/fc1/(bias|scale)", P(None, t)),
        ("layers/fc2/kernel", P(None, t, None)),
        ("layers/fc2/(bias|scale)", P(None)),
        ("final_ln/(weight|bias)", P()),
    ) + kv_cache_quant_rules()


def gpt_rules():
    """Rule table for the GPT param tree (``models.gpt.init_gpt``) plus
    the serving KV cache. First match wins; table is overlap-free."""
    t = ps.TENSOR_AXIS
    return (
        ("embedding/word/embedding", P(t, None)),
        ("embedding/position/embedding", P()),
        ("layers/(ln1|ln2)/(weight|bias)", P(None)),
        ("layers/qkv/kernel", P(None, None, t)),
        ("layers/qkv/bias", P(None, t)),
        ("layers/out/kernel", P(None, t, None)),
        ("layers/out/bias", P(None)),
        ("layers/fc1/kernel", P(None, None, t)),
        ("layers/fc1/bias", P(None, t)),
        ("layers/fc2/kernel", P(None, t, None)),
        ("layers/fc2/bias", P(None)),
        ("final_ln/(weight|bias)", P()),
    ) + _PAGED_KV_CACHE_RULES


def draft_gpt_rules():
    """Rule table for the speculative DRAFT model's param tree + its
    lockstep KV cache (``serving.draft_model.DraftModel``). The draft
    is a GPT sharded on the SAME mesh as the target, so the layout is
    :func:`gpt_rules` minus the rows that can never match a draft tree:
    draft configs (``models.gpt.draft_gpt_tiny``/``draft_gpt_medium``)
    are RoPE-only — no ``embedding/position`` leaf (the lockstep draft
    cache is a ``PagedKVCache`` like the target's, which the table
    already covers). A rule that can never match would be an APX701
    dead-rule finding."""
    return tuple(rule for rule in gpt_rules()
                 if rule[0] != "embedding/position/embedding")
