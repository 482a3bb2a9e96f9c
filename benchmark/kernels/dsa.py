"""Operations and bytes of learned-sparse latent attention's two kernels in a
decode step, from shapes (``apex_tpu/transformer/functional/sparse_index.py``
and ``mla_attention.py``). ``sizes`` are the reference's (``index_heads``,
``index_width``, ``index_pool``, ``heads``, ``kv_rank``, ``latent_width``).

``apex_dsa_index_fwd`` (one call per sparse layer per decode step): every
WHOLE group of ``index_pool`` positions a slot has mapped is one pooled key
of ``index_width`` numbers (256 bytes in bfloat16), read once and scored by
all ``index_heads`` heads, two operations a multiply-add: 32 x 2 x 128 = 8,192
operations over 256 bytes, 32 a byte against the v5e's ridge of 240: bound by
memory, and by the page-sized fetches (4 keys, 1 KB) the bytes come in.

``apex_mla_decode_fwd`` over the rows PICKED: every attended position is one
latent row of ``latent_width`` numbers, read once by all heads: a score
against the whole row and a value update with its ``kv_rank`` columns. 64
heads x 2 x (512 + 512) = 131,072 operations over 1,024 bytes, 128 a byte:
bound by memory. What is counted is what the kernel is GIVEN (the rows the
indexer picked and the tail), not what the slots have mapped: the copy that
gathers them is the program's choice and counted nowhere.
"""

_BF16 = 2


def index_flops(sizes: dict, positions: float) -> float:
    """One call over the whole groups of ``positions`` mapped positions."""
    return 2.0 * int(sizes["index_heads"]) * int(sizes["index_width"]) \
        * positions / int(sizes["index_pool"])


def index_bytes(sizes: dict, positions: float,
                cache_bytes: int = _BF16) -> float:
    return float(int(sizes["index_width"]) * cache_bytes) * positions \
        / int(sizes["index_pool"])


def attend_flops(sizes: dict, attended: float) -> float:
    """One call over ``attended`` rows of all slots."""
    return 2.0 * int(sizes["heads"]) * (
        int(sizes["latent_width"]) + int(sizes["kv_rank"])) * attended


def attend_bytes(sizes: dict, attended: float,
                 cache_bytes: int = _BF16) -> float:
    return float(int(sizes["latent_width"]) * cache_bytes) * attended
