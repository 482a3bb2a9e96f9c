"""Operations and bytes of the absorbed latent-attention decode kernel, from
shapes (``apex_tpu/transformer/functional/mla_attention.py``). ``sizes`` are
the reference's (``heads``, ``kv_rank``, ``latent_width``).

``apex_mla_decode_fwd`` (one call per layer per decode step): every cache
position a slot has mapped is one row of ``latent_width`` numbers (the normed
latent and the roped shared key: 576 as published), read ONCE and used by all
heads: a score against the whole row and a value update with its leading
``kv_rank`` columns, two operations a multiply-add. 128 heads x 2 x (576 +
512) = 278,528 operations over 1,152 bytes in bfloat16: 242 an operation a
byte against the v5e's 197 T / 819 G = 240, the chip's ridge. What is counted
is what the algorithm needs: the zeros that pad a row to whole 128-lane tiles
(640) and the second bfloat16 term of the queries and probabilities are the
implementation's, and show as a share below 100.
"""

_BF16 = 2


def decode_flops(sizes: dict, positions: float) -> float:
    """One call (one layer) over ``positions`` mapped rows of all slots."""
    return 2.0 * int(sizes["heads"]) * (
        int(sizes["latent_width"]) + int(sizes["kv_rank"])) * positions


def decode_bytes(sizes: dict, positions: float,
                 cache_bytes: int = _BF16) -> float:
    return float(int(sizes["latent_width"]) * cache_bytes) * positions
