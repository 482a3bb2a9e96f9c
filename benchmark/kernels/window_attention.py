"""Operations and bytes of the paged decode-attention kernel over K/V heads
fewer than the query heads, from shapes
(``apex_tpu/transformer/functional/paged_attention.py``): the FULL layers'
call (``apex_paged_decode_fwd``, over every position a slot has mapped) and
the sliding layers' (``apex_paged_window_decode_fwd``, over the ``window``
positions at most that its lower bound leaves). ``sizes`` are the reference's
(``heads``, ``kv_heads``, ``head_dim``).

One call (one layer) reads every position's K row and V row ONCE, ``kv_heads *
head_dim`` numbers each (2 x 1024 x 2 B = 4 KB in bfloat16 as published), and
every query head takes a score against its K/V head's part of the K row and a
value update with its part of the V row, two operations a multiply-add: ``4 *
heads * head_dim`` operations a position (32,768 at 64 heads of 128) over
4,096 bytes, 8 an operation a byte against the v5e's ridge of 240: the bytes
bound it, by a factor of thirty. What is counted is what the algorithm needs:
the three bfloat16 pieces the queries and the probabilities go to the MXU in,
and the block of zeros that lays every query head against the whole row, are
the implementation's and show as a share below 100.
"""

_BF16 = 2


def decode_flops(sizes: dict, positions: float) -> float:
    """One call (one layer) over ``positions`` rows of all slots."""
    return 4.0 * int(sizes["heads"]) * int(sizes["head_dim"]) * positions


def decode_bytes(sizes: dict, positions: float,
                 cache_bytes: int = _BF16) -> float:
    return 2.0 * int(sizes["kv_heads"]) * int(sizes["head_dim"]) \
        * cache_bytes * positions


def seconds_needed(sizes: dict, positions: float, peaks: dict) -> float:
    """One call's roofline: the larger of its two bounds."""
    return max(decode_flops(sizes, positions) / peaks["bf16_flops_per_s"],
               decode_bytes(sizes, positions) / peaks["hbm_bytes_per_s"])
