"""Bytes one decode step has to read: every weight once (bfloat16 matrices
and biases, the tied embedding table for the output head, float32 LayerNorm
parameters) and the K and V rows of the cache positions actually mapped.
Decode is bound by memory: 2 operations per weight per slot against 2 bytes
per weight.
"""


def weight_bytes(sizes: dict) -> int:
    h, f, n = sizes["hidden"], sizes["ffn"], sizes["layers"]
    matrices = n * (4 * h * h + 2 * h * f) + sizes["padded_vocab"] * h
    biases = n * (3 * h + h + f + h)
    norms = (2 * n + 1) * 2 * h
    return 2 * (matrices + biases) + 4 * norms


def kv_bytes(sizes: dict, positions: int, cache_bytes: int = 2) -> int:
    return 2 * sizes["layers"] * sizes["hidden"] * cache_bytes * positions


def bytes_needed(sizes: dict, mapped_positions: int) -> int:
    return weight_bytes(sizes) + kv_bytes(sizes, mapped_positions)
