"""Operations the forward and backward passes of a transformer encoder or
decoder require per token: 6 per matrix-multiply parameter (2 forward, 4
backward) plus 12 * layers * seq * hidden for the attention scores and the
weighted sum (2 matmuls of 2 * seq * hidden each, forward and twice
backward). Recomputed operations do not count, nor do embeddings looked up.
"""


def matmul_params(sizes: dict) -> int:
    h, f = sizes["hidden"], sizes["ffn"]
    per_layer = 4 * h * h + 2 * h * f          # qkv, out, fc1, fc2
    head = h * h + sizes["vocab"] * h          # MLM transform, tied decoder
    return sizes["layers"] * per_layer + head


def flops_per_token(sizes: dict, seq: int) -> float:
    return 6.0 * matmul_params(sizes) \
        + 12.0 * sizes["layers"] * seq * sizes["hidden"]
