"""Bytes one decode step of the ``deepseek_v3`` model has to move: every
weight of the layers held once (bfloat16 matrices: embedding row lookups
aside, the slice of the head whole; float32 norms and router biases) but of
the routed experts only those HIT (a held expert that no slot's token chose
is never read), and the latent rows of the cache positions mapped, in every
layer (``latent_width`` numbers a position a layer, read once for all heads).
Decode is bound by memory: 2 operations per weight per slot against 2 bytes
per weight, 2 rows an expert; only the latent attention sits at the ridge
(``kernels/mla.py``).

And the grouped expert product's share of it: both products of one expert
layer, the fused gate and up matrix ``(hidden, 2 * expert_ffn)`` and the down
matrix ``(expert_ffn, hidden)``, by ``kernels/moe.py``'s counts.
"""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BF16, _F32 = 2, 4


def _attention(sz: dict) -> tuple:
    """(matrix elements, float32 elements) of one layer's MLA."""
    h, nh = sz["hidden"], sz["heads"]
    qr, kr = sz["q_rank"], sz["kv_rank"]
    return (h * (qr + sz["latent_width"])
            + qr * nh * (sz["nope"] + sz["rope"])
            + kr * nh * (sz["nope"] + sz["v_dim"])
            + nh * sz["v_dim"] * h, h + qr + kr)


def _dense_mlp(sz: dict) -> tuple:
    return 3 * sz["hidden"] * sz["dense_ffn"], sz["hidden"]


def _experts_outside(sz: dict) -> tuple:
    """An expert layer's MLP without its routed experts: router, shared
    expert."""
    h = sz["hidden"]
    return (h * sz["router_experts"] + 3 * h * sz["shared_ffn"],
            h + sz["router_experts"])


def one_expert(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["expert_ffn"]


def weight_bytes(sz: dict, experts_hit: float) -> float:
    """``experts_hit``: held experts with at least one row, summed over the
    expert layers, of one step."""
    matrices = small = 0
    for count, (m, s) in ((sz["layers"], _attention(sz)),
                          (sz["dense_layers"], _dense_mlp(sz)),
                          (sz["expert_layers"], _experts_outside(sz))):
        matrices += count * m
        small += count * s
    matrices += sz["hidden"] * sz["vocab"]      # the head; the embedding is
    small += sz["hidden"]                       # looked up by row
    return _BF16 * (matrices + experts_hit * one_expert(sz)) + _F32 * small


def latent_bytes(sz: dict, positions: int, cache_bytes: int = _BF16) -> int:
    return sz["layers"] * sz["latent_width"] * cache_bytes * positions


def bytes_needed(sz: dict, mapped_positions: int, experts_hit: float) -> float:
    return weight_bytes(sz, experts_hit) + latent_bytes(sz, mapped_positions)


def gmm_layer_bytes(sz: dict, rows: float, hit: float) -> float:
    """Both grouped products of one expert layer over ``rows`` assignments
    that hit ``hit`` of the experts held."""
    moe = load_module("kernels", "moe", BENCH)
    h, f = int(sz["hidden"]), int(sz["expert_ffn"])
    return moe.gmm_bytes(rows, hit, h, 2 * f) + moe.gmm_bytes(rows, hit, f, h)


def gmm_layer_flops(sz: dict, rows: float) -> float:
    moe = load_module("kernels", "moe", BENCH)
    h, f = int(sz["hidden"]), int(sz["expert_ffn"])
    return moe.gmm_flops(rows, h, 2 * f) + moe.gmm_flops(rows, f, h)
