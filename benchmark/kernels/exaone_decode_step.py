"""Bytes one decode step of the ``exaone_moe`` model has to move: every weight
of the layers held once (bfloat16 matrices: embedding row lookups aside, the
slice of the head whole; float32 norms and router biases) but of the routed
experts only those HIT (a held expert that no slot's token chose is never
read); the K and V rows of the cache positions mapped, in every FULL layer;
and in every SLIDING layer the rows its window leaves (``window_positions``:
``min(positions held + 1, window)`` summed over the slots). Decode is bound by
memory: 2 operations per weight per slot against 2 bytes per weight, 4 rows an
expert.

And the parameter count the cut states, term by term (``parameters``), and
the grouped expert product's two products of one expert layer, which are
``kernels/deepseek_decode_step.py``'s counts at this model's widths
(``gmm_layer_bytes``, ``gmm_layer_flops``: the sizes' keys are the same).
"""

import os

from benchmark.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BF16, _F32 = 2, 4

_deepseek = load_module("kernels", "deepseek_decode_step", BENCH)
gmm_layer_bytes = _deepseek.gmm_layer_bytes
gmm_layer_flops = _deepseek.gmm_layer_flops


def attention(sz: dict) -> tuple:
    """(matrix elements, float32 elements) of one layer's attention: the
    fused q, k, v projection and the output's; the sub-layer's norm and the
    per-head norms of q and k."""
    h, hd = sz["hidden"], sz["head_dim"]
    q_width = sz["heads"] * hd
    return (h * (q_width + 2 * sz["kv_heads"] * hd) + q_width * h,
            h + 2 * hd)


def dense_mlp(sz: dict) -> tuple:
    return 3 * sz["hidden"] * sz["dense_ffn"], sz["hidden"]


def experts_outside(sz: dict) -> tuple:
    """An expert layer's MLP without its routed experts: router, shared
    expert; the sub-layer's norm and the router's bias."""
    h = sz["hidden"]
    return (h * sz["router_experts"] + 3 * h * sz["shared_ffn"],
            h + sz["router_experts"])


def one_expert(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["expert_ffn"]


def parameters(sz: dict, experts: int = None) -> tuple:
    """(matrix parameters, float32 parameters) of the share held, embedding
    and head among them; ``experts``: routed experts an expert layer, the
    experts held when not given."""
    experts = sz["experts_held"] if experts is None else experts
    matrices = small = 0
    for count, (m, s) in ((sz["layers"], attention(sz)),
                          (sz["dense_layers"], dense_mlp(sz)),
                          (sz["expert_layers"], experts_outside(sz))):
        matrices += count * m
        small += count * s
    matrices += sz["expert_layers"] * experts * one_expert(sz)
    return matrices + 2 * sz["hidden"] * sz["vocab"], small + sz["hidden"]


def weight_bytes(sz: dict, experts_hit: float) -> float:
    """``experts_hit``: held experts with at least one row, summed over the
    expert layers, of one step. The embedding is looked up by row."""
    matrices, small = parameters(sz, experts=0)
    matrices -= sz["hidden"] * sz["vocab"]
    return _BF16 * (matrices + experts_hit * one_expert(sz)) + _F32 * small


def cache_bytes(sz: dict, mapped_positions: float, window_positions: float,
                cache_bytes: int = _BF16) -> float:
    row = 2 * sz["kv_heads"] * sz["head_dim"] * cache_bytes    # K and V
    return row * (sz["full_layers"] * mapped_positions
                  + sz["window_layers"] * window_positions)


def bytes_needed(sz: dict, mapped_positions: float, window_positions: float,
                 experts_hit: float) -> float:
    return weight_bytes(sz, experts_hit) \
        + cache_bytes(sz, mapped_positions, window_positions)
