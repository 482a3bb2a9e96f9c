"""Bytes one decode step of the ``nemotron_h`` model has to move: every
weight of the layers held once (bfloat16 matrices: embedding row lookups
aside, the quarter of the head whole; float32 norms, decay parameters and
biases) but of the routed experts only those HIT (a held expert that no slot's
token chose is never read), the Mamba-2 state of the active slots read and
written in every ``M`` layer with their convolution tails (float32 both), and
the K and V rows of the cache positions mapped, in the ``*`` layers only.
Decode is bound by memory: 2 operations per weight per slot against 2 bytes
per weight, 5.5 rows an expert.
"""


def _mamba(sz: dict) -> tuple:
    """(matrix elements, float32 elements) of one Mamba-2 layer."""
    h, nh, p = sz["hidden"], sz["mamba_heads"], sz["mamba_head_dim"]
    di = nh * p
    cc = di + 2 * sz["ssm_groups"] * sz["ssm_state"]
    return (h * (di + cc + nh) + di * h + sz["conv_kernel"] * cc,
            h + cc + 3 * nh + di)


def _attention(sz: dict) -> tuple:
    h, q = sz["hidden"], sz["heads"] * sz["head_dim"]
    return h * (q + 2 * sz["kv_heads"] * sz["head_dim"]) + q * h, h


def _experts_outside(sz: dict) -> tuple:
    """An expert layer without its routed experts: router, latent
    projections, shared expert."""
    h, lat, sf = sz["hidden"], sz["latent"], sz["shared_ffn"]
    return (h * sz["router_experts"] + 2 * h * lat + 2 * h * sf,
            h + sz["router_experts"])


def one_expert(sz: dict) -> int:
    return 2 * sz["latent"] * sz["expert_ffn"]


def weight_bytes(sz: dict, experts_hit: float) -> float:
    """``experts_hit``: held experts with at least one row, summed over the
    expert layers, of one step."""
    matrices = small = 0
    for count, (m, s) in ((sz["mamba_layers"], _mamba(sz)),
                          (sz["attention_layers"], _attention(sz)),
                          (sz["expert_layers"], _experts_outside(sz))):
        matrices += count * m
        small += count * s
    matrices += sz["hidden"] * sz["vocab"]      # the head; the embedding is
    small += sz["hidden"]                       # looked up by row
    return 2 * (matrices + experts_hit * one_expert(sz)) + 4 * small


def state_bytes(sz: dict, active_slots: int) -> int:
    nh, p, n = sz["mamba_heads"], sz["mamba_head_dim"], sz["ssm_state"]
    tail = (sz["conv_kernel"] - 1) * (nh * p + 2 * sz["ssm_groups"] * n)
    return sz["mamba_layers"] * active_slots * 2 * 4 * (nh * p * n + tail)


def kv_bytes(sz: dict, positions: int, cache_bytes: int = 2) -> int:
    return 2 * sz["attention_layers"] * sz["kv_heads"] * sz["head_dim"] \
        * cache_bytes * positions


def bytes_needed(sz: dict, mapped_positions: int, active_slots: int,
                 experts_hit: float) -> float:
    return weight_bytes(sz, experts_hit) + state_bytes(sz, active_slots) \
        + kv_bytes(sz, mapped_positions)
