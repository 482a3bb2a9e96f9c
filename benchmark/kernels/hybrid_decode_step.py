"""Bytes one decode step of the hybrid model has to move: every weight once
(bfloat16 matrices: embedding row lookups aside, the untied head whole;
float32 norms and decay parameters), the recurrent state of the active slots
read and written in every linear layer with their convolution tails (float32
both), and the K and V rows of the cache positions mapped, in the full
layers only. Decode is bound by memory: 2 operations per weight per slot
against 2 bytes per weight.
"""


def weight_bytes(sizes: dict) -> int:
    h, f = sizes["hidden"], sizes["ffn"]
    nh, dk, dv = (sizes["linear_heads"], sizes["linear_key_dim"],
                  sizes["linear_value_dim"])
    chan = nh * (2 * dk + dv)
    mlp = 3 * h * f
    linear = h * (chan + nh * dv) + h * 2 * nh + sizes["conv_kernel"] * chan \
        + nh * dv * h + mlp
    full = 4 * h * h + mlp
    matrices = sizes["linear_layers"] * linear + sizes["full_layers"] * full \
        + h * sizes["vocab"]                    # the head; the embedding
    #                                             is looked up by row
    small = sizes["linear_layers"] * (2 * h + dv + 2 * nh) \
        + sizes["full_layers"] * 4 * h + h
    return 2 * matrices + 4 * small


def state_bytes(sizes: dict, active_slots: int) -> int:
    nh, dk, dv = (sizes["linear_heads"], sizes["linear_key_dim"],
                  sizes["linear_value_dim"])
    tail = (sizes["conv_kernel"] - 1) * nh * (2 * dk + dv)
    return sizes["linear_layers"] * active_slots * 2 * (
        4 * (nh * dk * dv + tail))


def kv_bytes(sizes: dict, positions: int, cache_bytes: int = 2) -> int:
    return 2 * sizes["full_layers"] * sizes["hidden"] * cache_bytes \
        * positions


def bytes_needed(sizes: dict, mapped_positions: int,
                 active_slots: int) -> int:
    return weight_bytes(sizes) + state_bytes(sizes, active_slots) \
        + kv_bytes(sizes, mapped_positions)
