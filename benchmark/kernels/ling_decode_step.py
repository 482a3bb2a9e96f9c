"""Bytes one decode step of the ``bailing_hybrid`` model has to move, and the
matrix parameters they are counted from, term by term. ``sz`` are the
reference's sizes (``reference/ling3_flash_vl.py::sizes_of``).

Every weight of the layers held once (bfloat16 matrices: embedding row
lookups aside, the slice of the head whole; float32 norms, ``A_log``,
``dt_bias`` and router biases) but of the routed experts only those HIT (a
held expert that no slot's token chose is never read); the recurrent state of
every slot that decodes TWICE (read and written, float32: the KDA step's 4
MiB a slot a layer); and the latent rows of the cache positions mapped, in
the MLA layers (``latent_width`` numbers a position, read once for all
heads). The convolution tails (0.1 MB a slot a layer) are left out: the share
reads a little low for it. Decode is bound by memory throughout: 2 operations
per weight per slot against 2 bytes per weight, 4 rows an expert, and the
latent attention at 32 heads 60 operations a byte against the chip's 240.
"""

_BF16, _F32 = 2, 4


def kda_layer(sz: dict) -> tuple:
    """(matrix elements, bfloat16 others, float32 elements) of one KDA
    mixer: ``[q~ | k~ | v~ | a | g | b]`` and ``W_o``; the convolution's
    taps; the norms, ``A_log`` and ``dt_bias``."""
    h, nh, d = sz["hidden"], sz["heads"], sz["head_dim"]
    w = nh * d
    return (h * (5 * w + nh) + w * h, sz["conv_kernel"] * 3 * w,
            h + nh + w + d)


def mla_layer(sz: dict) -> tuple:
    """(matrix elements, float32 elements) of one MLA mixer: ``[q | c | k_pe
    | gate]``, ``W_kvb`` by head, ``W_o``."""
    h, nh = sz["hidden"], sz["heads"]
    return (h * (nh * (sz["nope"] + sz["rope"]) + sz["latent_width"] + nh)
            + sz["kv_rank"] * nh * (sz["nope"] + sz["v_dim"])
            + nh * sz["v_dim"] * h, h + sz["kv_rank"])


def dense_mlp(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["dense_ffn"]


def shared_expert(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["shared_ffn"]


def router(sz: dict) -> int:
    return sz["hidden"] * sz["router_experts"]


def one_expert(sz: dict) -> int:
    return 3 * sz["hidden"] * sz["expert_ffn"]


def vocabulary(sz: dict) -> int:
    """Embedding and head."""
    return 2 * sz["vocab"] * sz["hidden"]


def matrix_parameters(sz: dict) -> int:
    """The matrix parameters this chip holds (the configuration file's
    ``deployment.parameters.matrix_sum``)."""
    return (sz["kda_layers"] * kda_layer(sz)[0]
            + sz["mla_layers"] * mla_layer(sz)[0]
            + sz["dense_layers"] * dense_mlp(sz)
            + sz["expert_layers"] * (shared_expert(sz) + router(sz)
                                     + sz["experts_held"] * one_expert(sz))
            + vocabulary(sz))


def weight_bytes(sz: dict, experts_hit: float) -> float:
    """``experts_hit``: held experts with at least one row, summed over the
    expert layers, of one step."""
    kda, taps, kda_small = kda_layer(sz)
    mla, mla_small = mla_layer(sz)
    matrices = (sz["kda_layers"] * (kda + taps) + sz["mla_layers"] * mla
                + sz["dense_layers"] * dense_mlp(sz)
                + sz["expert_layers"] * (shared_expert(sz) + router(sz))
                + experts_hit * one_expert(sz)
                + sz["hidden"] * sz["vocab"])       # the head; the embedding
    small = (sz["kda_layers"] * kda_small           # is looked up by row
             + sz["mla_layers"] * mla_small
             + sz["depth"] * sz["hidden"]           # each layer's mlp_norm
             + sz["expert_layers"] * sz["router_experts"]
             + sz["hidden"])
    return _BF16 * matrices + _F32 * small


def state_bytes(sz: dict, slots: int) -> int:
    """The recurrent state of ``slots`` slots in every KDA layer, float32,
    once."""
    return _F32 * sz["kda_layers"] * slots * sz["heads"] \
        * sz["head_dim"] ** 2


def latent_bytes(sz: dict, positions: int, cache_bytes: int = _BF16) -> int:
    return sz["mla_layers"] * sz["latent_width"] * cache_bytes * positions


def bytes_needed(sz: dict, mapped_positions: int, experts_hit: float,
                 state_slots: int) -> float:
    return (weight_bytes(sz, experts_hit) + 2 * state_bytes(sz, state_slots)
            + latent_bytes(sz, mapped_positions))
