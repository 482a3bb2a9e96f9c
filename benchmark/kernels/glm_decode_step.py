"""Bytes one decode step of the ``glm5_next_text`` model has to move, and the
matrix parameters they are counted from, term by term. ``sz`` are the
reference's sizes (``reference/glm_5_3_flash.py::sizes_of``).

Every weight of the layers held once (bfloat16 matrices: embedding row
lookups aside, the slice of the head whole; the hyper-connections' maps,
norms, ``A_log``, ``dt_bias`` and router biases in float32) but of the routed
experts only those HIT; the recurrent state of every slot that decodes TWICE
(read and written, float32: 4 MiB a slot a layer at 64 heads of 128); the
indexer's pooled keys of the positions MAPPED (one key of ``index_width`` for
every ``index_pool`` positions); and the latent rows of the positions ATTENDED
(the groups the indexer picked and the tail), ONCE: the copy that gathers them
for the kernel is the program's choice, not a need. The convolution tails and
the residual streams (64 slots x 4 x 4096 float32) are left out: the share
reads a little low for it.
"""

_BF16, _F32 = 2, 4


def kda_layer(sz: dict) -> tuple:
    """(matrix elements, bfloat16 others, float32 elements) of one KDA mixer:
    ``[q~ | k~ | v~ | a1 | g1 | b]``, the two gates' widening matrices and
    ``W_o``; the convolution's taps; the norms, ``A_log`` and ``dt_bias``."""
    h, nh, d, r = sz["hidden"], sz["heads"], sz["head_dim"], \
        sz["kda_gate_rank"]
    w = nh * d
    return (h * (3 * w + 2 * r + nh) + 2 * r * w + w * h,
            sz["conv_kernel"] * 3 * w, h + nh + w + d)


def indexer(sz: dict) -> int:
    """The indexer's matrices: its queries from the query latent, its key and
    head weights from the stream."""
    ih, iw = sz["index_heads"], sz["index_width"]
    return sz["q_rank"] * ih * iw + sz["hidden"] * (iw + ih)


def sparse_layer(sz: dict) -> tuple:
    """(matrix elements, float32 elements) of one sparse-attention mixer:
    ``[c_q | c]``, ``W_qb``, ``W_kvb`` by head, ``W_o`` and the indexer."""
    h, nh = sz["hidden"], sz["heads"]
    return (h * (sz["q_rank"] + sz["kv_rank"]) + sz["q_rank"] * nh * sz["nope"]
            + sz["kv_rank"] * nh * (sz["nope"] + sz["v_dim"])
            + nh * sz["v_dim"] * h + indexer(sz),
            h + sz["q_rank"] + sz["kv_rank"] + 2 * sz["index_width"])


def hyper_connection(sz: dict) -> int:
    """One sub-layer's maps (float32): ``P`` and the biases."""
    n = sz["streams"]
    return (n * sz["hidden"] + 1) * (2 * n + n * n)


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
    ``deployment.parameters.matrix_sum``): the hyper-connections' ``P``
    among them, two a layer."""
    n = sz["streams"]
    return (sz["kda_layers"] * kda_layer(sz)[0]
            + sz["mla_layers"] * sparse_layer(sz)[0]
            + 2 * sz["depth"] * n * sz["hidden"] * (2 * n + n * n)
            + sz["dense_layers"] * dense_mlp(sz)
            + sz["expert_layers"] * (shared_expert(sz) + router(sz)
                                     + sz["experts_held"] * one_expert(sz))
            + vocabulary(sz))


def weight_bytes(sz: dict, experts_hit: float) -> float:
    """``experts_hit``: held experts with at least one row, summed over the
    expert layers, of one step."""
    kda, taps, kda_small = kda_layer(sz)
    sparse, sparse_small = sparse_layer(sz)
    matrices = (sz["kda_layers"] * (kda + taps) + sz["mla_layers"] * sparse
                + sz["dense_layers"] * dense_mlp(sz)
                + sz["expert_layers"] * (shared_expert(sz) + router(sz))
                + experts_hit * one_expert(sz)
                + sz["hidden"] * sz["vocab"])       # the head; the embedding
    small = (sz["kda_layers"] * kda_small           # is looked up by row
             + sz["mla_layers"] * sparse_small
             + 2 * sz["depth"] * hyper_connection(sz)
             + sz["depth"] * sz["hidden"]           # each layer's mlp_norm
             + sz["expert_layers"] * sz["router_experts"]
             + sz["hidden"])
    return _BF16 * matrices + _F32 * small


def state_bytes(sz: dict, slots: int) -> int:
    """The recurrent state of ``slots`` slots in every KDA layer, float32,
    once."""
    return _F32 * sz["kda_layers"] * slots * sz["heads"] \
        * sz["head_dim"] ** 2


def index_bytes(sz: dict, positions: float, cache_bytes: int = _BF16) -> float:
    """The pooled keys of ``positions`` mapped positions, every sparse
    layer."""
    return sz["mla_layers"] * sz["index_width"] * cache_bytes * positions \
        / sz["index_pool"]


def latent_bytes(sz: dict, attended: float, cache_bytes: int = _BF16) -> float:
    """The latent rows of ``attended`` positions, every sparse layer, once."""
    return sz["mla_layers"] * sz["latent_width"] * cache_bytes * attended


def bytes_needed(sz: dict, mapped_positions: float, attended_positions: float,
                 experts_hit: float, state_slots: int) -> float:
    return (weight_bytes(sz, experts_hit) + 2 * state_bytes(sz, state_slots)
            + index_bytes(sz, mapped_positions)
            + latent_bytes(sz, attended_positions))
