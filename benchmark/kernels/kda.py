"""Operations and bytes of the two Kimi-Delta-Attention kernels, from shapes
(``apex_tpu/transformer/functional/gated_delta.py``, the per-channel calls).
Everything they touch is float32. ``sizes`` are the reference's (``heads``,
``head_dim``: key and value channels a head are the same number).

``apex_kda_decode_fwd`` (one call per KDA layer per decode step): for each
slot and head the ``(d, d)`` state is read and written once, 4 MiB a slot a
layer at 32 heads of 128; the rows beside it are small: q, k, beta k and the
per-channel decay (``d`` each), beta v and the output (``d`` each). A few
operations per state element on the vector unit: bound by memory.

``apex_kda_chunk_fwd`` (one call per KDA layer per prefill): the chunk walk of
``apex_gdn_chunk_fwd`` (``kernels/gated_delta.py``) with the chunk's decay a
row of ``d`` numbers instead of one: per chunk of ``chunk`` tokens and head,
three products with the state (``2 chunk d d`` each) and one inside the chunk
(``2 chunk^2 d``); read are ``W_v`` and the output's worth (``chunk d``
each), ``W_k``, ``Q``, ``K^T`` (``chunk d`` each), ``A`` (``chunk^2``) and
the chunk's decay (``d``); the state stays on the chip and leaves once per
call. What XLA does before the walk (the WY form's strips and its triangular
inverse) is not the kernel's.
"""

CHUNK = 64
_F32 = 4


def _dims(sizes: dict):
    return int(sizes["heads"]), int(sizes["head_dim"])


def decode_bytes(sizes: dict, slots: int) -> int:
    """Bytes one ``apex_kda_decode_fwd`` call needs for ``slots`` slots."""
    h, d = _dims(sizes)
    return _F32 * slots * h * (2 * d * d + 6 * d)


def chunk_flops(sizes: dict, tokens: int, chunk: int = CHUNK) -> int:
    """Operations of ``apex_kda_chunk_fwd`` over ``tokens`` positions of one
    layer (whole chunks: a bucket is a multiple of the chunk)."""
    h, d = _dims(sizes)
    return h * tokens * (6 * d * d + 2 * chunk * d)


def chunk_bytes(sizes: dict, tokens: int, calls: int = 1,
                chunk: int = CHUNK) -> int:
    """Bytes of ``apex_kda_chunk_fwd`` over ``tokens`` positions in ``calls``
    calls of one layer."""
    h, d = _dims(sizes)
    return _F32 * h * (tokens * (5 * d + chunk) + tokens // chunk * d
                       + calls * d * d)
