"""Operations and bytes of the two Gated DeltaNet kernels, from shapes
(``apex_tpu/transformer/functional/gated_delta.py``). Everything they touch is
float32. ``sizes`` are the reference's (``linear_heads``, ``linear_key_dim``,
``linear_value_dim``).

``apex_gdn_decode_fwd`` (one call per linear layer per decode step): for each
active slot and head the ``(d_k, d_v)`` state is read and written once; the
rows beside it are small: q, k and beta k (``d_k`` each), beta v and alpha
(``d_v`` each), the output (``d_v``). A few operations per state element on
the vector unit: bound by memory.

``apex_gdn_chunk_fwd`` (one call per linear layer per prefill): per chunk of
``chunk`` tokens and head, three products with the state (``W_k S``, ``Q S``,
``K^T U``: ``2 chunk d_k d_v`` each) and one inside the chunk (``A U``: ``2
chunk^2 d_v``); read are ``W_v`` and the output's worth (``chunk d_v`` each),
``W_k``, ``Q``, ``K^T`` (``chunk d_k`` each), ``A`` (``chunk^2``) and the
chunk's decay (``d_v``); the state stays on the chip and leaves once per call.
"""

CHUNK = 64
_F32 = 4


def _dims(sizes: dict):
    return (int(sizes["linear_heads"]), int(sizes["linear_key_dim"]),
            int(sizes["linear_value_dim"]))


def decode_bytes(sizes: dict, active_slots: int) -> int:
    """Bytes one ``apex_gdn_decode_fwd`` call needs."""
    h, dk, dv = _dims(sizes)
    per_head = 2 * dk * dv + 3 * dk + 2 * dv + dv
    return _F32 * active_slots * h * per_head


def chunk_flops(sizes: dict, tokens: int, chunk: int = CHUNK) -> int:
    """Operations of ``apex_gdn_chunk_fwd`` over ``tokens`` positions of one
    layer (whole chunks: a bucket is a multiple of the chunk)."""
    h, dk, dv = _dims(sizes)
    return h * tokens * (6 * dk * dv + 2 * chunk * dv)


def chunk_bytes(sizes: dict, tokens: int, calls: int = 1,
                chunk: int = CHUNK) -> int:
    """Bytes of ``apex_gdn_chunk_fwd`` over ``tokens`` positions in ``calls``
    calls of one layer."""
    h, dk, dv = _dims(sizes)
    per_token = 2 * dv + 3 * dk + chunk
    per_chunk = dv
    return _F32 * h * (tokens * per_token + tokens // chunk * per_chunk
                       + calls * dk * dv)
