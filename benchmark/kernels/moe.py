"""Operations and bytes of the grouped expert product, from rows, experts hit
and widths (``apex_tpu/transformer/functional/moe.py``).

``apex_moe_gmm_fwd`` multiplies ``rows`` rows, sorted by expert, each by its
expert's ``(k, n)`` matrix. Read are the rows (bfloat16) and the matrix of
every expert that has at least one row, ONCE each (an expert without a row is
never visited and its matrix never leaves HBM: counting the experts HELD
instead of the experts HIT would count weights that were not read); written
is one output row of ``n`` per row, in ``out_bytes`` a number (bfloat16 after
the first product, float32 after the second). With a handful of rows an
expert, as when every slot decodes one token, the bytes bound it; with a
prompt's hundreds, the operations.

An expert layer runs two products, ``latent -> expert_ffn`` (with
``relu(.)^2``) and back: :func:`layer_bytes` and :func:`layer_flops` are both.
"""

_BF16, _F32 = 2, 4


def gmm_bytes(rows: float, hit: float, k: int, n: int,
              out_bytes: int = _F32) -> float:
    return rows * k * _BF16 + hit * k * n * _BF16 + rows * n * out_bytes


def gmm_flops(rows: float, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def layer_bytes(sizes: dict, rows: float, hit: float) -> float:
    """Both products of one expert layer over ``rows`` assignments that hit
    ``hit`` of the experts held."""
    lat, f = int(sizes["latent"]), int(sizes["expert_ffn"])
    return gmm_bytes(rows, hit, lat, f, _BF16) + gmm_bytes(rows, hit, f, lat)


def layer_flops(sizes: dict, rows: float) -> float:
    lat, f = int(sizes["latent"]), int(sizes["expert_ffn"])
    return gmm_flops(rows, lat, f) + gmm_flops(rows, f, lat)
