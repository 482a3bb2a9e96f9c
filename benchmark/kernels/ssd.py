"""Bytes of the Mamba-2 decode kernel, from shapes
(``apex_tpu/transformer/functional/ssd.py``). Everything it touches is
float32. ``sizes`` are the reference's (``mamba_heads``, ``mamba_head_dim``,
``ssm_groups``, ``ssm_state``).

``apex_ssd_decode_fwd`` (one call per Mamba-2 layer per decode step): for each
active slot and head the ``(P, N)`` state is read and written once; beside it
come ``delta x`` and the head's decay, laid out ``(P,)`` each so that a head is
one lane of a tile, the output ``(P,)``, and per slot the ``B`` and ``C`` rows
of the ``G`` groups (``N`` each). Three multiplies and two adds per state
element on the vector unit: bound by memory.
"""

_F32 = 4


def decode_bytes(sizes: dict, active_slots: int) -> int:
    """Bytes one ``apex_ssd_decode_fwd`` call needs."""
    h, p = int(sizes["mamba_heads"]), int(sizes["mamba_head_dim"])
    g, n = int(sizes["ssm_groups"]), int(sizes["ssm_state"])
    per_slot = h * (2 * p * n + 3 * p) + 2 * g * n
    return _F32 * active_slots * per_slot
