"""Optional accelerants (ref: ``apex/contrib``).

The reference gates each contrib package behind a build flag
(``setup.py --xentropy --fast_multihead_attn ...``); here everything is
importable — kernels compile on TPU and interpret on CPU.

- :mod:`xentropy` — fused softmax-cross-entropy (no materialized softmax)
- ``multihead_attn`` lives as the flash-attention kernels in
  ``apex_tpu.transformer.functional.flash_attention`` (SURVEY §2b: the
  fast_multihead_attn rows are subsumed by the tiled pair); ``fmha``'s
  short-sequence case (128 / 256 positions, bidirectional) is a kernel
  pair of its own there, ``apex_fmha_fwd`` / ``apex_fmha_bwd``, which
  ``flash_attention_packed`` runs on a fused q / k / v projection.
"""

from apex_tpu.contrib import xentropy  # noqa: F401
