"""Shared Pallas-kernel plumbing (padding, masking constants).

One home for the helpers every kernel module needs, so fixes to
padding/masking behavior apply everywhere at once.
"""

import jax.numpy as jnp

# Masked-score constant. Finite (not -inf) so running-max arithmetic
# (m_prev - m_cur etc.) never produces inf-inf NaNs; exp(-1e30 - m)
# underflows to exactly 0 for any realistically-scaled logits, matching
# the reference kernels' additive -10000 for fp16-scale inputs.
NEG_INF = -1e30

# Every Pallas kernel of the package, by the ``name=`` of its
# ``pl.pallas_call`` (and of the ``jax.named_scope`` around it, which is
# what a device trace shows). The one list:
# ``tests/L0/run_utils/test_kernel_names.py`` holds it to the sources, and
# ``chip_smoke.py`` refuses a kernel it does not name.
KERNEL_NAMES = (
    "apex_flash_bwd_dkv", "apex_flash_bwd_dq", "apex_flash_fwd",
    "apex_dsa_index_fwd",
    "apex_fmha_bwd", "apex_fmha_fwd", "apex_gdn_chunk_fwd", "apex_gdn_decode_fwd",
    "apex_kda_chunk_fwd", "apex_kda_decode_fwd", "apex_ln_bwd",
    "apex_ln_bwd_coldx", "apex_ln_bwd_colsum", "apex_ln_fwd",
    "apex_mla_decode_fwd", "apex_moe_gmm_fwd", "apex_mt_adagrad",
    "apex_mt_adam", "apex_mt_axpby", "apex_mt_l2norm", "apex_mt_lamb",
    "apex_mt_novograd", "apex_mt_scale", "apex_mt_sgd",
    "apex_paged_decode_fwd", "apex_paged_window_decode_fwd",
    "apex_softmax_bwd",
    "apex_softmax_causal_fwd", "apex_softmax_masked_fwd",
    "apex_ssd_decode_fwd", "apex_w8_matmul", "apex_w8_matmul_bias",
    "apex_w8_matmul_nk", "apex_xentropy_bwd", "apex_xentropy_fwd")


def pad_axis(x, size: int, axis: int, value=0.0):
    """Zero-pad (or ``value``-pad) ``axis`` of ``x`` up to ``size``."""
    if x.shape[axis] == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pads, constant_values=value)


def dimsem(*sem):
    """``pltpu.CompilerParams`` with grid dimension semantics:
    ``"parallel"`` = revisit-free tiles Mosaic may pipeline/partition
    freely (measured ~12% on the flash kernels); any dim that
    accumulates into scratch or a revisited output block MUST stay
    ``"arbitrary"`` — on megacore parts a ``"parallel"`` dim may be
    split across TensorCores, and a shared revisited output would lose
    one core's partial writes."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=sem)
