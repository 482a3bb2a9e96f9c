"""Device milliseconds of the ``mixer`` group of regions in ``jit_prefill``
(every bucket) per thousand bucket tokens, counted as
``prefill_device_ms_per_ktok`` counts them: the recurrent mixer (Gated DeltaNet
/ Mamba-2) with its projections, convolution and kernel. The
``prefill_ms_per_ktok.*`` groups add up to the prefill programs' summed ``XLA
Ops`` time per thousand tokens (``benchmark/regions.py``). ``None`` where the
program carries no region or the traced span holds no prefill."""

from benchmark import regions


def read(run):
    return regions.prefill_ms_per_ktok(run, "mixer")
