"""Device milliseconds of the ``head`` group of regions in ``jit_prefill``
(every bucket) per thousand bucket tokens, counted as
``prefill_device_ms_per_ktok`` counts them: ``head`` and ``embed`` (the lookup,
the final norm, the logits). The ``prefill_ms_per_ktok.*`` groups add up to the
prefill programs' summed ``XLA Ops`` time per thousand tokens
(``benchmark/regions.py``). ``None`` where the program carries no region or the
traced span holds no prefill."""

from benchmark import regions


def read(run):
    return regions.prefill_ms_per_ktok(run, "head")
