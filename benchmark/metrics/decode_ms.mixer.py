"""Device milliseconds of the ``mixer`` group of regions per execution of
``jit_decode``: the recurrent mixer (Gated DeltaNet / Mamba-2) with its
projections, convolution and kernel. The MEAN over the traced span, so that the
``decode_ms.*`` groups add up to the program's summed ``XLA Ops`` time per
execution (``benchmark/regions.py``); ``decode_device_ms`` stays the median.
``None`` where the program carries no region."""

from benchmark import regions


def read(run):
    return regions.decode_ms(run, "mixer")
