"""Device milliseconds of the ``head`` group of regions per execution of
``jit_decode``: ``head`` and ``embed`` (the lookup, the final norm, the
logits). The MEAN over the traced span, so that the ``decode_ms.*`` groups add
up to the program's summed ``XLA Ops`` time per execution
(``benchmark/regions.py``); ``decode_device_ms`` stays the median. ``None``
where the program carries no region."""

from benchmark import regions


def read(run):
    return regions.decode_ms(run, "head")
