"""Device milliseconds of the ``attention`` group of regions per execution of
``jit_decode``: ``attention`` and ``cache_write`` (norms, projections, the
paged / latent kernel, the output product, the rows scattered into the pool).
The MEAN over the traced span, so that the ``decode_ms.*`` groups add up to the
program's summed ``XLA Ops`` time per execution (``benchmark/regions.py``);
``decode_device_ms`` stays the median. ``None`` where the program carries no
region."""

from benchmark import regions


def read(run):
    return regions.decode_ms(run, "attention")
