"""Device milliseconds of the ``unscoped`` group of regions per execution of
``jit_train_step``: what lies under no region (copies the compiler adds,
parameters handed through). Summed over the traced span and divided by the
executions; the ``train_step_ms.*`` groups add up to the program's summed ``XLA
Ops`` time (``benchmark/regions.py``). ``None`` where the program carries no
region."""

from benchmark import regions


def read(run):
    return regions.train_step_ms(run, "unscoped")
