"""Device milliseconds of the ``amp`` group of regions per execution of
``jit_train_step``: the model cast, loss scaling, the unscale and the
finiteness reduction. Summed over the traced span and divided by the
executions; the ``train_step_ms.*`` groups add up to the program's summed ``XLA
Ops`` time (``benchmark/regions.py``). ``None`` where the program carries no
region."""

from benchmark import regions


def read(run):
    return regions.train_step_ms(run, "amp")
