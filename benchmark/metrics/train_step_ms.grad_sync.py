"""Device milliseconds of the ``grad_sync`` group of regions per execution of
``jit_train_step``: the TensorCore's own work inside
``DistributedDataParallel.allreduce_grads`` (casts, the division, the
collectives' start and done); what is EXPOSED of the collectives stays
``allreduce_exposed_ms``. Summed over the traced span and divided by the
executions; the ``train_step_ms.*`` groups add up to the program's summed ``XLA
Ops`` time (``benchmark/regions.py``). ``None`` where the program carries no
region."""

from benchmark import regions


def read(run):
    return regions.train_step_ms(run, "grad_sync")
