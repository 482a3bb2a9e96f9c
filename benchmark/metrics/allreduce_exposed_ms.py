"""Per training step, the time in collectives during which no other
operation runs on that chip (averaged over the chips)."""


def read(run):
    trace = run["trace"]
    if len(trace.chips) < 2:
        return None
    steps = trace.executions("jit_train_step")
    if not steps or trace.collective_s() <= 0:
        return None
    return 1e3 * trace.collective_exposed_s() / steps
