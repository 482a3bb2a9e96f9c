"""How unevenly the router loads the experts held: the assignments the
fullest held expert got over the mean of the held experts, per expert layer,
the worst layer, over the window's decode steps (the program's ``moe_load``
counter, kept on the device and read before and after the window:
``counts["moe"]``). 1 is even. The fullest expert's rows are the longest
group of the grouped product and, in the deployment, the chip the others wait
for. A property of the seeded router, not a target."""


def read(run):
    moe = run["counts"].get("moe")
    if not moe or not moe.get("steps"):
        return None
    worst = [max(layer) * len(layer) / sum(layer) for layer in moe["load"]
             if sum(layer)]
    return max(worst) if worst else None
