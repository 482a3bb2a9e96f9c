"""The device operations of ``jit_train_step`` in the trace a ``--trace 1``
run of a BERT cell left under ``.bench_trace/``, by region and by kind (an
operation's name without its number, with its shape), in ms per execution:
the table of PERF.md section 5. ``python3 scripts/pr41/ops.py <cell>``."""

import collections
import os
import re
import sys

sys.path.insert(0, os.getcwd())

from benchmark import regions, trace  # noqa: E402


def main(cell):
    path = trace.find(os.path.join(".bench_trace", cell))
    table = regions.load(path)
    program = "jit_train_step"
    runs = table.executions[program]
    kinds = collections.Counter()
    calls = collections.Counter()
    by_region = collections.Counter()
    for (prog, name), op in table.ops.items():
        if prog != program:
            continue
        kind = re.sub(r"^%([a-zA-Z_\-]+?)[.\d]* ", r"\1 ",
                      trace.short(name, 120))
        key = (op.region, "bwd" if regions.backward(op.tf_op) else "fwd",
               kind)
        kinds[key] += op.seconds
        calls[key] += op.count
        by_region[key[:2]] += op.seconds
    print(f"{program}: {runs} executions, "
          f"{1e3 * table.module_s[program] / runs:.3f} ms each")
    for key, s in sorted(by_region.items(), key=lambda kv: -kv[1]):
        print(f"  {str(key[0]):12s} {key[1]}  {1e3 * s / runs:8.3f} ms")
    for key, s in kinds.most_common(60):
        print(f"{1e3 * s / runs:8.3f} ms  {calls[key] / runs:6.1f} calls  "
              f"{str(key[0]):10s} {key[1]}  {key[2]}")


if __name__ == "__main__":
    main(sys.argv[1])
