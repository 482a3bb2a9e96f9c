#!/bin/bash
mkdir -p chiprun_out/p40
for seed in 2718281828 1000000007; do
  python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed $seed --seconds 30 --trace 1 > chiprun_out/p40/traced_$seed.out 2> chiprun_out/p40/traced_$seed.err
  echo "traced $seed rc=$? $(tail -n 1 chiprun_out/p40/traced_$seed.out | cut -c1-300)"
  python3 - <<'PY' > chiprun_out/p40/steps_$seed.txt 2>&1
import sys
sys.path.insert(0, ".")
from benchmark import trace, spans
import glob, json
path = trace.find(".bench_trace/k_exaone_236b_a23b.long_context_reasoning")
red = trace.reduce_file(path)
times = red.program_times("jit_decode")
import statistics
print("jit_decode n", len(times), "median", statistics.median(times), "mean", sum(times)/len(times), "max", max(times))
print("by quarter", [round(1e3*statistics.median(times[i*len(times)//4:(i+1)*len(times)//4]),3) for i in range(4)])
rows = spans.load(path)
byname = {}
for r in rows:
    byname.setdefault(r[0] if isinstance(r[0], str) else str(r[0]), []).append(r)
print({k: len(v) for k, v in byname.items()})
PY
done
python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed 777000111 --seconds 30 --trace 0 --option break_tokens=1 > chiprun_out/p40/broken_777000111.out 2> chiprun_out/p40/broken_777000111.err
echo "broken rc=$? $(tail -n 1 chiprun_out/p40/broken_777000111.out | cut -c1-900)"
