#!/bin/bash
# The new cell once a seed, untraced: a line a run, then the spread of
# serve_tokens_per_s and setup_s over the set (the distance between the first
# and third quartile over the median, as statistics.quantiles gives them).
#   chiprun --timeout 3500 -- bash scripts/pr47/seeds.sh <tag> <budget s> <seed> ...
# With TREE=<dir> the runs are made from that checkout (.chip_tree/final: `git
# archive $(git write-tree)`, the committed files alone); OPTS are further
# arguments of every run.
tag=$1; budget=$2; shift 2
t0=$(date +%s); out=$PWD/chiprun_out/pr47/$tag; mkdir -p $out
cd ${TREE:-.}
cell=glm_5_3_flash.long_resident_sparse_decode
took=420
for seed in "$@"; do
  if [ $(( $(date +%s) - t0 + took + 20 )) -gt $budget ]; then echo "not started: $seed"; continue; fi
  began=$(date +%s)
  timeout 1500 python3 benchmark/run.py --workload $cell --seed $seed --seconds 30 --trace ${TRACE:-0} $OPTS > $out/$seed.out 2> $out/$seed.err
  echo "seed $seed rc=$? at $(( $(date +%s) - t0 )) s: $(tail -1 $out/$seed.out | cut -c1-700)"
  grep '"stage": "correct"' $out/$seed.out | python3 -c "
import json,sys
for l in sys.stdin:
    d=json.loads(l); print('   ', {n['number']: round(n['value'], 4) for n in d['numbers']}, 'routes', d.get('routes_agree'), 'picks', d.get('picks_agree'), 'ref_s', round(d.get('reference_s', 0)))"
  took=$(( $(date +%s) - began ))
done
python3 - $out <<'PY'
import glob, json, statistics, sys
rows = []
for path in sorted(glob.glob(sys.argv[1] + "/*.out")):
    last = [l for l in open(path) if l.startswith('{"correct"')]
    if last:
        d = json.loads(last[-1])
        rows.append((d["correct"], {k: v["value"] for k, v in d["metrics"].items()},
                     d["device"].get("memory_peak_bytes")))
print("runs", len(rows), "correct", [r[0] for r in rows], "peak", [r[2] for r in rows][:2])
for k in ("serve_tokens_per_s", "setup_s"):
    v = [r[1][k] for r in rows if k in r[1]]
    if len(v) >= 2:
        q = statistics.quantiles(v, n=4)
        print(k, "median", statistics.median(v), "spread", (q[2] - q[0]) / statistics.median(v), "values", v)
PY
