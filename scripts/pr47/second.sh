#!/bin/bash
# The second hand-in's one call, every run from `git archive` (.chip_tree/
# parent = the parent commit, .chip_tree/final = `git write-tree`, made
# before the call): the parent under this PR's benchmark files on the new
# cell (has to exit non-zero within seconds) and on one old cell, traced
# (has to give its result line); then the new cell from the committed files:
# once traced under an empty compile cache, then once a seed untraced.
#   chiprun --timeout 2400 -- bash scripts/pr47/second.sh <traced seed> <seed> ...
root=$PWD; cell=glm_5_3_flash.long_resident_sparse_decode
out=$root/chiprun_out/pr47/second; mkdir -p $out
over=$root/.chip_tree/parent_with_new_benchmark
rm -rf $over; cp -r $root/.chip_tree/parent $over
cp -r $root/.chip_tree/final/benchmark/. $over/benchmark/
cp -r $root/.chip_tree/final/tests/L0/run_benchmark/. $over/tests/L0/run_benchmark/
cp $root/.chip_tree/final/BENCHMARK.json $over/
began=$(date +%s)
(cd $over && timeout 300 python3 benchmark/run.py --workload $cell --seed 7 --seconds 30 --trace 0 > $out/parent_new_cell.out 2> $out/parent_new_cell.err)
echo "the parent on the new cell: rc=$? after $(( $(date +%s) - began )) s: $(tail -1 $out/parent_new_cell.err | cut -c1-300)"
began=$(date +%s)
(cd $over && timeout 600 python3 benchmark/run.py --workload gpt2_medium.offline_decode --seed 2147484101 --seconds 30 --trace 1 > $out/parent_old_cell.out 2> $out/parent_old_cell.err)
echo "the parent on gpt2_medium.offline_decode, traced: rc=$? after $(( $(date +%s) - began )) s: $(tail -1 $out/parent_old_cell.out | cut -c1-1500)"
traced=$1; shift
export TREE=$root/.chip_tree/final CACHE=$(mktemp -d)
bash scripts/pr47/cold.sh second 1 $traced
tail -1 $out/$traced.out | cut -c1-6000
bash scripts/pr47/cold.sh second 0 "$@"
python3 - $out $traced <<'PY'
import glob, json, statistics, sys
rows = []
for path in sorted(glob.glob(sys.argv[1] + "/2*.out")):
    last = [l for l in open(path) if l.startswith('{"correct"')]
    if last and sys.argv[2] not in path:
        d = json.loads(last[-1])
        rows.append((d["correct"], {k: v["value"] for k, v in d["metrics"].items()}))
print("untraced runs", len(rows), "correct", [r[0] for r in rows])
for k in ("serve_tokens_per_s", "setup_s"):
    v = [r[1][k] for r in rows if k in r[1]]
    if len(v) >= 2:
        q = statistics.quantiles(v, n=4)
        print(k, "median", statistics.median(v), "spread", (q[2] - q[0]) / statistics.median(v), "values", v)
PY
