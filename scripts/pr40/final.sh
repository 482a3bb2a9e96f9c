#!/bin/bash
# PR 40, review round, call 3: the proof from the committed files. Before it:
#   git add -A && rm -rf .chip_tree/final && mkdir -p .chip_tree/final \
#     && git archive $(git write-tree) | tar -x -C .chip_tree/final
#   chiprun --timeout 3000 -- bash scripts/pr40/final.sh
# Six seeds of the new cell (set 3; the first two with both controls), then
# one traced run with both controls. The tokens-altered run: altered.sh.
out=$PWD/chiprun_out/p40r; mkdir -p $out
cell=k_exaone_236b_a23b.long_context_reasoning
cd .chip_tree/final
i=0
for seed in 2999999963 3555555581 1212121217 2323232327 3434343437 1454545459; do
  ctl=0; if [ $i -lt 2 ]; then ctl=1; fi
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 30 --trace 0 --control $ctl > $out/set3_$seed.out 2> $out/set3_$seed.err
  echo "set3 $seed control=$ctl rc=$? $(tail -n 1 $out/set3_$seed.out | cut -c1-900)"
  grep -h '"stage": "control"' $out/set3_$seed.out | cut -c1-700
  i=$((i+1))
done
python3 benchmark/run.py --workload $cell --seed 4111111127 --seconds 30 --trace 1 --control 1 > $out/traced3.out 2> $out/traced3.err
echo "traced 4111111127 rc=$? $(tail -n 1 $out/traced3.out | cut -c1-3000)"
grep -h '"stage": "control"' $out/traced3.out | cut -c1-700
