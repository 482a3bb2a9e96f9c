#!/bin/bash
# parent against change from the committed files of each:
#   .chip_tree/parent = git archive 132db13 (the parent commit), .chip_tree/change = git archive $(git write-tree)
# bash scripts/pr41/pairs.sh <cell> <side>:<trace>:<seed> ...   (a traced run of a BERT cell also lists its operations)
out=$PWD/chiprun_out/p41; mkdir -p $out
cell=$1; shift
for spec in "$@"; do
  IFS=: read side trace seed <<< "$spec"
  t0=$(date +%s)
  ( cd .chip_tree/$side && python3 benchmark/run.py --workload $cell --seed $seed --seconds 30 --trace $trace > $out/${cell}_${side}_${seed}_t$trace.out 2> $out/${cell}_${side}_${seed}_t$trace.err
    echo "$cell $side seed $seed trace $trace rc=$? $(( $(date +%s) - t0 )) s $(tail -n 1 $out/${cell}_${side}_${seed}_t$trace.out | cut -c1-2600)"
    if [ "$trace" = 1 ] && [ -f ../../scripts/pr41/ops.py ] && [[ $cell == bert* ]]; then
      python3 ../../scripts/pr41/ops.py $cell > $out/ops_${cell}_${side}.out 2> $out/ops_${cell}_${side}.err; head -64 $out/ops_${cell}_${side}.out
    fi )
done
