#!/bin/bash
# the one-chip BERT cell from the working tree: bash scripts/pr41/cell.sh <tag> <trace> <seed>...
out=$PWD/chiprun_out/p41; mkdir -p $out
tag=$1; trace=$2; shift 2
for seed in "$@"; do
  t0=$(date +%s)
  python3 benchmark/run.py --workload bert_large.pretrain_s128 --seed $seed --seconds 30 --trace $trace > $out/${tag}_${seed}_t$trace.out 2> $out/${tag}_${seed}_t$trace.err
  echo "$tag seed $seed trace $trace rc=$? $(( $(date +%s) - t0 )) s $(tail -n 1 $out/${tag}_${seed}_t$trace.out | cut -c1-2500)"
done
