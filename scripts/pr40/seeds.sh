#!/bin/bash
# usage: seeds.sh <tag> <control-on-first-n> seed...
tag=$1; nctl=$2; shift 2
mkdir -p chiprun_out/p40
i=0
for seed in "$@"; do
  ctl=0; if [ $i -lt $nctl ]; then ctl=1; fi
  python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed $seed --seconds 30 --trace 0 --control $ctl > chiprun_out/p40/${tag}_$seed.out 2> chiprun_out/p40/${tag}_$seed.err
  echo "seed $seed rc=$? $(tail -n 1 chiprun_out/p40/${tag}_$seed.out | cut -c1-400)"
  i=$((i+1))
done
