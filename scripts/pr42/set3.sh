#!/bin/bash
# PR 42: a third set of six seeds of the new cell from .chip_tree/final (as
# proof_a.sh makes it), after set 2 spread 3.05% with a 1.87 s stall in it.
#   chiprun --timeout 2000 -- bash scripts/pr42/set3.sh
out=$PWD/chiprun_out/p42f; mkdir -p $out
cell=ling3_flash_vl.many_stream_reasoning
cd .chip_tree/final
for seed in 4242424243 1010101039 2121212149 3232323251 1717171727 2929292939; do
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 30 --trace 0 > $out/set3_$seed.out 2> $out/set3_$seed.err
  echo "set3 $seed rc=$? $(tail -n 1 $out/set3_$seed.out | cut -c1-700)"
  grep -h '"stage": "window"' $out/set3_$seed.out | cut -c1-1400
done
