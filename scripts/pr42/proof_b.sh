#!/bin/bash
# PR 42, proof call B, from the committed files (.chip_tree/final as proof_a.sh
# makes it): set 2 of six seeds, then the resident wave's trace.
#   chiprun --timeout 3500 -- bash scripts/pr42/proof_b.sh
out=$PWD/chiprun_out/p42f; mkdir -p $out
cell=ling3_flash_vl.many_stream_reasoning
cd .chip_tree/final
for seed in 2200000033 3999999979 1234567891 2468013579 3210987654 1357924680; do
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 30 --trace 0 > $out/set2_$seed.out 2> $out/set2_$seed.err
  echo "set2 $seed rc=$? $(tail -n 1 $out/set2_$seed.out | cut -c1-900)"
  grep -h '"stage": "correct"' $out/set2_$seed.out | cut -c1-900
  grep -h '"stage": "window"' $out/set2_$seed.out | cut -c1-1600
done
python3 scripts/pr42/resident_trace.py 24 > $out/resident_trace.out 2> $out/resident_trace.err
echo "resident trace rc=$?"; grep -v "^E[01]\|^W[01]\|^I[01]" $out/resident_trace.out | cut -c1-3000 | tail -8
