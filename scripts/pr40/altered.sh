#!/bin/bash
# PR 40, review round, last call: tokens altered where they are staged, from
# the committed files (.chip_tree/final = git archive $(git write-tree)).
#   chiprun --timeout 600 -- bash scripts/pr40/altered.sh
out=$PWD/chiprun_out/p40r; mkdir -p $out
cd .chip_tree/final
python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed 888000113 --seconds 30 --trace 0 --option break_tokens=1 > $out/broken3.out 2> $out/broken3.err
echo "altered tokens 888000113 rc=$? $(tail -n 1 $out/broken3.out | cut -c1-1200)"
