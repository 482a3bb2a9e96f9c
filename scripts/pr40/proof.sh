#!/bin/bash
out=$PWD/chiprun_out/p40; mkdir -p $out
t=$SECONDS
( cd .chip_tree/parent && python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed 5 --seconds 30 --trace 0 > $out/parent_newcell.out 2> $out/parent_newcell.err; echo "parent on the new cell rc=$? after $((SECONDS - t)) s: $(tail -n 1 $out/parent_newcell.err)" )
( cd .chip_tree/final && python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed 2999999929 --seconds 30 --trace 1 --control 1 > $out/final_traced.out 2> $out/final_traced.err; echo "final traced rc=$? $(tail -n 1 $out/final_traced.out | cut -c1-3000)" )
( cd .chip_tree/final && python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed 2999999957 --seconds 30 --trace 0 > $out/final_untraced.out 2> $out/final_untraced.err; echo "final untraced rc=$? $(tail -n 1 $out/final_untraced.out | cut -c1-600)" )
