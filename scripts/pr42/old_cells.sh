#!/bin/bash
# PR 42: two cells that were there, whose configurations run code this PR
# touched (gated_delta.py: the hybrid; models/deepseek.py: DeepSeek), a pair
# sharing a seed each: .chip_tree/parent (the parent's apex_tpu/) then
# .chip_tree/final (git archive of the index), as proof_a.sh makes them.
#   chiprun --timeout 2400 -- bash scripts/pr42/old_cells.sh
out=$PWD/chiprun_out/p42f; mkdir -p $out
run() {  # tree cell seed
  (cd .chip_tree/$1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 30 --trace 0 > $out/$1_$2_$3.out 2> $out/$1_$2_$3.err)
  echo "$1 $2 $3 rc=$? $(tail -n 1 $out/$1_$2_$3.out | cut -c1-700)"
}
run parent olmo_hybrid_7b.long_prompt_decode 4200000013
run final olmo_hybrid_7b.long_prompt_decode 4200000013
run final deepseek_v3.resident_context_decode 4200000017
run parent deepseek_v3.resident_context_decode 4200000017
