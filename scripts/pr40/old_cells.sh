#!/bin/bash
# parent (with this PR's benchmark files laid over it) against the change, from the committed files of each
out=$PWD/chiprun_out/p40; mkdir -p $out
run() { # side cell seed trace tag
  ( cd .chip_tree/$1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 30 --trace $4 > $out/old_$5_$1_$3.out 2> $out/old_$5_$1_$3.err; echo "$5 $1 seed $3 trace $4 rc=$? $(tail -n 1 $out/old_$5_$1_$3.out | cut -c1-330)" )
}
# the parent on the new cell: has to fail cleanly, at once
( cd .chip_tree/parent && /usr/bin/time -f "%e s" python3 benchmark/run.py --workload k_exaone_236b_a23b.long_context_reasoning --seed 5 --seconds 30 --trace 0 > $out/parent_newcell.out 2> $out/parent_newcell.err; echo "parent on the new cell rc=$? $(tail -n 2 $out/parent_newcell.err | tr '\n' ' ')" )
run parent gpt2_medium.offline_decode 4000000007 0 gpt
run change gpt2_medium.offline_decode 4000000007 0 gpt
run change gpt2_medium.offline_decode 4000000009 0 gpt
run parent gpt2_medium.offline_decode 4000000009 0 gpt
run parent gpt2_medium.offline_decode 4000000011 1 gpt
run change gpt2_medium.offline_decode 4000000011 1 gpt
run parent olmo_hybrid_7b.long_prompt_decode 4000000013 0 hybrid
run change olmo_hybrid_7b.long_prompt_decode 4000000013 0 hybrid
run parent nemotron3_super_120b_a12b.many_slot_decode 4000000015 0 nemotron
run change nemotron3_super_120b_a12b.many_slot_decode 4000000015 0 nemotron
run change deepseek_v3.resident_context_decode 4000000017 0 deepseek
run parent deepseek_v3.resident_context_decode 4000000017 0 deepseek
