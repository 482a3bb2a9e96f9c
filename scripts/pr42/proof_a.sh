#!/bin/bash
# PR 42, proof call A, from the committed files. Before it:
#   git add -A && rm -rf .chip_tree && mkdir -p .chip_tree/final .chip_tree/parent \
#     && git archive $(git write-tree) | tar -x -C .chip_tree/final \
#     && git archive HEAD | tar -x -C .chip_tree/parent \
#     && rm -rf .chip_tree/parent/benchmark .chip_tree/parent/tests/L0/run_benchmark \
#     && cp -r .chip_tree/final/benchmark .chip_tree/parent/ \
#     && cp -r .chip_tree/final/tests/L0/run_benchmark .chip_tree/parent/tests/L0/ \
#     && cp .chip_tree/final/BENCHMARK.json .chip_tree/parent/
#   chiprun --timeout 3500 -- bash scripts/pr42/proof_a.sh
# The parent on the new cell (has to exit non-zero within seconds), set 1 of
# six seeds (the first two with both controls), one traced run, one run with
# tokens altered where they are staged.
out=$PWD/chiprun_out/p42f; mkdir -p $out
cell=ling3_flash_vl.many_stream_reasoning
cd .chip_tree/parent
t=$(date +%s)
python3 benchmark/run.py --workload $cell --seed 5 --seconds 30 --trace 0 > $out/parent.out 2> $out/parent.err
echo "parent on the new cell rc=$? after $(( $(date +%s) - t )) s: $(tail -n 2 $out/parent.err | cut -c1-300)"
cd ../final
i=0
for seed in 2999999963 3555555581 1212121217 2323232327 3434343437 1454545459; do
  ctl=0; if [ $i -lt 2 ]; then ctl=1; fi
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 30 --trace 0 --control $ctl > $out/set1_$seed.out 2> $out/set1_$seed.err
  echo "set1 $seed control=$ctl rc=$? $(tail -n 1 $out/set1_$seed.out | cut -c1-900)"
  grep -h '"stage": "\(control\|correct\)"' $out/set1_$seed.out | cut -c1-900
  grep -h '"stage": "window"' $out/set1_$seed.out | cut -c1-1600
  i=$((i+1))
done
python3 benchmark/run.py --workload $cell --seed 4111111127 --seconds 30 --trace 1 > $out/traced.out 2> $out/traced.err
echo "traced 4111111127 rc=$? $(tail -n 1 $out/traced.out | cut -c1-4500)"
grep -h '"stage": "\(correct\|mapped\)"' $out/traced.out | cut -c1-900
python3 benchmark/run.py --workload $cell --seed 888000113 --seconds 30 --trace 0 --option break_tokens=1 > $out/altered.out 2> $out/altered.err
echo "altered 888000113 rc=$? $(tail -n 1 $out/altered.out | cut -c1-900)"
