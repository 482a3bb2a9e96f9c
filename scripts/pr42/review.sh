#!/bin/bash
# PR 42, the review session's one chip call, from the committed files
# (.chip_tree/final = `git archive $(git write-tree)`, as proof_a.sh says):
# leaf_check.py on this PR's cell (the tail as a ring, no barrier) and on the
# two cells whose models shift their tails; set 4 of six seeds (the first two
# with both controls); one traced run; then as many seeds of set 5 as fit in
# $1 seconds (the budget that was left).
#   chiprun --timeout 2700 -- bash scripts/pr42/review.sh 2600
budget=${1:-2600}; t0=$(date +%s)
out=$PWD/chiprun_out/p42r; mkdir -p $out
cell=ling3_flash_vl.many_stream_reasoning
cd .chip_tree/final
for c in $cell olmo_hybrid_7b.long_prompt_decode nemotron3_super_120b_a12b.many_slot_decode; do
  python3 scripts/pr42/leaf_check.py --cell $c > $out/leaf_$c.out 2> $out/leaf_$c.err
  rc=$?; echo "leaf_check $c rc=$rc after $(( $(date +%s) - t0 )) s"; cat $out/leaf_$c.out
  if [ $rc -ne 0 ]; then tail -n 5 $out/leaf_$c.err | cut -c1-400; fi
  if [ $rc -ne 0 ] && [ $c = $cell ]; then exit 1; fi
done
run() {  # name seed control trace
  python3 benchmark/run.py --workload $cell --seed $2 --seconds 30 --trace $4 --control $3 > $out/$1_$2.out 2> $out/$1_$2.err
  echo "$1 $2 control=$3 trace=$4 rc=$? at $(( $(date +%s) - t0 )) s $(tail -n 1 $out/$1_$2.out | cut -c1-4500)"
  grep -h '"stage": "\(control\|correct\|mapped\)"' $out/$1_$2.out | cut -c1-900
  grep -h '"stage": "window"' $out/$1_$2.out | cut -c1-1600
}
i=0
for seed in 2987654321 3141592653 1618033989 2236067977 1732050807 3316624791; do
  ctl=0; if [ $i -lt 2 ]; then ctl=1; fi
  run set4 $seed $ctl 0
  if [ $i -eq 0 ] && ! tail -n 1 $out/set4_$seed.out | grep -q '"correct": true'; then
    tail -n 8 $out/set4_$seed.err | cut -c1-400; exit 1
  fi
  i=$((i+1))
done
run traced 4123456789 0 1
for seed in 1414213563 2645751311 3605551275 1259921049 2080083823 2884499141; do
  if [ $(( $(date +%s) - t0 + 180 )) -gt $budget ]; then echo "set5 stops before $seed: $(( $(date +%s) - t0 )) s of $budget"; break; fi
  run set5 $seed 0 0
done
