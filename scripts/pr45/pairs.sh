#!/bin/bash
# PR 45: pairs of runs of the two cells that run apex_mla_decode_fwd, on one
# chip in one call: the parent (.chip_tree/parent = `git archive` of the
# parent commit) against the change (.chip_tree/final = `git archive
# $(git write-tree)`: the committed files), each pair sharing a seed, the side
# that runs first alternating (parent, change, change, parent, ...). The first
# pair of a cell may compile (its set-up is `first_setup_s`); a trailing "t"
# on a seed traces that pair. An item D:<seed> / L:<seed> is not a pair of
# runs but the cell's START alone, split by what JAX did (warm_split.py), the
# parent's then the change's: put it behind a pair, so that neither compiles.
# Before the items the kernel alone, the change's against the parent's at
# both cells' shapes (kernel_probe.py; PROBE=--sanity leaves the other sizes
# out, PROBE=no the whole step): a wrong result stops the call before it
# costs an hour.
#   chiprun --timeout 3600 -- bash scripts/pr45/pairs.sh <budget s> <d|l|D|L>:<seed>[t] ...
# (d = deepseek_v3.resident_context_decode, l =
# ling3_flash_vl.many_stream_reasoning). A pair that cannot end inside the
# budget is not started. Outputs: chiprun_out/p45/<cell>_<side>_<seed>_t<0|1>
# .{out,err} and split_<cell>_<side>_<seed>.out; a line a run here; `python3
# scripts/pr45/summary.py <cell>` reads the pairs.
budget=$1; shift
PROBE=${PROBE-}
t0=$(date +%s); root=$PWD
out=$root/chiprun_out/p45; mkdir -p $out
if [ "$PROBE" != no ]; then
  timeout 600 python3 scripts/pr45/kernel_probe.py --tree=.chip_tree/final --parent=.chip_tree/parent $PROBE --tag=$(date +%H%M) || { echo "the kernel alone is wrong or no faster: stop"; exit 1; }
fi
run() {  # cell side seed trace
  local dir=$root/.chip_tree/$2 name=$out/$1_$2_$3_t$4
  [ $2 = change ] && dir=$root/.chip_tree/final
  (cd $dir && timeout 1200 python3 benchmark/run.py --workload $1 --seed $3 --seconds 30 --trace $4 > $name.out 2> $name.err)
  echo "$1 $2 $3 trace=$4 rc=$? at $(( $(date +%s) - t0 )) s: $(python3 $root/scripts/pr45/summary.py --one $name.out | cut -c1-1500)"
}
split() {  # cell side seed
  local dir=$root/.chip_tree/$2 name=$out/split_$1_$2_$3
  [ $2 = change ] && dir=$root/.chip_tree/final
  (cd $dir && timeout 600 python3 $root/scripts/pr45/warm_split.py --workload $1 --seed $3 > $name.out 2> $name.err)
  echo "split $1 $2 $3 rc=$? at $(( $(date +%s) - t0 )) s: $(grep '^{"tree"' $name.out | cut -c1-3000)"
}
i=0
for item in "$@"; do
  case $item in
    D:*) split deepseek_v3.resident_context_decode parent ${item#*:}; split deepseek_v3.resident_context_decode change ${item#*:}; continue;;
    L:*) split ling3_flash_vl.many_stream_reasoning parent ${item#*:}; split ling3_flash_vl.many_stream_reasoning change ${item#*:}; continue;;
  esac
  seed=${item#*:}; trace=0; case $seed in *t) trace=1; seed=${seed%t};; esac
  case $item in
    d:*) cell=deepseek_v3.resident_context_decode; cost=290;;
    l:*) cell=ling3_flash_vl.many_stream_reasoning; cost=440;;
  esac
  ls $out/${cell}_*.out > /dev/null 2>&1 || cost=$(( cost + cost / 2 + 60 ))  # the cell's first pair may compile
  [ $trace = 1 ] && cost=$(( cost + 60 ))
  if [ $(( $(date +%s) - t0 + cost )) -gt $budget ]; then echo "not started: $item ($(( $(date +%s) - t0 )) s of $budget, a pair takes about $cost)"; continue; fi
  if [ $(( i % 2 )) -eq 0 ]; then run $cell parent $seed $trace; run $cell change $seed $trace
  else run $cell change $seed $trace; run $cell parent $seed $trace; fi
  i=$((i+1))
done
for cell in deepseek_v3.resident_context_decode ling3_flash_vl.many_stream_reasoning; do
  python3 $root/scripts/pr45/summary.py $cell | grep '"metric"'
done
