#!/bin/bash
# Pairs of runs of cells of BENCHMARK.json on the chip, in ONE call: the
# parent (.chip_tree/parent = `git archive` of the parent commit) against the
# change (.chip_tree/final = `git archive $(git write-tree)`: the committed
# files), each pair sharing a seed, the side that runs first alternating
# (parent, change, change, parent, ...). Both trees keep their compiled
# programs in one directory (JAX_COMPILATION_CACHE_DIR if the machine sets it,
# else .chip_tree/cache), so a cell's FIRST run compiles for both: an item
# w:<cell>:<seed> is such a run of the parent, read by nobody. A trailing "t"
# on a seed traces that pair.
#   chiprun --timeout 3600 -- bash scripts/pairs.sh <budget s> [w:]<cell>:<seed>[t] ...
# A run that cannot end inside the budget (by the cell's last run here, 300 s
# before it has one) is not started. Outputs: chiprun_out/pairs/<cell>_<side>_
# <seed>_t<0|1>.{out,err}; a line a run here, then medians by cell and side.
budget=$1; shift
t0=$(date +%s); root=$PWD
out=$root/chiprun_out/pairs; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$root/.chip_tree/cache}
declare -A took
say() { python3 - "$@" <<'PY'
import json, statistics, sys
def last(path):
    rows = [l for l in open(path) if l.startswith('{"')]
    d = json.loads(rows[-1]) if rows else {}
    m = {k: v["value"] for k, v in d.get("metrics", {}).items()}
    return d, {k: m[k] for k in ("train_tokens_per_s_per_chip",
               "serve_tokens_per_s", "itl_ms_p95", "setup_s",
               "sched_step_ms.serve", "decode_device_ms",
               "device_idle_pct.serve") if k in m}
if sys.argv[1] == "--one":
    d, m = last(sys.argv[2])
    print(json.dumps({"correct": d.get("correct"), **m,
                      "device": (d.get("device") or {}).get("kind")}))
else:   # medians by side over the runs given as side=path ...
    by = {}
    for item in sys.argv[1:]:
        side, path = item.split("=", 1)
        for k, v in last(path)[1].items():
            by.setdefault(k, {}).setdefault(side, []).append(v)
    print(json.dumps({k: {s: [statistics.median(v), len(v)]
                          for s, v in sides.items()}
                      for k, sides in by.items()}))
PY
}
run() {  # cell side seed trace
  local dir=$root/.chip_tree/parent name=$out/$1_$2_$3_t$4 began=$(date +%s)
  [ $2 = change ] && dir=$root/.chip_tree/final
  (cd $dir && timeout 1500 python3 benchmark/run.py --workload $1 --seed $3 --seconds 30 --trace $4 > $name.out 2> $name.err)
  echo "$1 $2 $3 trace=$4 rc=$? at $(( $(date +%s) - t0 )) s: $(say --one $name.out)"
  took[$1]=$(( $(date +%s) - began ))
}
i=0; cells=""
for item in "$@"; do
  warm=0; case $item in w:*) warm=1; item=${item#w:};; esac
  cell=${item%%:*}; seed=${item#*:}; trace=0
  case $seed in *t) trace=1; seed=${seed%t};; esac
  cost=$(( ${took[$cell]:-300} * (2 - warm) + 30 ))
  if [ $(( $(date +%s) - t0 + cost )) -gt $budget ]; then echo "not started: $item ($(( $(date +%s) - t0 )) s of $budget, needs about $cost)"; continue; fi
  if [ $warm = 1 ]; then run $cell parent $seed 0; mv $out/${cell}_parent_${seed}_t0.out $out/${cell}_warm_${seed}.txt; continue; fi
  case " $cells " in *" $cell "*) ;; *) cells="$cells $cell";; esac
  if [ $(( i % 2 )) -eq 0 ]; then run $cell parent $seed $trace; run $cell change $seed $trace
  else run $cell change $seed $trace; run $cell parent $seed $trace; fi
  i=$((i+1))
done
for cell in $cells; do
  args=""; for f in $out/${cell}_parent_*_t0.out; do args="$args parent=$f"; done
  for f in $out/${cell}_change_*_t0.out; do args="$args change=$f"; done
  echo "$cell medians [value, runs]: $(say $args)"
done
