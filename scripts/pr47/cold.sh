#!/bin/bash
# The cell once a seed under an EMPTY compile cache, as the first run of a
# check finds it: the run's whole wall time against the 360 s at which the
# driver stops a run (what refused this PR's first hand-in), stage by stage.
#   chiprun --timeout 1500 -- bash scripts/pr47/cold.sh <tag> <trace 0|1> <seed> ...
# With TREE=<dir> from that checkout; with CACHE=<dir> under that compile
# cache, kept (the first run of the call cold, the others warm).
tag=$1; trace=$2; shift 2
out=$PWD/chiprun_out/pr47/$tag; mkdir -p $out
cd ${TREE:-.}
for seed in "$@"; do
  cache=${CACHE:-$(mktemp -d)}
  start=$(date +%s)
  JAX_COMPILATION_CACHE_DIR=$cache timeout 900 python3 benchmark/run.py \
    --workload glm_5_3_flash.long_resident_sparse_decode --seed $seed \
    --seconds 30 --trace $trace > $out/$seed.out 2> $out/$seed.err
  echo "seed $seed trace $trace rc=$? wall $(( $(date +%s) - start )) s"
  [ -n "$CACHE" ] || rm -rf $cache
  python3 - $out/$seed.out <<'PY'
import json, sys
for line in open(sys.argv[1]):
    if not line.startswith("{"):
        continue
    d = json.loads(line)
    if "stage" in d:
        keep = {k: d[k] for k in ("seconds", "ms_per_1000_prompt_tokens",
                                  "judge_lengths", "compile_events",
                                  "reference_s", "requests_judged",
                                  "step_ms_p50") if k in d}
        print("  ", d["stage"], d["t"], keep)
    elif "correct" in d:
        print("   result", d["correct"],
              {k: v["value"] for k, v in d["metrics"].items()},
              d["device"].get("memory_peak_bytes"),
              {k: v["value"] for k, v in d.get("compared", {}).items()})
PY
done
