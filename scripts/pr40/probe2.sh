#!/bin/bash
# PR 40, review round, call 2: one run of the new cell under
# scripts/probe_ticks.py (with its ticker process, the steal column and the
# main thread's schedstat) and both controls: the first readings of the
# program whose prompt path attends in float32 against the reference that
# rounds K and V to the stated cache_dtype.
#   chiprun --timeout 1500 -- bash scripts/pr40/probe2.sh
out=chiprun_out/p40r; mkdir -p $out
cell=k_exaone_236b_a23b.long_context_reasoning
seed=2200000033
python3 scripts/probe_ticks.py --workload $cell --seed $seed --seconds 30 --trace 0 --control 1 > $out/probe2_$seed.out 2> $out/probe2_$seed.err
echo "probe2 $seed rc=$? $(tail -n 1 $out/probe2_$seed.out | cut -c1-900)"
grep -h '"stage": "correct"\|"stage": "control"\|"stage": "window"\|"stage": "warm"\|"stage": "resident"' $out/probe2_$seed.out | cut -c1-1500
python3 - <<'PY'
import json
d = json.load(open("chiprun_out/probe/ticks_2200000033.json"))
print(d["ticks"], d["tick_ms_p50"], d["pressure_cpu"], d["cgroup_cpu_before"], d["cgroup_cpu_after"])
print("ticker gaps", d["ticker_gaps_monotonic_from_to"])
for t in d["slow"]:
    if t["wall_ms"] < 450:
        t.pop("sampled_frames"); print(json.dumps(t))
for w in d["sampler_wakes_after_a_gap"][-12:]:
    print(json.dumps(w)[:1500])
PY
