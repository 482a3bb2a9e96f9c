#!/bin/bash
# PR 40, review round: which part of a tick takes 105-120 ms, 0-3 times a
# window? Two runs of the new cell under scripts/probe_ticks.py (the working
# tree), the first with both controls, whose readings are the first under the
# reference that rounds K and V to the stated cache_dtype.
#   chiprun --timeout 1500 -- bash scripts/pr40/probe.sh
out=chiprun_out/p40r; mkdir -p $out
cell=k_exaone_236b_a23b.long_context_reasoning
python3 scripts/probe_ticks.py --workload $cell --seed 3210987654 --seconds 30 --trace 0 --control 1 > $out/probe_3210987654.out 2> $out/probe_3210987654.err
echo "probe 3210987654 rc=$? $(tail -n 1 $out/probe_3210987654.out | cut -c1-700)"
grep -h '"stage": "control"' $out/probe_3210987654.out | cut -c1-900
python3 scripts/probe_ticks.py --workload $cell --seed 1618033988 --seconds 30 --trace 0 > $out/probe_1618033988.out 2> $out/probe_1618033988.err
echo "probe 1618033988 rc=$? $(tail -n 1 $out/probe_1618033988.out | cut -c1-700)"
python3 - <<'PY'
import json
for seed in (3210987654, 1618033988):
    d = json.load(open(f"chiprun_out/probe/ticks_{seed}.json"))
    print(seed, d["ticks"], d["tick_ms_p50"], d["cgroup_cpu_before"], d["cgroup_cpu_after"])
    for t in d["slow"]:
        if t["wall_ms"] < 400:
            print(json.dumps(t))
PY
