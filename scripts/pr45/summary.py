"""Reads the runs ``pairs.sh`` left in ``chiprun_out/p45/``: set-up by the
runner's stages, the end-to-end numbers and the kernel's readings, a line a
run, then medians by side, their difference and the pairs the change won.
``--one <file>`` prints one run's line; ``<cell>`` every run of that cell.
A run whose `warm` stage compiled (more than 40 s) counts as a FIRST run: its
set-up is ``first_setup_s``, and its pair stays out of every other median."""

import glob
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = ("mla_decode_kernel_ms_per_decode", "mla_decode_roofline_pct",
        "decode_device_ms", "decode_ms.attention", "sched_step_ms.serve",
        "deepseek_decode_hbm_pct", "ling_decode_hbm_pct",
        "device_idle_pct.serve")


def read(path):
    stages, got = {}, {}
    for line in open(path):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if "stage" in d:
            stages[d["stage"]] = d
        elif "metrics" in d:
            got["correct"] = d.get("correct")
            got.update({k: v["value"] for k, v in d["metrics"].items()
                        if k in KEYS + ("setup_s", "serve_tokens_per_s")})
            got["device"] = d.get("device")
    t = {k: v["t"] for k, v in stages.items()}
    if "resident" in t:
        got["stages"] = {"start": round(t["start"], 2),
                         "built": round(t["built"] - t["start"], 2),
                         "warm": round(t["warm"] - t["built"], 2),
                         "resident": round(t["resident"] - t["warm"], 2)}
        got["first"] = got["stages"]["warm"] > 40 or \
            got["stages"]["built"] > 15
    if "window" in stages:
        got.setdefault("setup_s", stages["window"]["values"]["setup_s"])
        got.setdefault("serve_tokens_per_s",
                       stages["window"]["values"]["serve_tokens_per_s"])
        got["step_ms_p50"] = stages["window"]["step_ms_p50"]
    return got


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    if sys.argv[1] == "--one":
        print(json.dumps(read(sys.argv[2])))
        return
    cell = sys.argv[1]
    runs = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "chiprun_out", "p45", f"{cell}_*_t[01].out"))):
        side, seed, trace = re.match(
            r".*_(parent|change)_(\d+)_t([01])\.out", path).groups()
        runs[side, seed, trace] = read(path)
    for key, got in sorted(runs.items()):
        print(*key, json.dumps(got))
    # a pair one side of which compiled counts for ``first setup_s`` alone
    cold = {seed for (_, seed, _), got in runs.items() if got.get("first")}
    for got in runs.values():
        for stage, seconds in got.get("stages", {}).items():
            got["stage " + stage] = seconds
    for name, first, trace in (("setup_s", False, None),
                               ("first setup_s", True, None),
                               *((f"stage {k}", False, None) for k in
                                 ("start", "built", "warm", "resident")),
                               ("serve_tokens_per_s", False, "0"),
                               ("step_ms_p50", False, "0"),
                               *((k, False, "1") for k in KEYS)):
        key = name[6:] if name.startswith("first ") else name
        sides = {}
        for (side, seed, t), got in runs.items():
            if key in got and (trace is None or t == trace) and (
                    got.get("first") if first else seed not in cold):
                sides.setdefault(side, {})[seed] = got[key]
        if len(sides) < 2:
            continue
        p, c = sides["parent"], sides["change"]
        both = sorted(set(p) & set(c))
        line = {"metric": name, "n": (len(p), len(c)),
                "parent_median": statistics.median(p.values()),
                "change_median": statistics.median(c.values())}
        line["change_less_parent"] = (line["change_median"]
                                      - line["parent_median"])
        line["change_over_parent_pct"] = 100 * (
            line["change_median"] / line["parent_median"] - 1)
        if len(p) >= 3 and len(c) >= 3:
            line["parent_spread_pct"] = 100 * spread(list(p.values()))
            line["change_spread_pct"] = 100 * spread(list(c.values()))
        line["pairs_change_higher"] = (
            sum(c[s] > p[s] for s in both), len(both))
        print(json.dumps(line))


if __name__ == "__main__":
    main()
