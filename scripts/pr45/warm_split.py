"""On the chip: where a server's start goes, up to the runner's ``warm`` line
(process start, weights, ``warm_up``, then the timed programs lowered again
for XLA's account of their memory: the stage ``warm`` below is that last
part alone, ``warm_up`` the one before it), by what JAX did: tracing, lowering
(Pallas -> Mosaic included), the backend's part (the cache's key, reading
and loading the executable, or compiling) and what is left (executions, the
host). ``setup_s``'s ``warm`` stage is what a kernel's text can move.

Run from the root of the checkout to be measured, with the cell's arguments:

    cd <checkout> && python3 <this file> --workload <cell> --seed <n>

It runs ``benchmark/run.py`` as it stands THERE, stops it at the ``warm``
line and prints one JSON line: seconds by stage, and inside each stage the
union of the spans of each of JAX's compile events (a span inside another of
its kind is counted once), with the five longest spans of the ``warm`` stage
by program.
"""

import collections
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402

spans = []      # (end, seconds, event, program)


def _on(event, seconds, fun_name=None, **_):
    spans.append((time.perf_counter(), seconds, event.rsplit("/", 1)[-1],
                  fun_name))


jax.monitoring.register_event_duration_secs_listener(_on)

from benchmark import run as bench  # noqa: E402
from benchmark import harness  # noqa: E402

marks = [("process", bench.T_START)]


def union(intervals):
    total, upto = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        total += max(0.0, hi - max(lo, upto))
        upto = max(upto, hi)
    return total


def report():
    out = {"tree": os.getcwd(), "argv": sys.argv[1:], "stages": {}}
    for (_, lo), (name, hi) in zip(marks, marks[1:]):
        by_event = collections.defaultdict(list)
        for end, seconds, event, _ in spans:
            if lo < end <= hi:
                by_event[event].append((end - seconds, end))
        out["stages"][name] = {
            "s": round(hi - lo, 3),
            **{event: [len(v), round(union(v), 3)]
               for event, v in sorted(by_event.items())}}
    lo = dict(marks)["built"]
    out["longest_in_warm"] = [
        [event, program, round(seconds, 3)] for _, seconds, event, program
        in sorted((s for s in spans if s[0] > lo), key=lambda s: -s[1])[:8]]
    print(json.dumps(out), flush=True)


_say = bench.Ctx.say


def say(self, **fields):
    stage = fields.get("stage")
    if stage in ("start", "built", "warm"):
        marks.append((stage, time.perf_counter()))
    _say(self, **fields)
    if stage == "warm":
        report()
        sys.stdout.flush()
        os._exit(0)


_runner = harness.Cell.runner


def runner(self):
    """The cell's runner, its ``warm_up`` (its own, or the one it takes from
    ``gpt_serve``, which the harness loads by file) marked where it ends."""
    module = _runner(self)
    owner = getattr(module, "gpt", module)
    real = owner.warm_up

    def warm_up(*args, **kwargs):
        out = real(*args, **kwargs)
        marks.append(("warm_up", time.perf_counter()))
        return out

    owner.warm_up = warm_up
    return module


bench.Ctx.say = say
harness.Cell.runner = runner

if __name__ == "__main__":
    sys.exit(bench.main(sys.argv[1:] + ["--seconds", "30"]))
