#!/usr/bin/env python3
"""Run one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result: there is no CPU fallback. The
last line of standard output is the result, one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``compared`` (each number that decided
``correct`` beside its limit, which are also the last lines of standard
error); everything else goes on earlier lines.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the first seconds of the
window by the readers under ``metrics/``.

``--cpu-rehearsal`` is the one way off the chip: the tiny ``rehearsal``
sizes of the configuration and traffic files on the CPU backend, every line
labelled a rehearsal, and no result line, because a rehearsal is not one.
"""

import argparse
import collections
import contextlib
import os
import shutil
import sys
import time

T_START = time.perf_counter()      # process start, as near as Python gives

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))     # the checkout

from benchmark import harness  # noqa: E402


class Ctx:
    """What a runner gets: the cell, the arguments, the devices, the clock,
    the compile counter and the tracing switches."""

    def __init__(self, cell, args, devices, counter):
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearsal = args.cpu_rehearsal or args.rehearsal_on_chip
        self.control = bool(args.control)
        self.options = dict(o.split("=", 1) for o in args.option)
        self.devices = devices
        self.chips = cell.chips
        self.counter = counter
        self.t_start = T_START
        self.label = {"workload": cell.name, "seed": args.seed,
                      "rehearsal": self.rehearsal,
                      **harness.describe(devices)}
        _, view = harness.views(cell, self.rehearsal)
        self.trace_seconds = min(float(self.options.get(
            "trace_seconds", view.get("trace_seconds", 4.0))), args.seconds)
        # a fixed place inside the checkout, emptied by each traced run
        self.trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
        self.traced = None          # (t_open, t_close) of the traced span
        self.trace_stop_s = None    # what stopping the profiler cost after
        self._tracing = False
        self._t_trace = None

    def say(self, **fields):
        harness.say(self.label, t=round(time.perf_counter() - T_START, 3),
                    **fields)

    def start_trace(self) -> bool:
        if not self.trace:
            return False
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing, self._t_trace = True, time.perf_counter()
        return True

    def stop_trace(self) -> bool:
        import jax
        if self._tracing:
            self.traced = (self._t_trace, time.perf_counter())
            jax.profiler.stop_trace()
            self.trace_stop_s = time.perf_counter() - self.traced[1]
            self._tracing = False
        return False

    def span(self, name: str):
        """A host span in the profiler's own trace while it runs."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend; no result line")
    ap.add_argument("--rehearsal-on-chip", action="store_true",
                    help="the tiny rehearsal sizes on whatever device JAX "
                         "finds: how the small recorded trace the tests "
                         "read was made; labelled, no result line")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control's numbers "
                         "(the builder's readings; no check runs it)")
    ap.add_argument("--option", action="append", default=[],
                    help="key=value handed to the runner: the switches the "
                         "controls and the harness's own tests use")
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload)
    import jax

    if args.cpu_rehearsal:
        try:
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", max(cell.chips, 1))
        except RuntimeError:
            pass    # a test process: its CPU backend is already up
    # outside a checkout of the repo this import is what fails
    import apex_tpu  # noqa: F401
    cache_dir = harness.enable_compile_cache()
    counter = harness.CompileCounter()
    devices = jax.devices()
    dev = harness.describe(devices)
    if not args.cpu_rehearsal and (dev["platform"] != "tpu"
                                   or dev["count"] < cell.chips):
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {dev['count']} x {dev['kind']} on platform "
              f"{dev['platform']!r}. There is no CPU fallback; "
              "--cpu-rehearsal runs the tiny sizes here.", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    ctx = Ctx(cell, args, devices, counter)
    if dev["platform"] == "tpu":
        cell.peaks(dev["kind"])     # an unknown device is an error, early
    ctx.say(stage="start", compile_cache_dir=cache_dir, jax=jax.__version__,
            trace=args.trace, seconds=args.seconds)

    out = cell.runner().run(ctx)

    values = out["values"]
    device = {**harness.describe(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    breakdown = None
    if ctx.trace and args.cpu_rehearsal:
        # the CPU backend's trace has no device plane; the reduction and
        # the readers are tested on the recorded chip trace instead
        ctx.say(stage="trace", skipped="no device plane on the CPU backend")
        metrics = {}
    elif ctx.trace:
        from benchmark import trace
        # a server may have nothing to do in its traced span (a backlog the
        # window drained): that is a reading, idle 100%. A training run
        # whose trace holds no device operation is a broken run.
        reduced = trace.reduce_dir(
            ctx.trace_dir, may_be_empty=cell.traffic["kind"] == "requests")
        if not reduced.chips:
            ctx.say(stage="empty_traced_window",
                    what="no operation ran on a device in the traced span: "
                         "the readers that need device work are left out",
                    traced_from_s=out["counts"]["traced_from_s"],
                    traced_s=ctx.traced[1] - ctx.traced[0],
                    backlog_left=out["counts"]["backlog_left"],
                    drained_at_s=out["counts"]["drained_at_s"])
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
        if ctx.options.get("keep_trace"):      # the tests' recorded trace
            found = trace.find(ctx.trace_dir)
            ctx.say(stage="keep_trace", bytes=os.path.getsize(found))
            if os.path.getsize(found) < 8 << 20:
                import gzip
                with open(found, "rb") as src, gzip.open(
                        ctx.options["keep_trace"], "wb") as dst:
                    dst.write(src.read())
        run = {"trace": reduced, "counts": out["counts"], "values": values,
               "config": cell.config, "traffic": cell.traffic,
               "peaks": cell.peaks(dev["kind"]), "chips": cell.chips,
               "cell": cell}
        metrics = {}
        for m in cell.per_layer:
            value = harness.load_module(
                "metrics", m["name"], cell.bench_dir).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        ctx.say(stage="trace", busy_s=reduced.busy_s,
                window_s=reduced.window_s, end_to_end_while_traced=values,
                stop_trace_s=ctx.trace_stop_s,
                bench_spans=collections.Counter(
                    name for name, _, _ in reduced.host.bench),
                programs=reduced.programs_summary(),
                custom_calls=reduced.custom_calls()
                if ctx.options.get("list_kernels") else None)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = harness.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, device=device,
        breakdown=breakdown, numbers=out["numbers"])
    if ctx.rehearsal:
        ctx.say(stage="rehearsal_result", would_be=line)
        return 0
    harness.say_compared(out["numbers"])
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
