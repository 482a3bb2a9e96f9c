#!/usr/bin/env python3
"""Why is a tick slow? One serving cell, run as ``benchmark/run.py`` runs it
(the same arguments, handed on), with every scheduler tick and every span
inside it timed twice: on the wall clock and on the MAIN THREAD'S CPU clock.

    python3 scripts/probe_ticks.py --workload <cell> --seed <n> --seconds 30 --trace 0

A tick that takes ``STALL_S`` or more is written down with its spans (wall
and CPU milliseconds each), the thread's context switches and page faults
over it, the process's CPU time over it, the garbage collector's counts
before and after, and what a SAMPLER thread saw of the main thread's stack
inside it (it wakes every ``SAMPLE_S`` and needs the GIL: samples inside the
stall say where the main thread waited with the GIL released; NO sample
inside it says the GIL was held, or the whole process stood still). What the
readings tell apart:

- thread CPU about equal to wall, no samples: the main thread computed with
  the GIL held for the whole stall (a collection, a C call that keeps it);
- thread CPU near nothing, samples: it was blocked with the GIL released (a
  device wait, a transfer): the samples' frames say in which call;
- thread CPU near nothing, no samples: no Python thread of the process
  ran. Then a TICKER, a second process that only sleeps 5 ms at a time and
  writes down every wake that came 20 ms late on the same monotonic clock,
  says whether the whole machine stood still (it has a gap at the same
  time) or this process alone (a third thread kept the GIL while it waited:
  the sampler's first wake after a gap lists every thread's frames). Beside
  them: the steal column of ``/proc/stat``, the main thread's
  ``schedstat`` (time on a CPU, time runnable and waiting for one) and the
  cgroup's ``nr_throttled``.

The run's own lines go where ``run.py`` sends them; the probe's go to
``chiprun_out/probe/ticks_<seed>.json``. The sampler takes the GIL a hundred
times a second: the run's end-to-end numbers are not a measurement.
(``faulthandler.dump_traceback_later`` would need no GIL, and crashes the
process when it fires while the main thread runs Python: tried, PR 40.)
"""

import collections
import gc
import json
import os
import resource
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STALL_S = 0.045
SAMPLE_S = 0.01


def cgroup_cpu():
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            with open(path) as f:
                return dict(line.split() for line in f)
        except OSError:
            continue
    return None


TICKER = """
import sys, time
out = open(sys.argv[1], "w")
t = time.monotonic()
while True:
    time.sleep(0.005)
    now = time.monotonic()
    if now - t > 0.025:
        out.write("%.6f %.6f\\n" % (t, now)); out.flush()
    t = now
"""


def _read(path):
    try:
        with open(path) as f:
            return f.read().split()
    except OSError:
        return None


def machine(tid):
    """(steal jiffies of all CPUs, [ns on a CPU, ns runnable and waiting,
    slices] of thread ``tid``)."""
    stat = _read("/proc/stat")
    return (stat and stat[8], _read(f"/proc/self/task/{tid}/schedstat"))


def install(seed):
    from apex_tpu.serving import observe, scheduler

    out = os.path.join(ROOT, "chiprun_out", "probe")
    os.makedirs(out, exist_ok=True)
    spans, open_at, slow, walls = [], {}, [], []
    samples = collections.deque(maxlen=256)     # (wall, the frames' names)
    main = threading.main_thread().ident
    tid = threading.get_native_id()
    # every thread's frames at the sampler's first wake after a gap
    after_a_gap = collections.deque(maxlen=80)
    ticker = subprocess.Popen(
        [sys.executable, "-c", TICKER, os.path.join(out, f"ticker_{seed}.txt")],
        env={})

    def named(frame, depth=10):
        names = []
        while frame is not None and len(names) < depth:
            names.append(f"{os.path.basename(frame.f_code.co_filename)}:"
                         f"{frame.f_lineno} {frame.f_code.co_name}")
            frame = frame.f_back
        return names

    def sample():
        last = time.perf_counter()
        while True:
            time.sleep(SAMPLE_S)
            now, frames = time.perf_counter(), sys._current_frames()
            if now - last > 5 * SAMPLE_S:
                after_a_gap.append({
                    "woke_at": now, "ms_late": 1e3 * (now - last),
                    "threads": {t.name: named(frames.get(t.ident), 6)
                                for t in threading.enumerate()},
                    "frames_of_no_python_thread": len(frames)
                    - len(threading.enumerate())})
            samples.append((now, named(frames.get(main))))
            last = now

    threading.Thread(target=sample, daemon=True).start()
    begin, end = observe.Tracer.begin, observe.Tracer.end
    step = scheduler.ContinuousBatchingScheduler.step

    def begin_timed(self, name, *a, **kw):
        open_at[name] = (time.perf_counter(), time.thread_time())
        return begin(self, name, *a, **kw)

    def end_timed(self, name, *a, **kw):
        got = end(self, name, *a, **kw)
        at = open_at.pop(name, None)
        if at is not None and name != "step":
            spans.append((name, 1e3 * (time.perf_counter() - at[0]),
                          1e3 * (time.thread_time() - at[1])))
        return got

    def step_timed(self):
        spans.clear()
        counts = [g["collections"] for g in gc.get_stats()]
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        before = machine(tid)
        w, c, p = time.perf_counter(), time.thread_time(), time.process_time()
        try:
            return step(self)
        finally:
            wall = time.perf_counter() - w
            cpu, proc = time.thread_time() - c, time.process_time() - p
            walls.append(wall)
            if wall >= STALL_S:
                after = resource.getrusage(resource.RUSAGE_THREAD)
                seen = [(t, names) for t, names in list(samples)
                        if w - 2 * SAMPLE_S <= t <= w + wall]
                slow.append({
                    "steal_and_schedstat_before": before,
                    "steal_and_schedstat_after": machine(tid),
                    "monotonic_at": time.monotonic() - wall,
                    "sampled_at_ms_into_the_tick": [
                        round(1e3 * (t - w), 1) for t, _ in seen],
                    "sampled_frames": [names for _, names in seen][:12],
                    "tick": len(walls), "at": w, "wall_ms": 1e3 * wall,
                    "thread_cpu_ms": 1e3 * cpu, "process_cpu_ms": 1e3 * proc,
                    "spans_wall_cpu_ms": [
                        (n, round(a, 2), round(b, 2)) for n, a, b in spans],
                    "voluntary_switches": after.ru_nvcsw - ru.ru_nvcsw,
                    "involuntary_switches": after.ru_nivcsw - ru.ru_nivcsw,
                    "minor_faults": after.ru_minflt - ru.ru_minflt,
                    "major_faults": after.ru_majflt - ru.ru_majflt,
                    "gc_collections_before": counts,
                    "gc_collections_after": [
                        g["collections"] for g in gc.get_stats()],
                    "gc_frozen": gc.get_freeze_count()})

    observe.Tracer.begin, observe.Tracer.end = begin_timed, end_timed
    scheduler.ContinuousBatchingScheduler.step = step_timed
    cpu_before = cgroup_cpu()

    def report():
        from benchmark import run

        for tick in slow:       # as the run's own lines count time (``t``)
            tick["t"] = round(tick.pop("at") - run.T_START, 3)
        ticker.kill()
        gaps = _read(os.path.join(out, f"ticker_{seed}.txt")) or []
        for wake in after_a_gap:
            wake["t"] = round(wake.pop("woke_at") - run.T_START, 3)
        walls_ms = sorted(1e3 * w for w in walls)
        with open(os.path.join(out, f"ticks_{seed}.json"), "w") as f:
            json.dump({
                "ticks": len(walls), "stall_ms": 1e3 * STALL_S,
                "tick_ms_p50": walls_ms[len(walls_ms) // 2] if walls else None,
                "slow": slow,
                "ticker_gaps_monotonic_from_to": list(zip(
                    map(float, gaps[::2]), map(float, gaps[1::2]))),
                "sampler_wakes_after_a_gap": list(after_a_gap),
                "pressure_cpu": _read("/proc/pressure/cpu"),
                "cgroup_cpu_before": cpu_before,
                "cgroup_cpu_after": cgroup_cpu(),
                "python": sys.version}, f, indent=1)

    return report


def main():
    from benchmark import run

    seed = sys.argv[sys.argv.index("--seed") + 1]
    report = install(seed)
    try:
        return run.main(sys.argv[1:])
    finally:
        report()


if __name__ == "__main__":
    sys.exit(main())
