"""What every cell shares: the manifest, the files a cell names, the device
check, the compile counter, the quantiles and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name: a cell
is an entry of ``workloads`` in ``BENCHMARK.json`` and everything that belongs
to it is a file found by the name written there (see ``README.md``).
"""

import importlib.util
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the keys of the result line the driver reads (``breakdown`` and the
#: benchmark's own ``compared`` follow them), and of ``device`` inside it
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class BenchmarkError(Exception):
    """The run cannot give a result; the process exits non-zero."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = HERE):
    """``<root>/<kind>/<name>.py`` as a module, by path: names may hold
    dots (``device_idle_pct.train``), which an import statement cannot."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmark")
        self.manifest = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise BenchmarkError(
                f"no workload {workload!r} in BENCHMARK.json; it has "
                f"{sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        config = {c["name"]: c for c in self.manifest["configs"]}[
            self.entry["config"]]
        self.config_name = config["name"]
        self.config = load_json(root, config["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(self.bench_dir, "traffic",
                                 self.traffic_name + ".json")
        self.peaks_table = load_json(self.bench_dir, "peaks.json")

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    @property
    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    @property
    def per_layer(self):
        return [m for m in self.manifest["per_layer"] if self._reports(m)]

    def runner(self):
        return load_module("runners", self.config["runner"], self.bench_dir)

    def reference(self):
        return load_module("reference", self.config_name, self.bench_dir)

    def peaks(self, device_kind: str) -> dict:
        if device_kind not in self.peaks_table:
            raise BenchmarkError(
                f"device kind {device_kind!r} is not in benchmark/peaks.json:"
                " an unknown device is an error, not a default")
        return self.peaks_table[device_kind]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path: the one
    ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads it itself), else
    ``.jax_cache`` in the checkout, which is also where the program's own
    ``enable_compile_cache`` puts it. Every program is kept, however quick
    its compile: a second run has to find them all, the tiny ones too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def views(cell, rehearsal: bool) -> tuple:
    """The cell's configuration and traffic as a runner reads them."""
    if not rehearsal:
        return cell.config, cell.traffic
    return rehearsal_view(cell.config), rehearsal_view(cell.traffic)


def rehearsal_view(d: dict) -> dict:
    """A configuration or traffic file with its ``rehearsal`` block laid
    over it: the tiny sizes that only ``--cpu-rehearsal`` ever runs."""
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    for k, v in d.get("rehearsal", {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def quantile(values, q: float) -> float:
    """The ``q`` quantile by linear interpolation between order statistics
    (numpy's default), on plain floats."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise BenchmarkError("quantile of nothing")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return statistics.median(float(v) for v in values)


def describe(devices) -> dict:
    """The device as JAX reports it."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class CompileCounter:
    """Counts the programs JAX hands to the backend (from the persistent
    cache or not): inside a measured window there may be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *a, **k):
        if name == self.EVENT:
            self.n += 1


def memory_peak_bytes(devices, program_bytes: int = 0) -> int:
    """Peak on the fullest chip. ``peak_bytes_in_use`` on this runtime counts
    live arrays and not a running program's temporaries (PERF.md), so the
    largest timed program's own account (arguments + temporaries, from
    ``memory_analysis()``, the arguments being live arrays already counted
    once) is a second floor under the peak; the larger of the two is given
    and both are printed on an earlier line."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(max(peaks), program_bytes))


def program_bytes(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"arguments": int(mem.argument_size_in_bytes),
            "aliased": int(mem.alias_size_in_bytes),
            "temp": int(mem.temp_size_in_bytes),
            "output": int(mem.output_size_in_bytes),
            "code": int(mem.generated_code_size_in_bytes)}


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None, numbers=()) -> str:
    """The last line of a run: the contract's keys and, last of all,
    ``compared``: each number that decided ``correct`` beside its limit
    (``numbers``: the rows ``comparison`` gives)."""
    for key in DEVICE_KEYS:
        if key not in device:
            raise BenchmarkError(f"device lacks {key!r}")
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {name: {"value": float(m["value"]), "unit": m["unit"]}
                       for name, m in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = {
            "device_ops": [[str(n), float(s)] for n, s in
                           breakdown["device_ops"][:10]],
            "idle_gaps": [[str(n), float(s)] for n, s in
                          breakdown["idle_gaps"][:10]]}
    out["compared"] = {n["number"]: {"value": n["value"], "limit": n["limit"]}
                       for n in numbers}
    return json.dumps(out)


def say_compared(numbers) -> None:
    """Each number compared beside its limit, one to a line: the last lines
    of a run's standard error."""
    for n in numbers:
        print(f"compared {n['number']} = {n['value']!r} (limit "
              f"{n['limit']!r}){'' if n['ok'] else ' NOT CORRECT'}",
              file=sys.stderr, flush=True)


def say(label: dict, **fields) -> None:
    """One labelled JSON line on standard output, before the result."""
    print(json.dumps({**label, **fields}, default=str), flush=True)


def comparison(numbers) -> tuple:
    """``numbers``: rows of (name, value, limit). Correct when every value
    is finite and at or under its limit."""
    rows, ok = [], True
    for name, value, limit in numbers:
        good = value == value and value <= limit   # NaN fails
        ok = ok and good
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": bool(good)})
    return ok, rows
