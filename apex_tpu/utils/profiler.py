"""Profiling hooks (SURVEY §5: tracing/profiling subsystem).

The reference leans on ``pyprof``/nvprof markers (removed upstream) and
``torch.cuda.nvtx`` ranges. The TPU-native story is XLA's own tracer:

- :func:`trace` wraps ``jax.profiler.trace`` — writes a TensorBoard-
  loadable trace (``tensorboard --logdir <dir>``, "Profile" tab, or
  ``xprof``). Device-side timelines come from XLA itself; nothing to
  instrument.
- :func:`annotate` (= ``jax.named_scope``) is the nvtx-range analogue:
  regions named here appear on the trace's Python/HLO-metadata rows, and
  the scope names survive into HLO op metadata (the ``tf_op`` stat of
  every ``XLA Ops`` event) so device kernels attribute back to model
  regions.
- :func:`region` is ``annotate`` held to ONE vocabulary, :data:`REGIONS`,
  the device-side counterpart of ``serving.observe.PHASES``. The library
  opens them where the work is written, so a user's step composed from
  ``h.cast_model`` / ``h.value_and_grad`` / a model / a loss / ``ddp`` /
  an optimizer's ``step`` is named without touching user code: ``embed``,
  ``attention``, ``mlp``, ``head`` (every in-tree model), ``mixer``
  (Gated DeltaNet, Mamba-2), ``router`` and ``experts`` (the sparse
  layers), ``loss`` (``mlm_loss``, the GPT losses), ``amp`` (the model
  cast, loss scaling, unscale, the finiteness reduction), ``grad_sync``
  (``DistributedDataParallel.allreduce_grads``), ``optimizer`` (every
  fused optimizer's ``step``, around its own ``<Name>.step`` scope) and
  ``cache_write`` (the row scatter into the serving page pool). An
  operation counts under the FIRST region on its path; ``layer{i}`` and
  the kernels' scopes are not regions. Every Pallas kernel carries a
  stable ``apex_<kernel>_<fwd|bwd>`` name and scope: a Mosaic
  ``custom-call`` is named in the trace after the innermost scope around
  it, and its time counts under the region around its call.
- :func:`span` is the HOST side: a ``jax.profiler.TraceAnnotation``
  named ``apex:<name>`` on the same timeline as the device planes. The
  serving scheduler opens one per phase of its tick.

Typical use::

    from apex_tpu.utils.profiler import annotate, trace
    with trace("/tmp/tb"):
        for _ in range(3):
            state = train_step(state)   # named scopes inside
"""

import contextlib
import glob
import gzip
import json
import os
from typing import Dict, List, Optional

import jax

annotate = jax.named_scope

#: The device regions, one vocabulary for every model, the amp frontend, the
#: data-parallel wrapper, the optimizers and the serving cache. A name here
#: is a plain component of an operation's ``tf_op`` path in the device
#: trace (``jit(train_step)/jvp(layer0)/attention/...``).
REGIONS = ("embed", "attention", "mixer", "mlp", "router", "experts", "head",
           "loss", "amp", "optimizer", "grad_sync", "cache_write")


def region(name: str):
    """``jax.named_scope(name)`` for a name in :data:`REGIONS`; any other
    name raises, where the scope is opened (at trace time). A scope is a
    name in the lowered module's locations: it costs nothing at run time
    and leaves the compiled program as it was."""
    if name not in REGIONS:
        raise ValueError(f"{name!r} is not a device region; REGIONS = "
                         f"{REGIONS}")
    return jax.named_scope(name)


def span(name: str, **counts):
    """A HOST span on the profiler's clock: ``apex:<name>`` on the host
    plane of the trace, beside the device planes, with ``counts`` as the
    event's stats. The serving scheduler opens one per phase of its tick
    (``apex:sched/<phase>``, ``serving.observe.PHASES``). With no
    profiler session it costs about a microsecond and records nothing."""
    return jax.profiler.TraceAnnotation("apex:" + name, **counts)


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False,
          python_tracer: bool = True):
    """Capture a device+host profile under ``log_dir``.

    ``python_tracer=False`` switches the profiler's Python function
    tracer off: the host plane then holds the program's own spans
    (:func:`span`) and not every Python call, and the host is not slowed
    by being watched (a serving tick ran a fifth slower under it)."""
    options = jax.profiler.ProfileOptions()
    if not python_tracer:
        options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link,
                             profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Trace report — the parse-and-report half of the reference's pyprof
# (``apex/pyprof`` annotated with nvtx AND parsed nsys output into op
# tables; annotate+trace alone is only half the workflow). jax writes a
# chrome-trace JSON next to the xplane file; stdlib parsing keeps the
# report dependency-free (no tensorboard install needed on the pod).
# ---------------------------------------------------------------------------


def summarize_trace(log_dir: str, *, top: int = 20,
                    device_only: bool = True) -> List[Dict]:
    """Aggregate the newest trace under ``log_dir`` into per-op totals.

    Returns rows ``{"name", "process", "count", "total_us", "avg_us"}``
    sorted by total duration, descending. ``device_only`` keeps only
    device lanes (``/device:...`` processes — XLA ops as executed);
    pass False to include host-side Python events. Works on any trace
    written by :func:`trace` / ``jax.profiler.trace``.
    """
    runs = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                         "*")))
    if not runs:
        raise FileNotFoundError(f"no profile runs under {log_dir}")
    paths = glob.glob(os.path.join(runs[-1], "*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(
            f"profile run {runs[-1]} has no *.trace.json.gz (this jax "
            "build wrote only the xplane file — open it with "
            "tensorboard/xprof instead)")
    agg: Dict[tuple, Dict] = {}
    for path in paths:
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        events = doc["traceEvents"] if isinstance(doc, dict) else doc
        pids = {e["pid"]: e.get("args", {}).get("name", str(e["pid"]))
                for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"}
        for e in events:
            if e.get("ph") != "X" or "dur" not in e:
                continue
            proc = pids.get(e.get("pid"), str(e.get("pid")))
            if device_only and "/device" not in proc:
                continue
            key = (proc, e["name"].lstrip("$"))
            row = agg.setdefault(key, {"name": key[1], "process": proc,
                                       "count": 0, "total_us": 0.0})
            row["count"] += 1
            row["total_us"] += float(e["dur"])
    if not agg and device_only:
        raise ValueError(
            "trace has no device lanes (CPU-only traces record host "
            "events only) — pass device_only=False to summarize host "
            "Python/dispatch events")
    rows = sorted(agg.values(), key=lambda r: -r["total_us"])[:top]
    for r in rows:
        r["avg_us"] = r["total_us"] / max(r["count"], 1)
    return rows


def print_summary(log_dir: str, *, top: int = 20,
                  device_only: bool = True,
                  file: Optional[object] = None) -> None:
    """Print :func:`summarize_trace` as a fixed-width table (the
    pyprof-style report)."""
    rows = summarize_trace(log_dir, top=top, device_only=device_only)
    print(f"{'total_us':>12} {'avg_us':>10} {'count':>7}  name",
          file=file)
    for r in rows:
        print(f"{r['total_us']:>12.1f} {r['avg_us']:>10.1f} "
              f"{r['count']:>7d}  {r['name'][:90]}", file=file)
