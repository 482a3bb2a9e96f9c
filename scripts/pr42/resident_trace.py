"""PR 42: the prompt kernel ``apex_kda_chunk_fwd`` read once from a trace of
(part of) the resident wave, which the cell's traced span does not hold: the
profiler around ``bench:resident_prefill`` of the first N residents, the
kernel's time per 1,000 bucket tokens and its share of its roofline
(``benchmark/kernels/kda.py::chunk_*``), and ``jit_prefill`` by region.

    python3 scripts/pr42/resident_trace.py [--cpu] [N]
"""
import os
import re
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
cpu = "--cpu" in sys.argv
if cpu:
    os.environ["JAX_PLATFORMS"] = "cpu"
import jax

from benchmark import harness, trace, traffic

n = int(([a for a in sys.argv[1:] if a.isdigit()] or [24])[0])
harness.enable_compile_cache()
cell = harness.Cell("ling3_flash_vl.many_stream_reasoning")
config, mix = harness.views(cell, cpu)
ref, runner = cell.reference(), cell.runner()
seed = 4200000011
trace_dir = os.path.join(ROOT, ".bench_trace", "resident_wave")


class Ctx(types.SimpleNamespace):
    def span(self, name):
        return jax.profiler.TraceAnnotation("bench:" + name)


ctx = Ctx(seed=seed, options={})
engine, sched, deliveries, sz = runner.build(ctx, config, ref)
arrivals = traffic.requests(mix, seed, 30.0, sz["vocab"], engine.max_len)
runner.gpt.warm_up(ctx, engine, sched, mix, sz)
deliveries.clear()
shutil.rmtree(trace_dir, ignore_errors=True)
jax.profiler.start_trace(trace_dir)
t = time.perf_counter()
rids, wave = runner.resident.make_resident(ctx, sched, arrivals[:n],
                                           deliveries)
jax.block_until_ready(engine.cache.lengths)
wall = time.perf_counter() - t
jax.profiler.stop_trace()
buckets = [min(b for b in engine.buckets if b >= len(a.prompt))
           for a in arrivals[:n]]
print("wave", wave, "wall_s", wall, "bucket_tokens", sum(buckets))
if cpu:
    sys.exit(0)
reduced = trace.reduce_dir(trace_dir)
match = re.compile(r"^%apex_kda_chunk_fwd(\.\d+)? = ").match
seconds, calls = reduced.kernel_time(match)
times = reduced.program_times("jit_prefill")
kda = harness.load_module("kernels", "kda")
peaks = cell.peaks(jax.devices()[0].device_kind)
tokens = sum(buckets)
least = sz["kda_layers"] * max(
    kda.chunk_flops(sz, tokens) / peaks["bf16_flops_per_s"],
    kda.chunk_bytes(sz, tokens, n) / peaks["hbm_bytes_per_s"])
print("prefills", len(times), "jit_prefill s", sum(times),
      "ms per 1000 bucket tokens", 1e6 * sum(times) / tokens,
      "ms per 1000 prompt tokens", 1e6 * sum(times) / wave["prompt_tokens"])
print("apex_kda_chunk_fwd calls", calls, "(6 a prefill:", calls == 6 * n, ")",
      "seconds", seconds, "ms per 1000 bucket tokens",
      1e6 * seconds / tokens,
      "roofline_pct", 100 * least / seconds,
      "flops bound s", sz["kda_layers"] * kda.chunk_flops(sz, tokens)
      / peaks["bf16_flops_per_s"],
      "bytes bound s", sz["kda_layers"] * kda.chunk_bytes(sz, tokens, n)
      / peaks["hbm_bytes_per_s"])
from benchmark import regions
found = trace.find(trace_dir)
by = regions.load(found).regions("jit_prefill")
print("jit_prefill regions, ms per 1000 bucket tokens",
      {k: round(1e6 * v / tokens, 3) for k, v in by.items()}
      if isinstance(by, dict) else by)
print("top device ops", reduced.breakdown()["device_ops"][:14])
