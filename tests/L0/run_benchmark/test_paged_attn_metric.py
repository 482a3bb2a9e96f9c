"""``paged_attn_kernel_ms_per_decode`` (PR 25): the device time of the
``apex_paged_decode_fwd`` calls per decode execution, by kernel name."""

import os
import types

import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@pytest.mark.parametrize("calls, want", [
    (240, 1e3 * 0.12 / 10), (24, 1e3 * 0.12), (0, None), (239, None)])
def test_paged_attn_kernel_ms_per_decode_wants_whole_executions(calls, want):
    names = {"%apex_paged_decode_fwd.1 = bf16[56,1,1024]{2,1,0:T(2,128)(2,1)"
             "S(1)} custom-call(s32[56,64] %block_tables.1, s32[56] %pos.1)":
             (0.12, calls),
             "%apex_paged_decode_fwd_other = f32[8] custom-call(f32[8] %b)":
             (9, 9),
             "%apex_flash_fwd.3 = bf16[1,16,512,64] custom-call(bf16[8] %a)":
             (5.0, 48)}

    def kernel_time(match):
        hit = [v for n, v in names.items() if match(n)]
        return sum(s for s, _ in hit), sum(c for _, c in hit)

    read = harness.load_module(
        "metrics", "paged_attn_kernel_ms_per_decode",
        os.path.join(REPO, "benchmark")).read
    got = read({"trace": types.SimpleNamespace(kernel_time=kernel_time),
                "apex_spans": [], "counts": {"sizes": {"layers": 24}}})
    assert got == (pytest.approx(want) if want else None)


def test_the_metric_is_declared_for_both_serving_cells():
    # wherever it stands in ``per_layer``: later PRs append after it
    [entry] = [m for m in harness.load_json(REPO, "BENCHMARK.json")[
        "per_layer"] if m["name"] == "paged_attn_kernel_ms_per_decode"]
    assert entry == {
        "name": "paged_attn_kernel_ms_per_decode", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "Kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2_medium.offline_decode",
                      "gpt2_medium.prompt_backlog"]}
