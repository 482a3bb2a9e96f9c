"""Two assertions of PR 23 / PR 25 pin the manifest as it stood then, in
files under the benchmark's own ``paths``, which only a ``benchmark`` PR may
edit. A ``model_config`` PR has to ADD a configuration that is cut in depth,
and may put new ``per_layer`` entries only at the END of their list (the
driver reads one put in the middle as a change to what was there and refuses
the PR before any run), and both assertions say it may not. So exactly these
two test ids, and no other, are STRICT expected failures: one that starts to
pass again fails the run until its line here is deleted (PERF.md, section 7).
Everything else either test checks is asserted again, for the new entries, in
``test_hybrid_cell.py`` (``test_what_the_two_pinned_tests_check_besides``).
"""

import pytest

PINNED = {
    "tests/L0/run_benchmark/test_manifest.py::"
    "test_config_entry_and_its_files[olmo_hybrid_7b]":
        "asserts `reduced == []` of every configuration: written when none "
        "was cut; olmo_hybrid_7b lists num_hidden_layers and layer_types",
    "tests/L0/run_benchmark/test_paged_attn_metric.py::"
    "test_the_metric_is_declared_for_both_serving_cells":
        "asserts paged_attn_kernel_ms_per_decode is the LAST per_layer "
        "entry: new entries have to be appended after it",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = PINNED.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
