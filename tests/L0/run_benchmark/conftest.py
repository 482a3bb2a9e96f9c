"""Seven assertions pin the benchmark as PR 32 left it, in files under the
benchmark's own ``paths``, which only a ``benchmark`` PR may edit: the list of
serving cells (three), the list of backlog mixes (three), and PR 27's cell,
configuration and readers as the LAST entries of their lists. A
``model_config`` PR has to ADD a configuration, a cell and readers, and may
put new entries only at the END of their lists (the driver reads one put in
the middle as a change to what was there and refuses the PR before any run),
so each of these has to fail. Exactly these test ids, and no other, are
expected failures, as PR 27 did for the two pins it met (PR 32 relaxed those
and deleted its file; the next ``benchmark`` PR should do the same here:
PERF.md, section 7).

Everything else these tests check is asserted again in
``test_nemotron_cell.py``: the window line of all four serving cells word for
word (``test_window_line_of_every_serving_cell``), so the four pinned runs of
it are not executed a second time here; the three others are cheap, run, and
are STRICT: one that starts to pass again fails the run until its line here
is deleted.
"""

import pytest

_WINDOW = ("tests/L0/run_benchmark/test_rehearsal.py::"
           "test_window_line_says_what_is_left_of_the_backlog[{}]")
_WHY_WINDOW = ("asserts SERVING == the three serving cells PR 32 knew; the "
               "benchmark has four since PR 33")
NOT_RUN = {_WINDOW.format(cell): _WHY_WINDOW for cell in (
    "gpt2_medium.offline_decode", "gpt2_medium.prompt_backlog",
    "olmo_hybrid_7b.long_prompt_decode",
    "nemotron3_super_120b_a12b.many_slot_decode")}
PINNED = {
    "tests/L0/run_benchmark/test_traffic.py::"
    "test_the_backlog_mixes_are_the_three_serving_cells":
        "asserts the backlog mixes are three; many_slot_decode is a fourth",
    "tests/L0/run_benchmark/test_hybrid_cell.py::"
    "test_manifest_gains_the_cell_and_only_appends_its_name":
        "asserts olmo_hybrid_7b's configuration, cell and readers are the "
        "LAST entries: new entries have to be appended after them",
    "tests/L0/run_benchmark/test_hybrid_cell.py::"
    "test_what_the_two_pinned_tests_check_besides":
        "asserts olmo_hybrid_7b is configs[-1] and its readers the last "
        "per_layer entries",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in NOT_RUN:
            item.add_marker(pytest.mark.xfail(reason=NOT_RUN[item.nodeid],
                                              run=False))
        elif item.nodeid in PINNED:
            item.add_marker(pytest.mark.xfail(reason=PINNED[item.nodeid],
                                              strict=True))
