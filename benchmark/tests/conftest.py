"""PR 27. ``test_bench_program_readers.py`` (PR 24's file, not PR 27's to
edit) pins the benchmark at 13 per-layer metrics and 2 cells: true when it
was written, false since the first entry added after it. The rule it stands
for (PR 24's five entries are all there, in their order, after what the
benchmark had before them, and nothing that was there moved) is kept by
``test_bench_lm.py::test_entries_the_benchmark_had_are_where_they_were``.
The marker is strict: when the pinned test is relaxed it has to go."""

import pytest

PINNED = ("test_bench_program_readers.py::"
          "test_the_new_entries_are_the_last_five_and_nothing_else_moved")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins 13 per-layer metrics and 2 cells; the benchmark "
                       "has grown since (PERF.md, Open questions)"))
