"""ONE stale pin, kept out of the way without touching the file that holds it
(a PR that is not a ``benchmark`` PR may add files under ``tests/benchmark``
and edit none).

``test_lfm2_moe.py::test_the_cell_is_one_chip_with_dsv3s_traffic_unchanged``
asserts, beside what its name says, that PR 44's configuration, cell and
three readers STAND LAST in ``BENCHMARK.json``'s lists and that
``paged_decode_roofline`` lists the LFM2 cell alone. Both were true the day
PR 44 landed and cannot stay true: every later cell is appended at the end
(the contract's rule), and PR 48's cell ``serve-jamba2-decode-closed`` reports
``paged_decode_roofline`` too (its reader's docstring: "any cell whose scan
calls ``paged_decode`` may list itself"). Every OTHER line of that test
still holds and is held, word for word, by
``test_jamba.py::test_the_lfm2_cell_stands_as_pr_44_left_it`` (the cell's
exact per-layer set, its readers' layer, unit and ``moves``, the two readers
that list it alone, and PR 44's entries by index where they stood); what the
test's NAME says is held for all four cells on the shared traffic file by
``test_jamba.py::test_the_cells_on_the_shared_traffic_file_keep_it_unchanged``.
The marker is STRICT: the day the three stale lines go from the LFM2 test
(a ``benchmark`` PR's to do, PERF.md section 7) it passes, the strict marker
turns that pass into a failure, and this file goes with them.
"""

import pytest

STALE = ("tests/benchmark/test_lfm2_moe.py::"
         "test_the_cell_is_one_chip_with_dsv3s_traffic_unchanged")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid == STALE:
            item.add_marker(pytest.mark.xfail(
                reason="pins PR 44's entries as the manifest's last and "
                "paged_decode_roofline's only cell: stale since PR 48 "
                "appended its own (tests/benchmark/conftest.py)",
                strict=True))
