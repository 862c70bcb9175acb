"""One test of this directory pins `BENCHMARK.json`'s LAST entries to the
cell its PR added (`test_benchmark_deepseek_v2.py`: the last workload, the
last configuration, the last three per-layer metrics and the last name of
each joined `workloads` list are `dsv2lite-1chip`'s). The contract has every
later PR append its entries at the end, so the first cell added after it
makes those four lines false, and the file is the benchmark's own: a PR
that adds a cell may not edit it (PR 32 could not). Until a `benchmark` PR
rewrites those lines to find their entries by name, the test is expected to
fail; `test_benchmark_olmo_hybrid.py` holds what it held, by name and by
order (`dsv2lite-1chip`'s entries are there and stand before the later
cell's)."""

import pytest

PINNED_TO_THE_LAST_ENTRY = (
    "test_benchmark_deepseek_v2.py::"
    "test_the_entries_are_the_cells_and_name_their_layers")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED_TO_THE_LAST_ENTRY):
            item.add_marker(pytest.mark.xfail(
                reason="pins BENCHMARK.json's last entries to dsv2lite-1chip;"
                       " a later cell is appended after it (see this "
                       "directory's conftest.py)", strict=False))
