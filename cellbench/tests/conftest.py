"""The benchmark's own tests that stand red since PR 30, marked as expected
failures with their reasons instead of left red or shadowed. A `model_config`
PR may add files here and edit none, and both tests need an edit to a file
that is there; the `benchmark` issue that makes it takes this file out again
(PERF.md section 7, row 18). `strict`: the day one passes, the run says so.
"""

import pytest

EXPECTED_FAILURES = {
    "test_unknown_model_type_names_the_missing_file":
        "cellbench/tests/test_families.py takes `falcon_h1` as the "
        "model_type no family file exists for; since PR 30 one does. The "
        "edit: any name no family has, e.g. `no_such_family` (tier 1 tests "
        "that in tests/test_cellbench_families.py)",
    "test_roofline_stays_under_100_for_both_configurations"
    "[falcon-h1-34b-span8-config2]":
        "cellbench/tests/test_trace.py passes every bound for falcon_h1's "
        "needs, then looks the configuration up in its two-entry dict of "
        "fastest measured decode steps. The edit: derive that dict from "
        "files, or add this configuration's fastest step (PERF.md section 7 "
        "row 18)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = EXPECTED_FAILURES.get(item.name)
        if reason is not None:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
