"""`test_benchmark.py` parametrises three DeepFM rehearsals over every cell of
`BENCHMARK.json`: they shrink a cell with DeepFM's keys (`vocabulary`,
`batch_per_chip` rows) and drive `drivers/train_scan.py` by hand, so they cannot
run a cell of another family. Such a cell brings its own rehearsals in a file of
its own (`test_<family>_cell.py`), and these three are skipped for it here; every
other test of `test_benchmark.py` runs on every cell."""

import pytest

from benchmark import run

DEEPFM_REHEARSALS = {"test_rehearsal_prints_the_contract_line_and_is_correct",
                     "test_program_passes_the_reference_and_each_control_fails",
                     "test_a_broken_timed_path_reads_not_correct"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        params = getattr(getattr(item, "callspec", None), "params", {})
        if (item.path.name == "test_benchmark.py" and "workload" in params
                and getattr(item, "originalname", "") in DEEPFM_REHEARSALS):
            family = run.resolve(params["workload"])[2]["family"]
            if family != "deepfm":
                item.add_marker(pytest.mark.skip(
                    reason=f"DeepFM's rehearsal; the {family} cell's is test_{family}_cell.py"))
