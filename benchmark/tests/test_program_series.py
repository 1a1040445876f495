"""`readers/program_series.py`: every label set of one series of the program's
registry folded to one number, and nothing where the program has no such series."""

import pytest

from benchmark.readers import program_series


@pytest.fixture
def series():
    from openembedding_tpu.utils import metrics
    metrics.observe("benchtest.fill", 0.25, "gauge", labels={"table": "a"})
    metrics.observe("benchtest.fill", 0.75, "gauge", labels={"table": "b"})
    metrics.observe("benchtest.bare", 3, "sum")
    metrics.observe("benchtest.bare", 4, "sum")
    metrics.observe("benchtest.fill_ms", 8.0, "hist", labels={"table": "a"})  # a longer name, and a histogram's quantile keys
    yield
    for key in [k for k in metrics._REGISTRY if k.startswith("benchtest.")]:
        metrics._REGISTRY.pop(key)


@pytest.mark.parametrize("fold,want", [("max", 0.75), ("sum", 1.0), ("min", 0.25)])
def test_program_series_folds_every_label_set(series, fold, want):
    assert program_series.read(None, {}, {"name": "benchtest.fill", "fold": fold}) == want


def test_program_series_reads_an_unlabelled_series(series):
    assert program_series.read(None, {}, {"name": "benchtest.bare", "fold": "sum"}) == 7.0
    assert program_series.read(None, {}, {"name": "benchtest.bare", "fold": "max"}) == 7.0


def test_program_series_leaves_out_other_names_and_quantile_keys(series):
    # `benchtest.fill_ms{table="a"}` and its `.p50` / `.p95` / `.p99` keys are another series'
    assert program_series.read(None, {}, {"name": "benchtest.fill_ms", "fold": "sum"}) == 8.0
    assert program_series.read(None, {}, {"name": "benchtest.fill", "fold": "sum"}) == 1.0


def test_program_series_reads_none_where_the_program_has_no_such_series(series):
    assert program_series.read(None, {}, {"name": "benchtest.no_such_series", "fold": "max"}) is None
    assert program_series.read(None, {}, {"name": "benchtest", "fold": "max"}) is None
