"""`readers/program_metric.py`: a series of the program's registry by its
`report()` key, and nothing where the program has no such series."""

from benchmark.readers import program_metric


def test_program_metric_reads_the_observed_series_and_none_for_unknown():
    from openembedding_tpu.utils import metrics
    name = 'benchtest.traces{fn="probe"}'
    metrics.observe("benchtest.traces", 1, "sum", labels={"fn": "probe"})
    metrics.observe("benchtest.traces", 1, "sum", labels={"fn": "probe"})
    try:
        assert program_metric.read(None, {}, {"name": name}) == 2.0
        assert program_metric.read(None, {}, {"name": name, "scale": 0.5}) == 1.0
        assert program_metric.read(None, {}, {"name": "benchtest.no_such_series"}) is None
    finally:
        metrics._REGISTRY.pop(name, None)
