"""One series of the program's own metrics registry over another
(`openembedding_tpu.utils.metrics.report()`; `params["over"]` the divisor).
`None` where the program has neither series, as a parent commit from before
the counters has not, or where the divisor reads 0."""


def read(trace, run, params):
    from openembedding_tpu.utils import metrics
    report = metrics.report()
    value, over = report.get(params["name"]), report.get(params["over"])
    return None if value is None or not over else value / over
