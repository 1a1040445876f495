"""A series of the program's own metrics registry, read in the same process
(`openembedding_tpu.utils.metrics.report()`), times an optional `scale`.
`params["name"]` is the key exactly as `report()` prints it, labels included
(`trainer.traces{fn="train_many"}`). `None` where the program has no such
series, as a parent commit from before the counter has not."""


def read(trace, run, params):
    from openembedding_tpu.utils import metrics
    value = metrics.report().get(params["name"])
    return None if value is None else value * params.get("scale", 1.0)
