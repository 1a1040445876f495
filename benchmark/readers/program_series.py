"""Every label set of ONE series of the program's own metrics registry, read in
the same process (`openembedding_tpu.utils.metrics.report()`) and folded to one
number: `params["name"]` is the series' name without labels
(`sparse.apply_fill` reads `sparse.apply_fill{table="categorical"}`,
`sparse.apply_fill{table="first_order"}`, ... and a bare `sparse.apply_fill`),
`params["fold"]` one of `max`, `sum`, `min`. `None` where the program has no
such series, as a parent commit from before the counter has not."""

FOLDS = {"max": max, "sum": sum, "min": min}


def read(trace, run, params):
    from openembedding_tpu.utils import metrics
    name = params["name"]
    values = [v for key, v in metrics.report().items()
              if key == name or (key.startswith(name + "{") and key.endswith("}"))]
    return FOLDS[params["fold"]](values) if values else None
