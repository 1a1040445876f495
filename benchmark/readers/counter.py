"""A number the run itself counted (`run[key]`), times an optional `scale`."""


def read(trace, run, params):
    value = run.get(params["key"])
    return None if value is None else value * params.get("scale", 1.0)
