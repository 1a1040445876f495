"""The traced window's device span (first op to last, busy + idle) on the
device where it is longest, over the window's steps."""


def read(trace, run, params):
    if not trace or not run.get("steps"):
        return None
    return trace["span_s"] / run["steps"] * 1e3
