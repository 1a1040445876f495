"""1 - (union of op intervals) / (traced window by the host's clock), on the
device that is busy least."""


def read(trace, run, params):
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_worst_s"] / trace["window_s"])
