"""Device time per step of one op class (`dot`, `collective`, or the remainder
`other`), on the device where it is largest."""


def read(trace, run, params):
    if not trace or not run.get("steps"):
        return None
    seconds = trace["class_s"][params["class"]]
    return seconds / run["steps"] * 1e3 if seconds > 0 else None
