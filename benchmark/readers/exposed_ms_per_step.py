"""The part of the collectives' time during which no other op runs on that
device, per step, on the device where it is largest."""


def read(trace, run, params):
    if not trace or not run.get("steps") or trace["class_s"]["collective"] <= 0:
        return None
    return trace["exposed_collective_s"] / run["steps"] * 1e3
