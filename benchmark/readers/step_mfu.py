"""The whole step's share of the chip's bf16 peak: the model's forward +
backward matmul FLOPs per example (benchmark/work.py, from the configuration's
widths) times the traced window's examples per second per chip."""

from benchmark import work


def read(trace, run, params):
    if not trace or not run.get("steps"):
        return None
    rate = run["steps"] * run["batch"] / run["chips"] / run["seconds"]
    return 100.0 * work.matmul_flops_per_example(run["cfg"]) * rate / run["peaks"]["bf16_flops_per_s"]
