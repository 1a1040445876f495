"""Least time the chip could take for a class's work, over the class's device
time. `work: tower_flops` counts the model's matmul FLOPs per chip and step
against the bf16 peak; `work: sparse_bytes` counts the unique rows of the
generated ids (read for the pull, read and written for the apply) against the
HBM peak. Nothing is taken from the program's counters or op names."""

from benchmark import work


def read(trace, run, params):
    if not trace or not run.get("steps"):
        return None
    seconds = trace["class_s"][params["class"]] / run["steps"]
    if seconds <= 0:
        return None
    cfg, peaks = run["cfg"], run["peaks"]
    if params["work"] == "tower_flops":
        least = work.matmul_flops_per_example(cfg) * run["batch"] / run["chips"] / peaks["bf16_flops_per_s"]
    elif params["work"] == "sparse_bytes":
        least = work.sparse_bytes_per_step(cfg, run["ids"], run["chips"]) / peaks["hbm_bytes_per_s"]
    else:
        raise ValueError(f"unknown work {params['work']!r}")
    return 100.0 * least / seconds
