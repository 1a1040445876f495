"""Least time the chip could take for a step's token rows (benchmark/work_lm.py:
the unique rows of the generated ids, read for the pull, read and written for
the apply, against the HBM peak) over the device time of everything that is no
matrix product (`class_s["other"]`): the share of that remainder the wide-row
sparse path has to be."""

from benchmark import work_lm


def read(trace, run, params):
    if not trace or not run.get("steps") or "hidden_size" not in run["cfg"]:
        return None
    seconds = trace["class_s"]["other"] / run["steps"]
    if seconds <= 0:
        return None
    least = work_lm.token_row_bytes_per_step(run["cfg"], run["ids"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
