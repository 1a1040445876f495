"""The whole step's share of the chip's bf16 peak for a language-model cell
whose configuration names its work module (`"work": "work_<x>"`, a file of
`benchmark/` with `train_flops_per_step(cfg, batch, seq, pairs_per_layer)`):
forward + backward FLOPs a step (the configuration's widths, the tokens, the
reference's count of routed pairs) times the traced window's steps per second.
`None` for a configuration that names none."""

import importlib


def read(trace, run, params):
    if not trace or not run.get("steps") or "work" not in run["cfg"]:
        return None
    work = importlib.import_module("benchmark." + run["cfg"]["work"])
    batch, seq = run["ids"].shape[1:]
    flops = work.train_flops_per_step(run["cfg"], batch, seq, run.get("ref_pairs_per_layer"))
    return 100.0 * flops * run["steps"] / run["seconds"] / run["chips"] / run["peaks"]["bf16_flops_per_s"]
