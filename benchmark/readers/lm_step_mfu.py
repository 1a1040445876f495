"""The whole step's share of the chip's bf16 peak for a language-model cell:
forward + backward FLOPs a step (benchmark/work_lm.py: the configuration's
widths, the tokens, the reference's count of routed pairs) times the traced
window's steps per second."""

from benchmark import work_lm


def read(trace, run, params):
    if not trace or not run.get("steps") or "hybrid_override_pattern" not in run["cfg"]:
        return None
    batch, seq = run["ids"].shape[1:]
    flops = work_lm.train_flops_per_step(run["cfg"], batch, seq, run.get("ref_pairs_per_layer"))
    return 100.0 * flops * run["steps"] / run["seconds"] / run["chips"] / run["peaks"]["bf16_flops_per_s"]
