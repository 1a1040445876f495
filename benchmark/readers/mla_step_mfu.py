"""A latent-attention language-model cell's forward + backward FLOPs a step
(benchmark/work_mla.py: the configuration's widths, the tokens, the
reference's count of routed pairs) against the chip's bf16 peak, over a time:
`params["over"]` = "window" (default): the traced window's seconds a step, the
whole step's share of the peak; "dot": the device time of the matrix-product
class a step (`class_s["dot"]`), the products' own share."""

from benchmark import work_mla


def read(trace, run, params):
    if not trace or not run.get("steps") or "q_lora_rank" not in run["cfg"]:
        return None
    seconds = run["seconds"] if params.get("over", "window") == "window" else trace["class_s"]["dot"]
    if seconds <= 0:
        return None
    batch, seq = run["ids"].shape[1:]
    flops = work_mla.train_flops_per_step(run["cfg"], batch, seq, run.get("ref_pairs_per_layer"))
    return 100.0 * flops * run["steps"] / seconds / run["chips"] / run["peaks"]["bf16_flops_per_s"]
