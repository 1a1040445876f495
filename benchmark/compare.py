"""The comparison that decides `correct` for a training cell, and nothing else.

Both sides hand over the same summary: per-step losses, and per leaf the norm
of the gradients as Adagrad received them (sqrt(sum(acc_end - acc_start)), the
root-sum-square over the followed steps), the norm of the first step's gradient
on the rows no later step touched (which is what the fused dispatch keeps of
step 1), the norm of the parameters' change, and that change on the rows that
only the first three steps touch (what the dispatch keeps of the state after
three steps). `loss3_gap` is `loss_gap` over the first three steps: a seed whose
optimisation overshoots in its last steps amplifies rounding there. Numbers are gaps between the
two sides' norms (not norms of differences), by the worst leaf, against the
reference's norm of that leaf or of the median leaf, whichever is larger.

One rule on the reference: Adagrad's float32 accumulator starts at 0.1, where one
ulp is 7.45e-9, and a leaf whose elements receive less than a few ulps a step
(the wide kernels: g^2 about 2.5e-9 an element) leaves in `acc_end - acc_start` a
staircase of round-off, not its gradient. So `grad_gap` takes only the leaves
whose reference norm reaches `grad_floor` (4 ulps an element-update); the others'
gradients are held by `delta_gap`, which their weights resolve. PERF.md section 2 has
the readings.
"""

from __future__ import annotations

import statistics
from typing import Dict

import numpy as np


def _worst_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    if not ref:
        return 0.0
    med = statistics.median(ref.values())
    worst = 0.0
    for leaf, r in ref.items():
        denom = max(r, med)
        gap = abs(prog[leaf] - r) / denom if denom > 0 else float(prog[leaf] != r)
        worst = max(worst, gap if np.isfinite(gap) else float("inf"))
    return worst


def by_leaf(prog: Dict[str, float], ref: Dict[str, float]) -> Dict[str, list]:
    """For the builder's look: every leaf's [gap, program's norm, reference's norm]."""
    med = statistics.median(ref.values()) if ref else 0.0
    return {leaf: [abs(prog[leaf] - r) / max(r, med) if max(r, med) > 0 else 0.0, prog[leaf], r]
            for leaf, r in ref.items()}


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """`prog`, `ref`: {"losses": (K,), "grad": {leaf: norm}, "first_grad":
    {leaf: norm}, "delta": {leaf: norm}}; `ref` also {"grad_floor": {leaf: norm}}."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        per_step = np.abs(lp - lr) / np.abs(lr)
    per_step = np.where(np.isfinite(per_step), per_step, np.inf)
    floor = ref.get("grad_floor", {})
    resolved = {leaf: r for leaf, r in ref["grad"].items() if r >= floor.get(leaf, 0.0)}
    return {"loss_gap": float(np.max(per_step)), "loss3_gap": float(np.max(per_step[:3])),
            "early_delta_gap": _worst_gap(prog["early_delta"], ref["early_delta"]),
            "grad_gap": _worst_gap(prog["grad"], resolved),
            "first_grad_gap": _worst_gap(prog["first_grad"], ref["first_grad"]),
            "delta_gap": _worst_gap(prog["delta"], ref["delta"])}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """-> {"correct": bool, "compared": {name: {"value", "limit"}}}; every limit
    of the cell must be met, and every number with a limit must be there."""
    compared = {k: {"value": nums[k], "limit": lim} for k, lim in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values())
    return {"correct": bool(ok), "compared": compared}
