"""The work a step has to do, counted from the configuration's widths and from
the generated ids; never from the program's counters or its op names. The same
whatever implements the step.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def tower_layers(cfg: Dict) -> List[List[int]]:
    """[fan_in, fan_out] of every matmul of the model, per example."""
    f_in = cfg["num_dense"] + cfg["num_sparse"] * cfg["embedding_dim"]
    layers = [[cfg["num_dense"], 1]]
    for w in list(cfg["hidden"]) + [1]:
        layers.append([f_in, w])
        f_in = w
    return layers


def matmul_flops_per_example(cfg: Dict) -> int:
    """Forward + backward matmul FLOPs of one example: 2*m*n forward, and twice
    that backward (one product for the input's gradient, one for the kernel's).
    The first layer's input gradient (towards the rows) is needed, so it counts."""
    return sum(3 * 2 * m * n for m, n in tower_layers(cfg))


def packed_row_bytes(cfg: Dict) -> int:
    """Bytes of one row with its optimizer slot, over all the model's tables:
    (width weights + width accumulators) * itemsize."""
    dim = cfg["embedding_dim"]
    width = dim + 1  # folded: dim + first order; split: dim and 1, the same total
    return 2 * width * 4


def unique_rows_per_step(ids: np.ndarray, chips: int = 1) -> float:
    """Mean over the stacked steps of the rows a step touches, on the worst chip.
    `ids` (K, B, F). One chip: the step's unique ids. `chips` > 1: the unique ids
    a chip OWNS (id % chips), since the owner reads and writes each once."""
    per_step = []
    for step in ids:
        u = np.unique(step)
        if chips == 1:
            per_step.append(u.size)
        else:
            per_step.append(max(int(np.sum(u % chips == c)) for c in range(chips)))
    return float(np.mean(per_step))


def sparse_bytes_per_step(cfg: Dict, ids: np.ndarray, chips: int = 1) -> float:
    """Least HBM traffic of a step's sparse work on the worst chip: every unique
    row read once for the pull (weights), then read and written once for the
    apply (weights + accumulators)."""
    row = packed_row_bytes(cfg)
    return unique_rows_per_step(ids, chips) * (row / 2 + 2 * row)
