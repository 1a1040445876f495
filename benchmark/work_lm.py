"""The work a language-model training step has to do, counted from the
configuration's widths, the tokens, and the reference's own count of the
(token, choice) pairs routed to the experts held; never from the program's
counters or its op names. The same whatever implements the step.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmark import work


def pattern_of(cfg: Dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def balanced_pairs_per_layer(cfg: Dict, tokens: int) -> float:
    """Pairs a balanced router sends the held experts of one layer."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` tokens: 2*m*n a
    token for every matrix product a token takes part in; for a routed expert,
    2*m*n a PAIR routed to an expert held (`pairs_per_layer`, mean over the
    expert layers; the balanced router's where not given); causal attention's
    two products over the S(S+1)/2 (query, key) pairs a sequence has; and the
    state-space recurrence's own two products (state update, readout) of
    heads x head_dim x state multiply-adds a token."""
    tokens = batch * seq
    d = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    if pairs_per_layer is None:
        pairs_per_layer = balanced_pairs_per_layer(cfg, tokens)
    per_token = {
        "M": d * (inner + conv_dim + cfg["mamba_num_heads"]) + inner * d
             + 2 * inner * cfg["ssm_state_size"],
        "*": d * (q + 2 * kv) + q * d,
        "E": d * cfg["router_width"] + 2 * d * cfg["moe_shared_expert_intermediate_size"],
    }
    flops = 0.0
    for kind in pattern_of(cfg):
        flops += 2.0 * per_token[kind] * tokens
        if kind == "*":
            flops += batch * 2 * 2.0 * q * seq * (seq + 1) / 2
        if kind == "E":
            flops += 2.0 * 2 * d * cfg["moe_intermediate_size"] * pairs_per_layer
    return flops + 2.0 * d * cfg["vocab_size"] * tokens


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not count."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)


def token_row_bytes_per_step(cfg: Dict, ids: np.ndarray) -> float:
    """Least HBM traffic of a step's token rows: every unique row read once for
    the pull (weights), then read and written once for the apply (weights +
    accumulators). `ids` (K, B, S): the staged steps; the mean over them."""
    row = 2 * cfg["hidden_size"] * 4  # weights + Adagrad accumulators, f32
    return work.unique_rows_per_step(ids) * (row / 2 + 2 * row)
