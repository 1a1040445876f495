"""The work a training step of a Mamba-2 / attention hybrid with a SwiGLU after
every mixer and a tied head has to do on PACKED sequences, counted from the
configuration's widths, the tokens and the number of (query, key) pairs that lie
inside documents; never from the program's counters or its op names. The same
whatever implements the step.
"""

from __future__ import annotations

from typing import Dict, Optional


def kinds_of(cfg: Dict):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def mamba_projection_macs_per_token(cfg: Dict) -> int:
    """in_proj to [z ; xBC ; dt] and out_proj (the convolution is depthwise:
    no matrix product)."""
    d = cfg["hidden_size"]
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d * (2 * inner + 2 * bc + cfg["mamba_n_heads"]) + inner * d


def scan_macs_per_token(cfg: Dict) -> float:
    """The chunked form's four products at the published chunk Q, a token:
    C.B^T over the (l, s <= l) pairs of a chunk, ONCE a group (N a pair);
    its product with x over the same pairs, a head (P a pair); what a chunk
    leaves, B^T x, and what a position reads of the entering state, C h, a
    head (N P a token each). The recurrence over chunk states is not a
    matrix product the algorithm needs."""
    q, n, p = cfg["mamba_chunk_size"], cfg["mamba_d_state"], cfg["mamba_d_head"]
    h, g = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    pairs_per_token = (q + 1) / 2
    return pairs_per_token * (g * n + h * p) + 2 * h * n * p


def attention_projection_macs_per_token(cfg: Dict) -> int:
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return d * (2 * cfg["num_attention_heads"] * hd + 2 * cfg["num_key_value_heads"] * hd)


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` tokens: 2*m*n a
    token for every matrix product a token takes part in (the projections, the
    SwiGLU after every mixer, the tied head once); the chunked scan's four
    products a Mamba layer; causal attention's two products over
    `pairs_per_layer`, the (query, key) pairs INSIDE documents a step (one
    document a sequence, S(S+1)/2 pairs, where not given)."""
    d = cfg["hidden_size"]
    tokens = batch * seq
    if pairs_per_layer is None:
        pairs_per_layer = batch * seq * (seq + 1) / 2
    kinds = kinds_of(cfg)
    mamba, attn = kinds.count("mamba"), kinds.count("attention")
    macs = tokens * (len(kinds) * 3 * d * cfg["shared_intermediate_size"] + d * cfg["vocab_size"])
    macs += tokens * mamba * (mamba_projection_macs_per_token(cfg) + scan_macs_per_token(cfg))
    macs += attn * (tokens * attention_projection_macs_per_token(cfg)
                    + pairs_per_layer * cfg["num_attention_heads"] * 2 * (d // cfg["num_attention_heads"]))
    return 2.0 * macs


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not count."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)
