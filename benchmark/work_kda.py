"""The work a training step of a gated delta-rule / softmax hybrid with routed
experts has to do, counted from the configuration's widths (its head counts
are the heads held), the tokens, and the reference's own count of the (token,
choice) pairs routed to the experts held; never from the program's counters
or its op names. `scan_flops` / `scan_bytes` are the chunked delta rule's own,
kept for its roofline share (PERF.md section 7).
"""

from __future__ import annotations

from typing import Dict, Optional


def linear_layers(cfg: Dict) -> int:
    return sum(1 for i in range(cfg["num_hidden_layers"]) if i not in cfg["gqa_layers"])


def balanced_pairs_per_layer(cfg: Dict, tokens: int) -> float:
    """Pairs a balanced router sends the held experts of one layer."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]


def softmax_macs_per_token(cfg: Dict) -> int:
    """The softmax layer's projections: q, k, v, the gate, o."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * hq * (3 if cfg["use_gqa_gate"] else 2) + 2 * d * hkv


def linear_macs_per_token(cfg: Dict) -> int:
    """The linear layer's projections: q, k, v, o; the decay's and the output
    gate's low-rank pair; beta."""
    d, lin, r = cfg["hidden_size"], cfg["linear_attn_config"], cfg["gate_rank"]
    inner = lin["num_heads"] * lin["head_dim"]
    return 4 * d * inner + 2 * (d * r + r * inner) + d * lin["num_heads"]


def chunk_macs(chunk: int, dk: int, dv: int) -> float:
    """One chunk of one head of the chunked form (`models/solar_open2.
    kda_chunked`): A and P over the pairs s < t and s <= t (C^2 Dk together),
    the forward substitution of (I + A) W = [bV | bKe^G] (C(C-1)/2 rows of Dv +
    Dk), K_end^T [W_k | W_v], the state's step (K_end^T W_k) S, U = W_v - W_k S,
    (Q e^G) S and P U."""
    c = chunk
    return (c * c * dk + c * (c - 1) / 2 * (dv + dk) + c * dk * (dk + dv) + dk * dk * dv
            + 2 * c * dk * dv + c * (c + 1) / 2 * dv)


def scan_flops(cfg: Dict, batch: int, seq: int) -> float:
    """Forward FLOPs of ONE linear layer's chunked delta rule over the heads held."""
    lin, c = cfg["linear_attn_config"], cfg["chunk_size"]
    chunks = -(-seq // c)
    return 2.0 * batch * lin["num_heads"] * chunks * chunk_macs(c, lin["head_dim"], lin["head_dim"])


def scan_bytes(cfg: Dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of one linear layer's chunked delta rule, forward: q, k,
    v read and o written at `itemsize` bytes, the decay's exponent (f32) and beta
    read; the state never leaves the chip."""
    lin = cfg["linear_attn_config"]
    inner = lin["num_heads"] * lin["head_dim"]
    return batch * seq * (4 * inner * itemsize + inner * 4 + lin["num_heads"] * 4)


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` tokens: 2*m*n a
    token for every matrix product a token takes part in (SwiGLU: three
    products); for a routed expert, 2*m*n a PAIR routed to an expert held
    (`pairs_per_layer`, mean over the layers; the balanced router's where not
    given); causal attention's two products over the S(S+1)/2 (query, key)
    pairs a sequence has; the chunked delta rule's products a chunk and head."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    tokens = batch * seq
    if pairs_per_layer is None:
        pairs_per_layer = balanced_pairs_per_layer(cfg, tokens)
    width = cfg["moe_intermediate_size"]
    routed = d * cfg["router_width"] + 3 * d * cfg["n_shared_experts"] * width
    layers, linear = cfg["num_hidden_layers"], linear_layers(cfg)
    macs = tokens * (layers * routed + (layers - linear) * softmax_macs_per_token(cfg)
                     + linear * linear_macs_per_token(cfg) + d * cfg["vocab_size"])
    macs += (layers - linear) * batch * cfg["num_attention_heads"] * 2 * hd * seq * (seq + 1) / 2
    macs += layers * pairs_per_layer * 3 * d * width
    return 2.0 * macs + linear * scan_flops(cfg, batch, seq)


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not count."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)
