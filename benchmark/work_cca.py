"""The work a training step of a compressed-convolutional-attention / top-1
routed-expert decoder with a tied head has to do, counted from the
configuration's widths, the tokens, and the reference's own count of the
(token, choice) pairs routed to the experts held; never from the program's
counters or its op names.
"""

from __future__ import annotations

from typing import Dict, Optional


def balanced_pairs_per_layer(cfg: Dict, tokens: int) -> float:
    """Pairs a balanced router sends the held experts of one layer."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]


def cca_macs_per_token(cfg: Dict) -> int:
    """The attention sub-layer's products a token: q~, k~, the two value
    halves, o; Conv_B's `cca_time1` taps of a d x d product a head (Conv_A is
    depthwise: no matrix product)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (2 * hq + 2 * hkv) + cfg["cca_time1"] * (hq + hkv) * hd


def router_macs_per_token(cfg: Dict) -> int:
    """The router: the down-projection, two square layers, the output layer."""
    d, r = cfg["hidden_size"], cfg["router_hidden_size"]
    return d * r + 2 * r * r + r * cfg["router_width"]


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` tokens: 2*m*n a
    token for every matrix product a token takes part in; for a routed expert
    (SwiGLU: three products), 2*m*n a PAIR routed to an expert held
    (`pairs_per_layer`, mean over the layers; the balanced router's where not
    given); causal attention's two products over the S(S+1)/2 (query, key)
    pairs a sequence has; the tied head once."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    tokens = batch * seq
    if pairs_per_layer is None:
        pairs_per_layer = balanced_pairs_per_layer(cfg, tokens)
    layers = cfg["num_hidden_layers"]
    macs = tokens * (layers * (cca_macs_per_token(cfg) + router_macs_per_token(cfg)) + d * cfg["vocab_size"])
    macs += layers * batch * cfg["num_attention_heads"] * 2 * hd * seq * (seq + 1) / 2
    macs += layers * pairs_per_layer * 3 * d * cfg["moe_intermediate_size"]
    return 2.0 * macs


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not count."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)
