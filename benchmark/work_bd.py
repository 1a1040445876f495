"""The work a block-diffusion training step of a Qwen3-MoE decoder (`sdar_moe`)
has to do, counted from the configuration's widths, the positions, the exact
pairs of the block-diffusion mask and the reference's own count of the
(position, choice) pairs routed to the experts held; never from the program's
counters or its op names. The same whatever implements the step.

`seq` is the STACK's length, 2L: the noised copy and the clean one (the
readers' `run["ids"]` is (K, B, 2L)). The stack's products run over all 2L
positions; the head and the loss over the noisy half's L.
"""

from __future__ import annotations

from typing import Dict, Optional


def mask_pairs(seq: int, block: int) -> int:
    """The (query, key) pairs the block-diffusion mask lets through in one
    sequence and head, L = seq / 2 a multiple of `block`: a noisy query sees
    `block` noisy keys and block x (its block's index) clean ones, a clean
    query block x (its block's index + 1): L^2 + L x block in all."""
    half = seq // 2
    return half * half + half * block


def balanced_pairs_per_layer(cfg: Dict, positions: int) -> float:
    """Pairs a balanced router sends the held experts of one layer."""
    return positions * cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_width"]


def projection_macs_per_position(cfg: Dict) -> int:
    """q, k, v and o; the router."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (2 * hq + 2 * hkv) + d * cfg["router_width"]


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` = 2L positions:
    2*m*n a position of the stack for every projection and the router; for a
    routed expert (SwiGLU: three products), 2*m*n a PAIR routed to an expert
    held (`pairs_per_layer`, mean over the layers; the balanced router's where
    not given); the core's two products over the mask's pairs; the head over
    the L noisy positions alone."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    positions = batch * seq
    if pairs_per_layer is None:
        pairs_per_layer = balanced_pairs_per_layer(cfg, positions)
    layers = cfg["num_hidden_layers"]
    macs = layers * positions * projection_macs_per_position(cfg)
    macs += layers * batch * cfg["num_attention_heads"] * 2 * hd * mask_pairs(seq, cfg["block_length"])
    macs += layers * pairs_per_layer * 3 * d * cfg["moe_intermediate_size"]
    macs += batch * (seq // 2) * d * cfg["vocab_size"]
    return 2.0 * macs


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not count."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)


def attn_core_flops(cfg: Dict, batch: int, seq: int) -> float:
    """One layer's attention core, forward and backward (the forward's two
    products and the backward's four, over the mask's pairs; the scores the
    backward pass makes again do not count)."""
    return 3.0 * 2.0 * 2 * cfg["head_dim"] * batch * cfg["num_attention_heads"] * mask_pairs(
        seq, cfg["block_length"])


def attn_core_bytes(cfg: Dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """The least HBM traffic of one layer's core, forward and backward: the
    forward reads q, k, v and writes the output and its f32 log-sum-exp; the
    backward reads q, k, v, the output's gradient, the log-sum-exp and the
    f32 row sums of dO * O, and writes dq, dk, dv."""
    hd = cfg["head_dim"]
    q = batch * seq * cfg["num_attention_heads"] * hd * itemsize
    kv = batch * seq * cfg["num_key_value_heads"] * hd * itemsize
    rows = batch * seq * cfg["num_attention_heads"] * 4
    forward = q + 2 * kv + q + rows
    backward = q + 2 * kv + q + 2 * rows + q + 2 * kv
    return float(forward + backward)
