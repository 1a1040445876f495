"""The work a training step of a latent-attention / routed-expert language
model with a multi-token-prediction module has to do, counted from the
configuration's widths, the tokens, and the reference's own count of the
(token, choice) pairs routed to the experts held; never from the program's
counters or its op names. The same whatever implements the step.
"""

from __future__ import annotations

from typing import Dict, Optional


def routed_layers(cfg: Dict) -> int:
    """The stack's routed layers and the prediction module's."""
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] + cfg["num_nextn_predict_layers"]


def balanced_pairs_per_layer(cfg: Dict, tokens: int) -> float:
    """Pairs a balanced router sends the held experts of one layer."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_width"]


def attention_macs_per_token(cfg: Dict) -> int:
    """The latent attention's five projections: x -> c_q -> q, x -> [c_kv ; k_rot],
    c_kv -> [k_nope ; v], o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (n + r) + d * (cfg["kv_lora_rank"] + r)
            + cfg["kv_lora_rank"] * h * (n + v) + h * v * d)


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` tokens: 2*m*n a
    token for every matrix product a token takes part in (SwiGLU: three
    products); for a routed expert, 2*m*n a PAIR routed to an expert held
    (`pairs_per_layer`, mean over the routed layers, the module's among them;
    the balanced router's where not given); causal attention's two products
    over the S(S+1)/2 (query, key) pairs a sequence has, at key width nope +
    rope and value width v. The prediction module works over the S - 1
    positions a sequence that have a token after the next: its merge, its
    layer, its pass through the head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    core = h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    routed = d * cfg["router_width"] + 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    dense = 3 * d * cfg["intermediate_size"]
    head = d * cfg["vocab_size"]
    tokens = batch * seq
    if pairs_per_layer is None:
        pairs_per_layer = balanced_pairs_per_layer(cfg, tokens)
    macs = 0.0
    for i in range(cfg["num_hidden_layers"]):
        ffn = routed if i >= cfg["first_k_dense_replace"] else dense
        macs += tokens * (attention_macs_per_token(cfg) + ffn) + batch * core * seq * (seq + 1) / 2
    macs += tokens * head
    if cfg["num_nextn_predict_layers"]:
        positions = batch * (seq - 1)
        macs += positions * (2 * d * d + attention_macs_per_token(cfg) + routed + head)
        macs += batch * core * (seq - 1) * seq / 2
    macs += routed_layers(cfg) * pairs_per_layer * 3 * d * cfg["moe_intermediate_size"]
    return 2.0 * macs


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not count."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)
