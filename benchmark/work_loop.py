"""The work a training step of a looped dense decoder has to do (a stack of
`num_hidden_layers` layers walked `total_ut_steps` times over the same
weights, an exit through the head and a gate after every pass), counted from
the configuration's widths and the tokens; never from the program's counters
or its op names. The same whatever implements the step: a scanned walk and an
unrolled one count alike.
"""

from __future__ import annotations

from typing import Dict, Optional


def layer_macs_per_token(cfg: Dict) -> int:
    """One layer application's products a token: q, k, v, o; gate, up, down."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * (2 * q + 2 * kv) + 3 * d * cfg["intermediate_size"]


def forward_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward FLOPs of one step of `batch` sequences of `seq` tokens: 2*m*n a
    token for every matrix product a token takes part in, and causal
    attention's two products over the S(S+1)/2 (query, key) pairs a sequence
    has, a layer APPLICATION (`total_ut_steps` x `num_hidden_layers` of them);
    an exit's head and gate once a pass. `pairs_per_layer` is the routed
    families' argument (`readers/family_step_mfu.py` hands it to every work
    module): there is no routed layer here."""
    del pairs_per_layer
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    tokens, passes, layers = batch * seq, cfg["total_ut_steps"], cfg["num_hidden_layers"]
    macs = passes * layers * (tokens * layer_macs_per_token(cfg)
                              + batch * cfg["num_attention_heads"] * 2 * hd * seq * (seq + 1) / 2)
    macs += passes * tokens * (d * cfg["vocab_size"] + d)
    return 2.0 * macs


def train_flops_per_step(cfg: Dict, batch: int, seq: int, pairs_per_layer: Optional[float] = None) -> float:
    """Forward + backward: the backward pass takes two products for each of the
    forward's (the input's gradient and the kernel's). Recomputed work does not
    count: a pass made again in the backward pass is the program's choice."""
    return 3.0 * forward_flops_per_step(cfg, batch, seq, pairs_per_layer)
