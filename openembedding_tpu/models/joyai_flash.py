"""JoyAI-LLM-Flash (a DeepSeek-V3-style decoder: multi-head latent attention
in every layer, one leading dense SwiGLU layer, then routed SwiGLU experts,
and a multi-token-prediction module) on the normal train path.

Token ids are the sparse feature: the token embedding is an `Embedding`
variable (packed pull, dedup, fused sparse apply), the decoder stack is the
dense module `Trainer` trains. No bias anywhere; every norm is an RMSNorm.

- Layer: x <- x + Attn(RMSNorm(x)); x <- x + FFN(RMSNorm(x)). FFN is the
  dense MLP down(silu(gate x) * (up x)) in the first `first_k_dense_replace`
  layers and the routed layer after them.
- Latent attention, in its NON-ABSORBED form (keys and values expanded per
  head: the training form; the absorbed form with a latent cache is a
  serving form and is not here): c_q = RMSNorm(x W_qa); q = c_q W_qb, a head
  [q_nope ; q_rot]; [c_kv ; k_rot] = x W_kva, c_kv <- RMSNorm(c_kv);
  [k_nope ; v] a head = c_kv W_kvb; q_rot and k_rot take rotary positions
  over interleaved pairs (x_2i, x_2i+1), angle pos * theta^(-2i/R), with NO
  scaling factor (`rope_scaling: null`), k_rot ONE vector shared by all heads;
  k = [k_nope ; k_rot]; causal softmax(q k^T / sqrt(nope + rot)) v
  (`nemotron_h.blockwise_causal_attention`: keys wider than values); o_proj.
- Routed layer: `nemotron_h.MoE(gated=True)`: sigmoid scores, the top k of
  score + correction bias (a buffer), weights over their sum times
  `routed_scaling_factor`, the experts HELD here (`experts_held`,
  `expert_offset`) and one shared expert, all SwiGLU. `n_group` =
  `topk_group` = 1: no group of experts is masked before the top k.
- Head: RMSNorm -> untied `lm_head` -> f32 logits over the vocabulary slice.
- Multi-token prediction, depth 1 (DeepSeek-V3 section 2.2 as the published
  checkpoints lay it out): h'_t = W_eh [RMSNorm_e(Emb(x_{t+1})) ;
  RMSNorm_h(h_t)], h_t the last held layer's output BEFORE the final norm,
  Emb(x_{t+1}) the pulled row of position t + 1 (the same pull); one more
  decoder layer (latent attention + routed layer), its own final RMSNorm,
  the SAME `lm_head`: logits for x_{t+2}. The last position has no next row:
  it is fed zeros and carries weight 0 in the loss.
  loss = xent_main + `mtp_loss_weight` * xent_mtp (`mtp_xent`).

Stage names (`utils/trace.py`): `attn.{q_latent,kv_latent,rope,core,out}`,
`mlp.dense`, `moe.{route,dispatch,experts,combine,shared}`, `mtp.{merge,
layer,head,loss}` (the module's layer's own stages nest under `mtp.layer`),
`lm.{head,loss}`. Counters: `moe.*` as `nemotron_h`, `lm.main_loss`,
`lm.mtp_loss` (`window_stats`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..embedding import Embedding
from ..initializers import Normal
from ..model import EmbeddingModel
from ..utils import trace as _trace
from .nemotron_h import (TOKEN, MoE, NemotronH, _expert_mlp, _fold_layers,
                         _keep_products, blockwise_causal_attention, rms_norm,
                         xent)


def rope_interleaved(x, positions, theta: float):
    """Rotary positions over interleaved pairs: (x_2i, x_2i+1) turned by the
    angle pos * theta^(-2i/R). x (B, S, H, R), R even; positions (S,). Angles
    and the turn in f32, the input's dtype out. The pair swap is a product
    with a fixed R x R signed permutation (entries 0 and +-1: exact), which
    keeps R in the lanes where a reshape to (R/2, 2) would not."""
    R = x.shape[-1]
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (S, R/2)
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, None, :]        # (S, 1, R)
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, None, :]
    swap = np.zeros((R, R), np.float32)   # (x swap)[2i] = -x[2i+1], [2i+1] = x[2i]
    swap[np.arange(1, R, 2), np.arange(0, R, 2)] = -1.0
    swap[np.arange(0, R, 2), np.arange(1, R, 2)] = 1.0
    turned = jnp.einsum("bshr,rt->bsht", x, jnp.asarray(swap, x.dtype))
    return (x.astype(jnp.float32) * cos
            + turned.astype(jnp.float32) * sin).astype(x.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention, non-absorbed (module docstring)."""

    hidden: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    eps: float
    block: int = 512
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, S, _ = x.shape
        H, N, R, V = (self.num_heads, self.qk_nope_head_dim,
                      self.qk_rope_head_dim, self.v_head_dim)

        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            name=name)(y)

        with _trace.scope("attn", "q_latent"):
            c_q = rms_norm(dense("q_a", self.q_lora_rank, x), self.param(
                "q_norm_scale", nn.initializers.ones, (self.q_lora_rank,)),
                self.eps)
            q = dense("q_b", H * (N + R), c_q).reshape(B, S, H, N + R)
        with _trace.scope("attn", "kv_latent"):
            ckv = dense("kv_a", self.kv_lora_rank + R, x)
            c_kv = rms_norm(ckv[..., :self.kv_lora_rank], self.param(
                "kv_norm_scale", nn.initializers.ones, (self.kv_lora_rank,)),
                self.eps)
            kv = dense("kv_b", H * (N + V), c_kv).reshape(B, S, H, N + V)
        with _trace.scope("attn", "rope"):
            pos = jnp.arange(S)
            q_rot = rope_interleaved(q[..., N:], pos, self.rope_theta)
            k_rot = rope_interleaved(ckv[..., None, self.kv_lora_rank:], pos,
                                     self.rope_theta)           # one "head"
            q = jnp.concatenate([q[..., :N], q_rot], axis=-1)
            k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
                k_rot, (B, S, H, R))], axis=-1)
        with _trace.scope("attn", "core"):
            o = blockwise_causal_attention(q, k, kv[..., N:], block=self.block)
        with _trace.scope("attn", "out"):
            return dense("o_proj", self.hidden, o.reshape(B, S, H * V))


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size of a decoder layer, as the published config names them
    (`experts_held`, `expert_offset`, `working_pairs`, `attention_block`:
    this program's own)."""

    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    experts_held: int
    expert_offset: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    working_pairs: int
    eps: float
    attention_block: int


class DecoderLayer(nn.Module):
    """x + Attn(RMSNorm(x)), then x + FFN(RMSNorm(x)) -> (x, the routed
    layer's step stats; {} for the dense MLP)."""

    routed: bool
    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.dims
        ones = nn.initializers.ones
        h = rms_norm(x, self.param("attn_norm_scale", ones, (c.hidden_size,)),
                     c.eps)
        h = LatentAttention(
            c.hidden_size, c.num_attention_heads, c.q_lora_rank,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.rope_theta, c.eps, c.attention_block, self.dtype,
            name="attn")(h)
        x = x + h.astype(x.dtype)
        h = rms_norm(x, self.param("ffn_norm_scale", ones, (c.hidden_size,)),
                     c.eps)
        stats = {}
        if self.routed:
            h, stats = MoE(
                c.hidden_size, c.n_routed_experts, c.num_experts_per_tok,
                c.moe_intermediate_size,
                c.n_shared_experts * c.moe_intermediate_size, c.experts_held,
                c.expert_offset, c.routed_scaling_factor, c.norm_topk_prob,
                c.working_pairs, self.dtype, gated=True, name="moe")(h)
        else:
            init = nn.initializers.lecun_normal()
            B, S, D = h.shape
            with _trace.scope("mlp", "dense"):
                h = _expert_mlp(
                    h.reshape(B * S, D),
                    self.param("mlp_gate", init, (D, c.intermediate_size)),
                    self.param("mlp_up", init, (D, c.intermediate_size)),
                    self.param("mlp_down", init, (c.intermediate_size, D)),
                    self.dtype).reshape(B, S, D)
        return x + h.astype(x.dtype), stats


class JoyAIFlash(nn.Module):
    """The decoder stack over pulled token rows -> ((B, S, vocabulary) f32
    logits for the next token, the same for the token after it from the
    prediction module); the main logits alone where `mtp` is off."""

    num_layers: int
    first_k_dense: int
    mtp: bool
    vocabulary: int
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # per-step stats -> how a `train_many` window folds them (`Trainer`)
    window_stats = NemotronH.window_stats + (("lm.main_loss", "avg"),
                                             ("lm.mtp_loss", "avg"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        c, dt = self.dims, self.compute_dtype
        ones = nn.initializers.ones
        rows = embedded[TOKEN].astype(dt)
        # a layer keeps its input and its plain products' outputs for the
        # backward pass and makes the rest again (as `nemotron_h.NemotronH`)
        layer = nn.remat(DecoderLayer, policy=_keep_products)
        x, per_layer = rows, []
        for i in range(self.num_layers):
            x, stats = layer(i >= self.first_k_dense, c, dt,
                             name=f"layers_{i}")(x)
            if stats:
                per_layer.append(stats)
        head = self.param("lm_head", nn.initializers.lecun_normal(),
                          (c.hidden_size, self.vocabulary))

        def logits_of(h, scale):
            return jnp.dot(rms_norm(h, scale, c.eps), head.astype(dt),
                           preferred_element_type=jnp.float32)

        with _trace.scope("lm", "head"):
            out = logits_of(x, self.param("norm_f_scale", ones,
                                          (c.hidden_size,)))
        if self.mtp:
            nxt, stats = MTPModule(c, dt, name="mtp")(rows, x)
            per_layer.append(stats)
            with _trace.scope("mtp", "head"):
                out = (out, logits_of(nxt, self.param(
                    "mtp_norm_scale", ones, (c.hidden_size,))))
        if not with_stats:
            return out
        return out, _fold_layers(per_layer)

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (outputs, {stat name: scalar}): the step's `moe.*`; `mtp_xent`
        adds the two loss terms."""
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


class MTPModule(nn.Module):
    """One multi-token-prediction depth: (token rows (B, S, D), the stack's
    output before its final norm) -> (the module's hidden states, its routed
    layer's stats). Position t merges the row of position t + 1."""

    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, rows, h):
        c = self.dims
        ones = nn.initializers.ones
        with _trace.scope("mtp", "merge"):
            nxt = jnp.pad(rows[:, 1:], ((0, 0), (0, 1), (0, 0)))
            merged = jnp.concatenate([
                rms_norm(nxt, self.param("enorm_scale", ones,
                                         (c.hidden_size,)), c.eps),
                rms_norm(h, self.param("hnorm_scale", ones,
                                       (c.hidden_size,)), c.eps)], axis=-1)
            x = nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                         name="eh_proj")(merged)
        with _trace.scope("mtp", "layer"):
            return nn.remat(DecoderLayer, policy=_keep_products)(
                True, c, self.dtype, name="layer")(x)


def mtp_xent(outputs, labels, weight=None, *, mtp_weight: float = 0.3,
             main_weight: float = 1.0):
    """A `loss_fn` of two terms for `JoyAIFlash`'s (main, mtp) logits:
    `main_weight` * xent(main, labels) + `mtp_weight` * xent(mtp at t,
    labels at t + 1), the second a mean over the S - 1 positions of a
    sequence that have such a label (0 for a stack without the module, whose
    output is the main logits alone). -> (loss, {"lm.main_loss",
    "lm.mtp_loss"}): the terms unweighted, for the step's stats."""
    main, mtp = outputs if isinstance(outputs, tuple) else (outputs, None)
    S = labels.shape[1]
    with _trace.scope("lm", "loss"):
        l_main = xent(main, labels, weight)
    if mtp is None:
        return main_weight * l_main, {"lm.main_loss": l_main,
                                      "lm.mtp_loss": jnp.zeros_like(l_main)}
    with _trace.scope("mtp", "loss"):
        has_next = (jnp.arange(S) < S - 1).astype(jnp.float32)
        w = has_next if weight is None else has_next * jnp.asarray(
            weight, jnp.float32).reshape(labels.shape[0], -1)
        l_mtp = xent(mtp, jnp.roll(labels, -1, axis=1),
                     jnp.broadcast_to(w, labels.shape))
    return (main_weight * l_main + mtp_weight * l_mtp,
            {"lm.main_loss": l_main, "lm.mtp_loss": l_mtp})


def make_joyai_flash(vocabulary: int, hidden_size: int, num_hidden_layers: int,
                     *, first_k_dense_replace: int = 1,
                     num_nextn_predict_layers: int = 1,
                     num_attention_heads: int, q_lora_rank: int,
                     kv_lora_rank: int, qk_nope_head_dim: int,
                     qk_rope_head_dim: int, v_head_dim: int,
                     rope_theta: float = 10000.0, intermediate_size: int,
                     n_routed_experts: int, num_experts_per_tok: int,
                     moe_intermediate_size: int, n_shared_experts: int = 1,
                     experts_held: Optional[int] = None, expert_offset: int = 0,
                     routed_scaling_factor: float = 1.0,
                     norm_topk_prob: bool = True, working_pairs: int = 0,
                     eps: float = 1e-6, attention_block: int = 512,
                     mtp_loss_weight: float = 0.3, optimizer=None,
                     compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """JoyAI-LLM-Flash as an `EmbeddingModel`. Batches: {"sparse": {"token":
    (B, S) int32}, "label": (B, S) int32 next tokens}. `num_hidden_layers`:
    the layers held here (the first `first_k_dense_replace` dense, the rest
    routed); `num_nextn_predict_layers` 0 or 1: the prediction module, one
    more routed layer behind the stack, its loss weighed `mtp_loss_weight`;
    `experts_held` / `expert_offset`: the routed experts this program holds,
    [offset, offset + held) of `n_routed_experts` (default: all);
    `vocabulary`: the rows of the table and of the head held here."""
    held = n_routed_experts if experts_held is None else experts_held
    if not 0 < held <= n_routed_experts - expert_offset:
        raise ValueError(f"experts [{expert_offset}, {expert_offset + held}) "
                         f"are not among {n_routed_experts}")
    if num_nextn_predict_layers not in (0, 1):
        raise ValueError("one multi-token-prediction depth or none")
    if qk_rope_head_dim % 2:
        raise ValueError("rotary positions turn pairs: qk_rope_head_dim is even")
    dims = Dims(
        hidden_size=hidden_size, num_attention_heads=num_attention_heads,
        q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
        v_head_dim=v_head_dim, rope_theta=float(rope_theta),
        intermediate_size=intermediate_size, n_routed_experts=n_routed_experts,
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        n_shared_experts=n_shared_experts, experts_held=held,
        expert_offset=expert_offset,
        routed_scaling_factor=routed_scaling_factor,
        norm_topk_prob=norm_topk_prob, working_pairs=working_pairs, eps=eps,
        attention_block=attention_block)
    mtp = bool(num_nextn_predict_layers)
    module = JoyAIFlash(num_layers=num_hidden_layers,
                        first_k_dense=first_k_dense_replace, mtp=mtp,
                        vocabulary=vocabulary, dims=dims,
                        compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=1.0),
                    optimizer=optimizer)
    config = dict(family="joyai_flash", vocabulary=vocabulary,
                  num_hidden_layers=num_hidden_layers,
                  first_k_dense_replace=first_k_dense_replace,
                  num_nextn_predict_layers=num_nextn_predict_layers,
                  mtp_loss_weight=mtp_loss_weight,
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(
        module, [emb], config=config,
        loss_fn=functools.partial(mtp_xent, mtp_weight=mtp_loss_weight))
