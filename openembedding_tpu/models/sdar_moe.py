"""SDAR (`sdar_moe`: the Qwen3-MoE decoder, converted to generate by diffusion
over blocks) on the normal train path, trained by block diffusion (BD3-LM,
arXiv:2503.09573).

Token ids are the sparse feature behind the token table (`sparse.pull` /
`sparse.apply`, as NemotronH's); the flax module is the decoder stack, the
final RMSNorm and the untied head. A step's batch is NOT ids and ids shifted:

- `sparse.token` (B, 2L) = [x_t ; x_0]: a noised copy of each sequence, in
  which every position of a denoising block b (`block_length` tokens) is the
  mask id with probability t_b, then the clean sequence; both halves take the
  rotary positions 0..L-1.
- `label` (B, L) = x_0, unshifted: a masked position is scored against its own
  clean token.
- `dense` (B, L): each noisy position's loss weight, 1 / t_b where masked and
  0 elsewhere (`corrupt_blocks` draws x_t and the weights from a key on the
  device; the benchmark draws them on the host).

rms(.) is RMSNorm with a learned scale (eps `rms_norm_eps`). A layer:

- u = rms(x); q_h = rms_h(W_q,h u), k_g = rms_h(W_k,g u), v_g = W_v,g u (a
  per-head RMSNorm over `head_dim`, no bias); rotary half-rotation over all
  of a head's dims, theta `rope_theta`, positions `i mod L`
  (`zaya1.rope_half`); a = W_o softmax(q k^T / sqrt(head_dim) + M) v over
  grouped key/value heads, M the block-diffusion mask
  (`nemotron_h.diffusion_pairs`: a noisy query sees the noisy keys of its own
  denoising block and the clean keys of earlier ones, a clean query the clean
  keys of its own block and earlier ones; on a TPU at a shape its tiling
  takes, the fused kernel's block-diffusion walk, `ops/flash_attention.py`).
- h = x + a; w = rms(h); p = softmax(W_r w) over `num_experts` in f32, the
  top `num_experts_per_tok` kept, gates p_top / sum(p_top)
  (`norm_topk_prob`); y = h + sum_e g_e W_down,e (silu(W_gate,e w) * W_up,e
  w) through `nemotron_h.MoE(gated=True)` handed that routing, over the
  experts HELD (`experts_held` from `expert_offset`): what absent experts add
  is left out; no shared expert.
- After the last layer: rms, then the head over the NOISY half alone; loss
  `diffusion_xent` = sum_i weight_i CE(logits_i, x_0,i) / (B L).

Stage names (`utils/trace.py`): `bd.embed` (the two halves' rows),
`attn.{qkv,qk_norm,rope,core,out}`, `moe.{route,dispatch,experts,combine}`,
`bd.head`, `bd.loss`. Counters: `moe.*` as `nemotron_h`;
`attn.cores{path="block_diffusion"}` (trace time, a call site the kernel's
walk takes); `window_stats` `bd.key_block_share` (the key blocks the kernel
walks over those a causal walk over 2L would, at the block it chose; 1.0
where it refuses the shape) and `bd.masked_share` (masked noisy positions
over B L), means over a window.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..embedding import Embedding
from ..initializers import Normal
from ..model import EmbeddingModel
from ..utils import trace as _trace
from .nemotron_h import (TOKEN, MoE, NemotronH, _flash, _fold_layers,
                         _keep_products, blockwise_causal_attention,
                         rms_norm, token_xent)
from .zaya1 import rope_half

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size of a layer, as the published config names them
    (`experts_held`, `expert_offset`, `working_pairs`, `block_length`,
    `attention_block`: this program's own)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    experts_held: int
    expert_offset: int
    working_pairs: int
    eps: float
    block_length: int
    attention_block: int


class Attention(nn.Module):
    """Grouped-query attention with per-head q / k norms and rotary positions
    `i mod L` over the doubled sequence, under the block-diffusion mask."""

    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        c = self.dims
        B, S, _ = u.shape
        Hq, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        ones = nn.initializers.ones

        def proj(name, heads):
            y = nn.Dense(heads * D, use_bias=False, dtype=self.dtype,
                         name=name)(u)
            return y.reshape(B, S, heads, D)

        with _trace.scope("attn", "qkv"):
            q, k, v = proj("q_proj", Hq), proj("k_proj", Hkv), proj("v_proj", Hkv)
        with _trace.scope("attn", "qk_norm"):
            q = rms_norm(q, self.param("q_norm_scale", ones, (D,)), c.eps)
            k = rms_norm(k, self.param("k_norm_scale", ones, (D,)), c.eps)
        with _trace.scope("attn", "rope"):
            pos = jnp.arange(S) % (S // 2)
            q = rope_half(q, pos, c.rope_theta, D)
            k = rope_half(k, pos, c.rope_theta, D)
        with _trace.scope("attn", "core"):
            o = blockwise_causal_attention(q, k, v, block=c.attention_block,
                                           denoise=c.block_length)
        with _trace.scope("attn", "out"):
            return nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                            name="o_proj")(o.reshape(B, S, Hq * D))


def softmax_top_k(w, kernel, k: int, renormalise: bool = True):
    """The router: p = softmax(w W_r) in f32 (the product at highest), the
    top `k` kept; gates p_top, over their sum where `renormalise`.
    -> (chosen (T, k), gate (T, k))."""
    logits = jnp.dot(w.astype(jnp.float32), kernel, precision=HI)
    p = jax.nn.softmax(logits, axis=-1)
    gate, chosen = jax.lax.top_k(p, k)
    if renormalise:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return chosen, gate


class Block(nn.Module):
    """h = x + Attention(rms(x)); h + MoE(rms(h)) -> (out, the routed
    layer's step stats)."""

    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.dims
        B, S, D = x.shape
        ones = nn.initializers.ones
        u = rms_norm(x, self.param("attn_norm_scale", ones, (D,)), c.eps)
        h = x + Attention(c, self.dtype, name="attn")(u).astype(x.dtype)
        w = rms_norm(h, self.param("ffn_norm_scale", ones, (D,)), c.eps)
        with _trace.scope("moe", "route"):
            kernel = self.param("router_kernel", nn.initializers.lecun_normal(),
                                (D, c.num_experts))
            routing = softmax_top_k(w.reshape(B * S, D), kernel,
                                    c.num_experts_per_tok, c.norm_topk_prob)
        y, stats = MoE(D, c.num_experts, c.num_experts_per_tok,
                       c.moe_intermediate_size, 0, c.experts_held,
                       c.expert_offset, working_pairs=c.working_pairs,
                       dtype=self.dtype, gated=True, name="moe")(w, routing)
        return h + y.astype(h.dtype), stats


class SdarMoE(nn.Module):
    """The stack over the pulled rows of [x_t ; x_0] and the noisy half's
    weights (the batch's `dense`) -> ((B, L, vocabulary) f32 logits of the
    noisy half, its weights (B, L) f32): `diffusion_xent` takes both."""

    num_layers: int
    vocabulary: int
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # per-step stats -> how a `train_many` window folds them (`Trainer`)
    window_stats = NemotronH.window_stats + (("bd.key_block_share", "avg"),
                                             ("bd.masked_share", "avg"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        c, dt = self.dims, self.compute_dtype
        with _trace.scope("bd", "embed"):
            x = embedded[TOKEN].astype(dt)
            S = x.shape[1]
            if S % 2 or dense_inputs is None or dense_inputs.shape[1] != S // 2:
                raise ValueError("ids [x_t ; x_0] (B, 2L) and the noisy half's "
                                 "weights (B, L) as the batch's dense entry")
            weight = jnp.asarray(dense_inputs, jnp.float32)
        # a layer keeps its input and its plain products' outputs for the
        # backward pass and makes the rest again (as `nemotron_h.NemotronH`)
        layer = nn.remat(Block, policy=_keep_products)
        per_layer = []
        for i in range(self.num_layers):
            x, stats = layer(c, dt, name=f"layers_{i}")(x)
            per_layer.append(stats)
        with _trace.scope("bd", "head"):
            scale = self.param("norm_f_scale", nn.initializers.ones,
                               (c.hidden_size,))
            head = self.param("lm_head", nn.initializers.lecun_normal(),
                              (c.hidden_size, self.vocabulary))
            logits = jnp.dot(rms_norm(x[:, :S // 2], scale, c.eps),
                             head.astype(dt), preferred_element_type=jnp.float32)
        out = (logits, weight)
        if not with_stats:
            return out
        block = _flash().diffusion_block(S, c.head_dim, c.head_dim,
                                         c.num_attention_heads,
                                         c.num_key_value_heads, c.block_length)
        share = 1.0 if block is None else _flash().diffusion_key_block_share(
            S, block)
        stats = _fold_layers(per_layer)
        stats["bd.key_block_share"] = jnp.float32(share)
        stats["bd.masked_share"] = jnp.mean((weight > 0).astype(jnp.float32))
        return out, stats

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (outputs, {stat name: scalar}): the step's `window_stats`."""
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


def diffusion_xent(outputs, labels):
    """A `loss_fn` for `SdarMoE`'s (noisy-half logits, weights): sum_i
    weight_i CE(logits_i, labels_i) / (B L), labels the clean ids (B, L)."""
    logits, weight = outputs
    with _trace.scope("bd", "loss"):
        return jnp.sum(token_xent(logits, labels) * weight) / labels.size


def corrupt_blocks(key, x0, block: int, eps: float, mask_id: int):
    """Block diffusion's noise, drawn on the device: x0 (B, L) clean ids, L a
    multiple of `block` -> (x_t (B, L), weights (B, L) f32). Each denoising
    block b of a sequence draws t_b ~ U[eps, 1]; each of its positions is
    `mask_id` with probability t_b, and weighs 1 / t_b where masked, 0
    elsewhere (a linear schedule; the masked share is E[t] = (1 + eps) / 2).
    A batch for `make_sdar_moe` is {"sparse": {"token": [x_t ; x_0]},
    "dense": weights, "label": x0}."""
    B, L = x0.shape
    if L % block:
        raise ValueError(f"a sequence of {L} is no whole number of blocks of {block}")
    k_t, k_mask = jax.random.split(key)
    t = jax.random.uniform(k_t, (B, L // block), jnp.float32, eps, 1.0)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(k_mask, (B, L), jnp.float32) < t
    return (jnp.where(masked, jnp.asarray(mask_id, x0.dtype), x0),
            jnp.where(masked, 1.0 / t, 0.0))


def make_sdar_moe(vocabulary: int, hidden_size: int, num_hidden_layers: int, *,
                  num_attention_heads: int, num_key_value_heads: int,
                  head_dim: int, rope_theta: float = 1_000_000.0,
                  num_experts: int, num_experts_per_tok: int,
                  moe_intermediate_size: int, norm_topk_prob: bool = True,
                  experts_held: Optional[int] = None, expert_offset: int = 0,
                  working_pairs: int = 0, eps: float = 1e-6,
                  block_length: int = 4, attention_block: int = 512,
                  optimizer=None, compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """SDAR as an `EmbeddingModel` trained by block diffusion. Batches:
    {"sparse": {"token": (B, 2L) int32 [x_t ; x_0]}, "dense": (B, L) f32 the
    noisy half's weights, "label": (B, L) int32 x_0} (module docstring;
    `corrupt_blocks` makes one). `num_hidden_layers`: the layers held here;
    `experts_held` / `expert_offset`: the experts this program holds, [offset,
    offset + held) of `num_experts` (default: all; the router keeps
    `num_experts` outputs); `vocabulary`: the rows of the table and of the
    head held here, the mask id among them; `block_length`: the denoising
    block."""
    held = num_experts if experts_held is None else experts_held
    if not 0 < held <= num_experts - expert_offset:
        raise ValueError(f"experts [{expert_offset}, {expert_offset + held}) "
                         f"are not among {num_experts}")
    if num_attention_heads % num_key_value_heads or head_dim % 2:
        raise ValueError("query heads must divide by key/value heads, and "
                         "rotary positions turn pairs: head_dim is even")
    dims = Dims(
        hidden_size=hidden_size, num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        rope_theta=float(rope_theta), num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        norm_topk_prob=norm_topk_prob, experts_held=held,
        expert_offset=expert_offset, working_pairs=working_pairs, eps=eps,
        block_length=block_length, attention_block=attention_block)
    module = SdarMoE(num_layers=num_hidden_layers, vocabulary=vocabulary,
                     dims=dims, compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=1.0),
                    optimizer=optimizer)
    config = dict(family="sdar_moe", vocabulary=vocabulary,
                  num_hidden_layers=num_hidden_layers,
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(module, [emb], loss_fn=diffusion_xent, config=config)
