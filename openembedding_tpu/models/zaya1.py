"""ZAYA1 (a decoder whose every layer is a compressed convolutional attention
sub-layer, then a top-1 routed expert sub-layer; the router an MLP whose
state travels up the stack; the token table tied to the head) on the normal
train path.

Token ids are the sparse feature, but the token table trains DENSELY: it is
the output head too (`tie_word_embeddings`), every row of it gets a gradient
every step, so the `Embedding` is `sparse_as_dense` (the reference system's
'Cache' mode), its rows are looked up through `model.sad_rows`, and the
module reads the whole table beside them (`takes_tables`,
`model.TABLES_KEY`): ONE parameter, one Adagrad accumulator, one step on the
sum of the lookup's and the head's gradients.

r is the residual stream (the compute dtype), u = RMSNorm(r); a layer l:

- Residual merge (both sub-layers), y the sub-layer's output:
  r <- s_r * (r + b_r) + s_y * (y + b_y), four vectors a channel (scales from
  1, biases from 0); layer 0's first sub-layer has no (s_r, b_r). f32.
- Compressed convolutional attention (`CCA`; arXiv:2510.04476), H query
  heads over G key/value heads of width d: q~ = u W_q, k~ = u W_k, no bias;
  z = [q~ ; k~] ((H + G) d channels); c = Conv_B(Conv_A(z)), Conv_A depthwise
  causal (`nemotron_h.causal_conv`, `cca_time0` taps, bias), Conv_B causal,
  `cca_time1` taps, bias, grouped one group a head (d -> d inside a group);
  m_q[h] = (q~[h] + k~[g(h)]) / 2, m_k[j] = the mean of m_q over the heads of
  group j; q = c_q + m_q, k = c_k + m_k; v = [u_t W_v1 ; u_{t-1} W_v2]: the
  first half of the key/value heads hold the current position's values, the
  second half the PREVIOUS position's (u_{-1} = 0); q <- sqrt(d) q / |q|,
  k <- sqrt(d) exp(tau_j) k / |k| (tau a key/value head, from 0); rotary
  angles on the first `rotary_dim` dims of each head (`rope_half`); causal
  softmax at scale d^-1/2 (`blockwise_causal_attention`); W_o.
- Router (`Router`; arXiv:2511.17127), all f32, matmuls at highest:
  rho_l = u W_d + b_d (-> `router_hidden_size`); for l > 0 rho_l <- rho_l +
  gamma_l * rho_{l-1} (gamma a channel, from 1; rho_l as just formed goes on
  to layer l + 1: a second stream beside r); logits = W_3 GELU(W_2 GELU(W_1
  RMSNorm(rho_l) + b_1) + b_2); p = softmax(logits); e = argmax(p + bias)
  (a balancing buffer: no gradient reaches it); gate = p_e.
- Expert sub-layer: `nemotron_h.MoE(gated=True, shared_width=0)` handed the
  router's (e, gate): y = gate * W_down_e (silu(W_gate_e u) * (W_up_e u));
  no shared expert; a token whose expert is held elsewhere gets 0 here.
- Head: logits = RMSNorm(r_L) E^T, E the token table itself, f32.

Stage names (`utils/trace.py`): `cca.{project,conv,mean_norm,out}`,
`attn.core`, `router.mlp` inside `moe.route`, `moe.{dispatch,experts,
combine}`, `lm.{head,loss}`. Counters: `moe.*` and `attn.cores{path=}` as
`nemotron_h`; `router.gate_mean` (the mean chosen probability, mean over
layers: the scale of the whole expert sub-layer, 1 / experts under a flat
router) and `cca.key_temp_max` (the largest exp(tau)), both `window_stats`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..embedding import Embedding
from ..initializers import Normal
from ..model import TABLES_KEY, EmbeddingModel
from ..utils import trace as _trace
from .nemotron_h import (TOKEN, MoE, NemotronH, _fold_layers, _keep_products,
                         blockwise_causal_attention, causal_conv, rms_norm,
                         softmax_xent)
from .solar_open2 import _l2_norm

HI = jax.lax.Precision.HIGHEST


def rope_half(x, positions, theta: float, rotary_dim: int):
    """Rotary positions by half-rotation over the first `rotary_dim` dims of
    each head: with R = rotary_dim, (x_i, x_{i + R/2}) turned by the angle
    pos * theta^(-2i/R), i < R/2; the dims past R pass through. x (B, S, H,
    D); positions (S,). Angles and the turn in f32, the input's dtype out
    (`joyai_flash.rope_interleaved`'s sibling: pairs a half apart, not
    neighbours)."""
    half = rotary_dim // 2
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (S, R/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b, rest = (x32[..., :half], x32[..., half:rotary_dim],
                  x32[..., rotary_dim:])
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1).astype(x.dtype)


def grouped_causal_conv(x, w, bias, dtype):
    """Causal convolution over time, grouped: x (B, S, N, d) (N groups of d
    channels), w (K, N, d, d) (tap, group, in, out), bias (N, d) -> (B, S, N,
    d) f32; tap j reads position t - (K - 1) + j, positions before the
    sequence read 0. `dtype` inputs, f32 accumulation. The group axis leads
    the product (where a batch axis of a product ends up anyway; given in
    the middle, the CPU backend has no bf16 x bf16 -> f32 product for it)."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(jnp.moveaxis(x.astype(dtype), 2, 0),
                     ((0, 0), (0, 0), (K - 1, 0), (0, 0)))
    acc = 0.0
    for j in range(K):
        acc = acc + jnp.einsum("nbsi,nio->nbso", padded[:, :, j:j + S],
                               w[j].astype(dtype),
                               preferred_element_type=jnp.float32)
    return jnp.moveaxis(acc, 0, 2) + bias.astype(jnp.float32)


class CCA(nn.Module):
    """Compressed convolutional attention (module docstring) over u =
    RMSNorm(r) -> (the sub-layer's output, the largest exp(tau))."""

    hidden: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    time0: int
    time1: int
    rotary_dim: int
    rope_theta: float
    block: int = 512
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        B, S, _ = u.shape
        H, G, d = self.num_heads, self.num_kv_heads, self.head_dim
        N, f32 = H + G, jnp.float32

        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            name=name)(y)

        with _trace.scope("cca", "project"):
            qk = jnp.concatenate([dense("q_proj", H * d, u),
                                  dense("k_proj", G * d, u)], axis=-1)
            # the second half of the value heads read the previous position:
            # u_{t-1} W_v2 is (u W_v2) moved one position on (no bias)
            late = dense("v_proj_prev", G * d // 2, u)
            late = jnp.pad(late, ((0, 0), (1, 0), (0, 0)))[:, :S]
            v = jnp.concatenate([dense("v_proj_now", G * d - G * d // 2, u),
                                 late], axis=-1).reshape(B, S, G, d)
        with _trace.scope("cca", "conv"):
            normal = nn.initializers.normal
            c = causal_conv(
                qk,
                self.param("conv0_kernel", normal(self.time0 ** -0.5),
                           (self.time0, N * d)),
                self.param("conv0_bias", nn.initializers.zeros, (N * d,)))
            c = grouped_causal_conv(
                c.reshape(B, S, N, d),
                self.param("conv1_kernel",
                           normal((self.time1 * d) ** -0.5),
                           (self.time1, N, d, d)),
                self.param("conv1_bias", nn.initializers.zeros, (N, d)),
                self.dtype)
        with _trace.scope("cca", "mean_norm"):
            heads = qk.astype(f32).reshape(B, S, N, d)
            mq = 0.5 * (heads[:, :, :H].reshape(B, S, G, H // G, d)
                        + heads[:, :, H:, None])
            mk = jnp.mean(mq, axis=3)
            q = c[:, :, :H] + mq.reshape(B, S, H, d)
            k = c[:, :, H:] + mk
            tau = self.param("key_temp", nn.initializers.zeros, (G,))
            temp = jnp.exp(tau.astype(f32))
            q = _l2_norm(q) * d ** 0.5
            k = _l2_norm(k) * (d ** 0.5 * temp)[:, None]
            pos = jnp.arange(S)
            q = rope_half(q, pos, self.rope_theta, self.rotary_dim)
            k = rope_half(k, pos, self.rope_theta, self.rotary_dim)
        with _trace.scope("attn", "core"):
            o = blockwise_causal_attention(
                q.astype(self.dtype), k.astype(self.dtype), v,
                block=self.block)
        with _trace.scope("cca", "out"):
            return (dense("o_proj", self.hidden, o.reshape(B, S, H * d)),
                    jnp.max(temp))


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


class Router(nn.Module):
    """The router MLP with carried state (module docstring): u (T, D), prev
    (T, R) the layer below's state or None -> (chosen (T, 1), gate (T, 1)
    f32, this layer's state (T, R) f32). All f32, matmuls at highest."""

    hidden: int
    n_experts: int
    eps: float

    @nn.compact
    def __call__(self, u, prev):
        R, f32 = self.hidden, jnp.float32

        def dense(name, width, y, bias=True):
            return nn.Dense(width, use_bias=bias, dtype=f32, precision=HI,
                            name=name)(y)

        rho = dense("down", R, u.astype(f32))
        if prev is not None:
            rho = rho + self.param("carry_scale", nn.initializers.ones,
                                   (R,)) * prev
        h = rms_norm(rho, self.param("norm_scale", nn.initializers.ones, (R,)),
                     self.eps)
        h = _gelu(dense("fc1", R, h))
        h = _gelu(dense("fc2", R, h))
        p = jax.nn.softmax(dense("fc3", self.n_experts, h, bias=False),
                           axis=-1)
        bias = self.param("balance_bias", nn.initializers.zeros,
                          (self.n_experts,))
        chosen = jnp.argmax(p + jax.lax.stop_gradient(bias), axis=-1)[:, None]
        return chosen, jnp.take_along_axis(p, chosen, axis=-1), rho


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size of a decoder layer, as the published config names them
    (`experts_held`, `expert_offset`, `working_pairs`, `attention_block`:
    this program's own)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    cca_time0: int
    cca_time1: int
    partial_rotary_factor: float
    rope_theta: float
    router_hidden_size: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    experts_held: int
    expert_offset: int
    working_pairs: int
    eps: float
    attention_block: int


class ResidualMerge(nn.Module):
    """r <- s_r * (r + b_r) + s_y * (y + b_y) in f32, r's dtype out;
    `scale_residual` False: r <- r + s_y * (y + b_y)."""

    scale_residual: bool = True

    @nn.compact
    def __call__(self, r, y):
        D, ones, zeros = r.shape[-1], nn.initializers.ones, nn.initializers.zeros
        r32 = r.astype(jnp.float32)
        if self.scale_residual:
            r32 = (self.param("res_scale", ones, (D,))
                   * (r32 + self.param("res_bias", zeros, (D,))))
        y32 = (self.param("out_scale", ones, (D,))
               * (y.astype(jnp.float32) + self.param("out_bias", zeros, (D,))))
        return (r32 + y32).astype(r.dtype)


class DecoderLayer(nn.Module):
    """The attention sub-layer, then the expert sub-layer -> (r, this
    layer's router state, the layer's step stats). `first`: the model's layer
    0 (no residual scale on its first merge; `carried`, the router state of
    the layer below, is None there)."""

    first: bool
    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, r, carried):
        c = self.dims
        ones = nn.initializers.ones
        B, S, D = r.shape
        u = rms_norm(r, self.param("attn_norm_scale", ones, (D,)), c.eps)
        y, temp = CCA(D, c.num_attention_heads, c.num_key_value_heads,
                      c.head_dim, c.cca_time0, c.cca_time1,
                      int(c.head_dim * c.partial_rotary_factor), c.rope_theta,
                      c.attention_block, self.dtype, name="cca")(u)
        r = ResidualMerge(not self.first, name="attn_merge")(r, y)
        u = rms_norm(r, self.param("ffn_norm_scale", ones, (D,)), c.eps)
        with _trace.scope("moe", "route"), _trace.scope("router", "mlp"):
            chosen, gate, state = Router(
                c.router_hidden_size, c.num_experts, c.eps, name="router")(
                    u.reshape(B * S, D), carried)
        y, stats = MoE(D, c.num_experts, c.num_experts_per_tok,
                       c.moe_intermediate_size, 0, c.experts_held,
                       c.expert_offset, working_pairs=c.working_pairs,
                       dtype=self.dtype, gated=True, name="moe")(
                           u, (chosen, gate))
        r = ResidualMerge(name="ffn_merge")(r, y)
        stats = dict(stats, gate_mean=jnp.mean(gate), key_temp_max=temp)
        return r, state, stats


class Zaya1(nn.Module):
    """The decoder stack over looked-up token rows and the table itself ->
    (B, S, vocabulary) f32 logits."""

    num_layers: int
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # the module reads the token table whole: it is its head (`model.TABLES_KEY`)
    takes_tables = True
    # per-step stats -> how a `train_many` window folds them (`Trainer`)
    window_stats = NemotronH.window_stats + (("router.gate_mean", "avg"),
                                             ("cca.key_temp_max", "max"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        c, dt = self.dims, self.compute_dtype
        x = embedded[TOKEN].astype(dt)
        table = embedded[TABLES_KEY][TOKEN]
        # a layer keeps its input and its plain products' outputs for the
        # backward pass and makes the rest again (as `nemotron_h.NemotronH`)
        layer = nn.remat(DecoderLayer, policy=_keep_products)
        per_layer, carried = [], None
        for i in range(self.num_layers):
            x, carried, stats = layer(i == 0, c, dt, name=f"layers_{i}")(
                x, carried)
            per_layer.append(stats)
        with _trace.scope("lm", "head"):
            scale = self.param("norm_f_scale", nn.initializers.ones,
                               (c.hidden_size,))
            logits = jnp.einsum("bsd,vd->bsv", rms_norm(x, scale, c.eps),
                                table.astype(dt),
                                preferred_element_type=jnp.float32)
        if not with_stats:
            return logits
        stats = _fold_layers(per_layer)
        stats["router.gate_mean"] = jnp.mean(
            jnp.stack([s["gate_mean"] for s in per_layer]))
        stats["cca.key_temp_max"] = jnp.max(
            jnp.stack([s["key_temp_max"] for s in per_layer]))
        return logits, stats

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (logits, {stat name: scalar}): the step's `window_stats`."""
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


def make_zaya1(vocabulary: int, hidden_size: int, num_hidden_layers: int, *,
               num_attention_heads: int, num_key_value_heads: int,
               head_dim: int, cca_time0: int = 2, cca_time1: int = 2,
               partial_rotary_factor: float = 0.5,
               rope_theta: float = 5_000_000.0, router_hidden_size: int = 256,
               num_experts: int, num_experts_per_tok: int = 1,
               moe_intermediate_size: int,
               experts_held: Optional[int] = None, expert_offset: int = 0,
               working_pairs: int = 0, eps: float = 1e-5,
               attention_block: int = 512, table_init_stddev: float = 0.02,
               compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """ZAYA1 as an `EmbeddingModel`. Batches: {"sparse": {"token": (B, S)
    int32}, "label": (B, S) int32 next tokens}. `num_hidden_layers`: the
    layers held here, from the model's layer 0; `experts_held` /
    `expert_offset`: the routed experts this program holds, [offset, offset +
    held) of `num_experts` (default: all; the router keeps `num_experts`
    outputs); `vocabulary`: the rows of the token table held here, which is
    the head too: it trains densely (`sparse_as_dense`), with the trainer's
    optimizer."""
    held = num_experts if experts_held is None else experts_held
    if not 0 < held <= num_experts - expert_offset:
        raise ValueError(f"experts [{expert_offset}, {expert_offset + held}) "
                         f"are not among {num_experts}")
    if num_attention_heads % num_key_value_heads or num_key_value_heads % 2:
        raise ValueError("query heads must divide by key/value heads, and "
                         "those by 2 (half hold the previous position)")
    if num_experts_per_tok != 1:
        raise ValueError("the router picks ONE expert a token (an argmax)")
    rotary = int(head_dim * partial_rotary_factor)
    if rotary % 2 or not 0 < rotary <= head_dim:
        raise ValueError(f"rotary width {rotary} of a head of {head_dim}")
    dims = Dims(
        hidden_size=hidden_size, num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        cca_time0=cca_time0, cca_time1=cca_time1,
        partial_rotary_factor=partial_rotary_factor, rope_theta=rope_theta,
        router_hidden_size=router_hidden_size, num_experts=num_experts,
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size, experts_held=held,
        expert_offset=expert_offset, working_pairs=working_pairs, eps=eps,
        attention_block=attention_block)
    module = Zaya1(num_layers=num_hidden_layers, dims=dims,
                   compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=table_init_stddev),
                    sparse_as_dense=True)
    config = dict(family="zaya1", vocabulary=vocabulary,
                  num_hidden_layers=num_hidden_layers,
                  table_init_stddev=table_init_stddev,
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(module, [emb], loss_fn=softmax_xent, config=config)
