"""Solar-Open2 (a hybrid decoder: one softmax layer, then three gated
delta-rule linear-attention layers, a period of four; every layer routed) on
the normal train path.

Token ids are the sparse feature: the token embedding is an `Embedding`
variable (packed pull, dedup, fused sparse apply), the decoder stack is the
dense module `Trainer` trains. No bias anywhere; every norm is an RMSNorm.

- Layer l: x <- x + Mix_l(RMSNorm(x)); x <- x + MoE(RMSNorm(x)); Mix_l is the
  softmax layer where l is in `gqa_layers`, else the linear layer.
- Softmax layer (`nemotron_h.Attention(gate=True)`): grouped key/value heads,
  NO positions, causal softmax at scale d^-1/2 (`blockwise_causal_attention`),
  W_o [sigmoid(W_g x) * o], the gate element-wise and as wide as o.
- Linear layer (`KDAMixer`, Kimi Delta Attention, arXiv:2510.26692), a head h
  of width d: q = L2Norm(SiLU(Conv(W_q x))) d^-1/2, k = L2Norm(SiLU(Conv(W_k
  x))), v = SiLU(Conv(W_v x)), Conv a depthwise causal convolution over time
  (`nemotron_h.causal_conv`, no bias); a decay a CHANNEL g = -exp(A_log_h)
  softplus(W_f2 W_f1 x + dt_bias) in R^d; beta = 2 sigmoid(w_b,h . x) (the 2:
  `allow_neg_eigval`, eigenvalues of I - beta k k^T in (-1, 1]); the state
  S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
  S_0 = 0 a sequence; o_t = S_t^T q_t; out = W_o [sigmoid(W_g2 W_g1 x) *
  RMSNorm_head(o)]. Computed chunk by chunk (`kda_chunked`).
- Routed layer: `nemotron_h.MoE(gated=True)`, the experts HELD here and one
  shared expert, all SwiGLU.
- Head: RMSNorm -> untied `lm_head` -> f32 logits over the vocabulary slice.

THE HEADS HELD. `num_attention_heads` / `num_key_value_heads` /
`linear_num_heads` are the heads this program holds of a tensor-parallel
group's (every width per head is the model's own): a sub-layer then returns
the held heads' part of its output projection's sum, and that partial sum
goes on to the next layer (one chip's share; on one chip there is no
all-reduce and nothing stands in for one). The low-rank down-projections
W_f1, W_g1 and every norm are whole on every chip.

Stage names (`utils/trace.py`): `kda.{qkv,conv,gates,scan,gate_norm,out}`,
`attn.{qkv,core,gate,out}`, `moe.{route,dispatch,experts,combine,shared}`,
`lm.{head,loss}`. Counters: `moe.*` as `nemotron_h`; `kda.scans{path=}`
counts the traced call sites of the chunked form; `kda.chunk_decay_floor`
(`window_stats`, a minimum) is the smallest exp of a chunk's cumulated
per-channel exponent.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..embedding import Embedding
from ..initializers import Normal
from ..model import EmbeddingModel
from ..utils import metrics as _metrics
from ..utils import trace as _trace
from .nemotron_h import (TOKEN, Attention, MoE, NemotronH, _dt_bias_init,
                         _fold_layers, _keep_products, _mm, causal_conv,
                         rms_norm, softmax_xent)

SUB_ROWS = 16  # positions of a chunk's sub-block (`kda_chunked`)


def kda_chunked(q, k, v, g, beta, chunk: int = 64, dtype=jnp.float32):
    """The gated delta rule S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1}
    + b_t k_t v_t^T, o_t = S_t^T q_t, chunk by chunk. q, k (B, L, H, Dk);
    v (B, L, H, Dv); g (B, L, H, Dk) f32, <= 0; beta (B, L, H) f32.
    -> (o (B, L, H, Dv) f32, the smallest exp of a chunk's cumulated
    exponent). L need not be a multiple of `chunk`: the tail is padded with
    beta = 0 and g = 0, which neither writes nor decays the state.

    With G the exponent cumulated from a chunk's start and S_0 the state
    entering it, u_t = b_t (v_t - (Diag(e^{G_t}) S_0 + sum_{s<t} Diag(e^{G_t
    - G_s}) k_s u_s^T)^T k_t) makes S_t = Diag(e^{G_t}) S_0 + sum_{s<=t}
    Diag(e^{G_t - G_s}) k_s u_s^T, so inside a chunk (the WY / UT form)
    (I + A) U = b (V - (K e^G) S_0), A_ts = b_t sum_c k_tc k_sc e^{G_tc -
    G_sc} for s < t: a unit lower-triangular solve a head and chunk, W =
    (I + A)^-1 [b V | b K e^G], U = W_v - W_k S_0; O = (Q e^G) S_0 + P U,
    P_ts = sum_c q_tc k_sc e^{G_tc - G_sc} for s <= t; and the chunk leaves
    S' = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U. Only that last line is a
    recurrence: a `lax.scan` over the chunks whose step is one product a
    head (S' = e^{G_C} * S - (K_end^T W_k) S + K_end^T W_v).

    NO FACTOR EXCEEDS 1. e^{G_t - G_s} factored as (q e^{G_t}) (k e^{-G_s})
    overflows once a channel has decayed by e^88 inside a chunk (a fast
    channel does within 40 positions under this model's own start values).
    Here a pair (t, s) in different sub-blocks of `SUB_ROWS` positions is
    factored at the START b of t's sub-block, (q e^{G_t - G_b}) (k e^{G_b -
    G_s}), both exponents <= 0 because s <= b <= t; a pair inside one
    sub-block takes the pairwise form, its exponent G_t - G_s <= 0 itself.
    A factor that underflows to 0 stands for a product smaller still.

    Decays, A, P's diagonal blocks and the solve are f32; every matrix
    product takes `dtype` inputs and accumulates in f32."""
    _metrics.observe("kda.scans", 1, "sum", labels={"path": "chunked"})
    B, L, H, Dk = q.shape
    Dv = v.shape[-1]
    C = chunk
    R = SUB_ROWS if C % SUB_ROWS == 0 else C
    J = C // R
    pad = (-L) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    N = (L + pad) // C
    f32 = jnp.float32

    def chunks(t):  # (B, N*C, H, ...) -> (B, N, H, C, ...)
        return jnp.moveaxis(t.reshape((B, N, C) + t.shape[2:]), 3, 2)

    q, k, v = (chunks(t).astype(f32) for t in (q, k, v))
    beta = chunks(beta.astype(f32))                           # (B,N,H,C)
    G = jnp.cumsum(chunks(g.astype(f32)), axis=3)             # (B,N,H,C,Dk)
    total = G[:, :, :, -1]                                    # (B,N,H,Dk)
    floor = jnp.min(jnp.exp(total))

    # -- pairs in different sub-blocks: products of decayed operands ---------
    Gs = G.reshape(B, N, H, J, R, Dk)
    start = jnp.concatenate([jnp.zeros_like(Gs[:, :, :, :1, -1]),
                             Gs[:, :, :, :-1, -1]], axis=3)   # (B,N,H,J,Dk)
    into = jnp.exp(Gs - start[:, :, :, :, None])              # e^{G_t - G_b}
    rows = jnp.concatenate([k.reshape(Gs.shape) * into,
                            q.reshape(Gs.shape) * into], axis=4)  # (..,J,2R,Dk)
    before = (jnp.arange(C)[None, :] < (jnp.arange(J) * R)[:, None])  # (J,C)
    upto = jnp.exp(jnp.where(before[:, :, None],
                             start[:, :, :, :, None] - G[:, :, :, None],
                             -jnp.inf))                       # e^{G_b - G_s}
    off = _mm("bnhjrd,bnhjsd->bnhjrs", rows, k[:, :, :, None] * upto, dtype)
    # -- pairs inside one sub-block: the pairwise form, f32 -----------------
    low = jnp.tril(jnp.ones((R, R), bool))
    seg = Gs[:, :, :, :, :, None] - Gs[:, :, :, :, None, :]   # (..,J,R,R,Dk)
    kd = jnp.exp(jnp.where(low[:, :, None], seg, -jnp.inf)) \
        * k.reshape(Gs.shape)[:, :, :, :, None]
    same = jnp.eye(J, dtype=f32)

    def whole(off_rows, mine, strict):
        """(.., J, R, C) off-diagonal rows + (.., J, R, R) diagonal blocks
        -> (.., C, C)."""
        diag = jnp.sum(mine.reshape(Gs.shape)[:, :, :, :, :, None] * kd, axis=-1)
        if strict:
            diag = jnp.where(jnp.tril(low, -1), diag, 0.0)
        diag = jnp.einsum("bnhjrs,ji->bnhjris", diag, same).reshape(off_rows.shape)
        return (off_rows + diag).reshape(B, N, H, C, C)

    A = whole(off[:, :, :, :, :R], k, True) * beta[..., None]
    P = whole(off[:, :, :, :, R:], q, False)

    # -- the solve, and what a chunk leaves behind ---------------------------
    into_chunk = jnp.exp(G)
    rhs = jnp.concatenate([v, k * into_chunk], axis=-1) * beta[..., None]
    W = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    Wv, Wk = W[..., :Dv], W[..., Dv:]
    k_end = k * jnp.exp(total[:, :, :, None] - G)
    trans = _mm("bnhcd,bnhce->bnhde", k_end, Wk, dtype)        # (..,Dk,Dk)
    fresh = _mm("bnhcd,bnhce->bnhde", k_end, Wv, dtype)        # (..,Dk,Dv)
    keep = jnp.exp(total)[..., None]                           # (B,N,H,Dk,1)

    def step(S, x):
        keep_n, trans_n, fresh_n = x
        return keep_n * S - _mm("bhde,bhef->bhdf", trans_n, S, dtype) + fresh_n, S

    _, entering = jax.lax.scan(
        step, jnp.zeros((B, H, Dk, Dv), f32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (keep, trans, fresh)))
    entering = jnp.moveaxis(entering, 0, 1)                    # (B,N,H,Dk,Dv)
    U = Wv - _mm("bnhcd,bnhde->bnhce", Wk, entering, dtype)
    o = _mm("bnhcd,bnhde->bnhce", q * into_chunk, entering, dtype) \
        + _mm("bnhcs,bnhse->bnhce", P, U, dtype)
    o = jnp.moveaxis(o, 2, 3).reshape(B, N * C, H, Dv)
    return o[:, :L], floor


def _l2_norm(x, eps=1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, f32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _kda_a_log_init(key, shape, dtype=jnp.float32):
    """log of a draw from uniform(1, 16), a head (the KDA layer's own start)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class KDAMixer(nn.Module):
    """A gated delta-rule linear-attention sub-layer over the heads held
    (module docstring) -> (the held heads' part of the output projection's
    sum, the smallest chunk decay)."""

    hidden: int
    num_heads: int
    head_dim: int
    conv_kernel: int
    gate_rank: int
    chunk: int
    eps: float
    allow_neg_eigval: bool = True
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, S, _ = x.shape
        H, D, K = self.num_heads, self.head_dim, self.conv_kernel
        inner = H * D

        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            name=name)(y)

        with _trace.scope("kda", "qkv"):
            qkv = jnp.concatenate([dense(n, inner, x) for n in
                                   ("q_proj", "k_proj", "v_proj")], axis=-1)
        with _trace.scope("kda", "conv"):
            w = self.param("conv_kernel", nn.initializers.normal(K ** -0.5),
                           (K, 3 * inner))
            qkv = jax.nn.silu(causal_conv(qkv, w)).reshape(B, S, 3, H, D)
            q = (_l2_norm(qkv[:, :, 0]) * D ** -0.5).astype(self.dtype)
            k = _l2_norm(qkv[:, :, 1]).astype(self.dtype)
            v = qkv[:, :, 2].astype(self.dtype)
        with _trace.scope("kda", "gates"):
            f = dense("f_b", inner, dense("f_a", self.gate_rank, x))
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
            A_log = self.param("A_log", _kda_a_log_init, (H,))
            g = -jnp.exp(A_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
                f.astype(jnp.float32) + dt_bias).reshape(B, S, H, D)
            beta = jax.nn.sigmoid(dense("b_proj", H, x).astype(jnp.float32))
            if self.allow_neg_eigval:
                beta = 2.0 * beta
            out_gate = dense("g_b", inner, dense("g_a", self.gate_rank, x))
        with _trace.scope("kda", "scan"):
            o, floor = kda_chunked(q, k, v, g, beta, self.chunk, self.dtype)
        with _trace.scope("kda", "gate_norm"):
            scale = self.param("o_norm_scale", nn.initializers.ones, (D,))
            o = rms_norm(o, scale, self.eps) * jax.nn.sigmoid(
                out_gate.astype(jnp.float32)).reshape(B, S, H, D)
            o = o.reshape(B, S, inner).astype(self.dtype)
        with _trace.scope("kda", "out"):
            return dense("o_proj", self.hidden, o), floor


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size of a decoder layer, as the published config names them
    (head counts: the heads HELD; `experts_held`, `expert_offset`,
    `working_pairs`, `attention_block`, `chunk_size`, `gate_rank`: this
    program's own)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    use_gqa_gate: bool
    linear_num_heads: int
    linear_head_dim: int
    short_conv_kernel_size: int
    gate_rank: int
    allow_neg_eigval: bool
    chunk_size: int
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
    """x + Mix(RMSNorm(x)), then x + MoE(RMSNorm(x)) -> (x, the routed
    layer's step stats, a linear layer's chunk decay floor or None)."""

    softmax: bool
    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.dims
        ones = nn.initializers.ones
        h = rms_norm(x, self.param("mix_norm_scale", ones, (c.hidden_size,)),
                     c.eps)
        floor = None
        if self.softmax:
            h = Attention(c.hidden_size, c.num_attention_heads,
                          c.num_key_value_heads, c.head_dim, c.attention_block,
                          self.dtype, gate=c.use_gqa_gate, name="attn")(h)
        else:
            h, floor = KDAMixer(
                c.hidden_size, c.linear_num_heads, c.linear_head_dim,
                c.short_conv_kernel_size, c.gate_rank, c.chunk_size, c.eps,
                c.allow_neg_eigval, self.dtype, name="kda")(h)
        x = x + h.astype(x.dtype)
        h = rms_norm(x, self.param("ffn_norm_scale", ones, (c.hidden_size,)),
                     c.eps)
        h, stats = MoE(
            c.hidden_size, c.n_routed_experts, c.num_experts_per_tok,
            c.moe_intermediate_size,
            c.n_shared_experts * c.moe_intermediate_size, c.experts_held,
            c.expert_offset, c.routed_scaling_factor, c.norm_topk_prob,
            c.working_pairs, self.dtype, gated=True,
            name="moe")(h)
        return x + h.astype(x.dtype), stats, floor


class SolarOpen2(nn.Module):
    """The decoder stack over pulled token rows -> (B, S, vocabulary) f32
    logits. `softmax_layers`: the layers (of those held) that are softmax
    layers; the others are linear."""

    num_layers: int
    softmax_layers: Sequence[int]
    vocabulary: int
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # per-step stats -> how a `train_many` window folds them (`Trainer`)
    window_stats = NemotronH.window_stats + (("kda.chunk_decay_floor", "min"),)

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        c, dt = self.dims, self.compute_dtype
        x = embedded[TOKEN].astype(dt)
        # a layer keeps its input and its plain products' outputs for the
        # backward pass and makes the rest again (as `nemotron_h.NemotronH`)
        layer = nn.remat(DecoderLayer, policy=_keep_products)
        per_layer, floors = [], []
        for i in range(self.num_layers):
            x, stats, floor = layer(i in self.softmax_layers, c, dt,
                                    name=f"layers_{i}")(x)
            per_layer.append(stats)
            if floor is not None:
                floors.append(floor)
        with _trace.scope("lm", "head"):
            scale = self.param("norm_f_scale", nn.initializers.ones,
                               (c.hidden_size,))
            head = self.param("lm_head", nn.initializers.lecun_normal(),
                              (c.hidden_size, self.vocabulary))
            logits = jnp.dot(rms_norm(x, scale, c.eps), head.astype(dt),
                             preferred_element_type=jnp.float32)
        if not with_stats:
            return logits
        stats = _fold_layers(per_layer)
        stats["kda.chunk_decay_floor"] = (
            jnp.min(jnp.stack(floors)) if floors else jnp.ones((), jnp.float32))
        return logits, stats

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (logits, {stat name: scalar}): the step's `window_stats`."""
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


def make_solar_open2(vocabulary: int, hidden_size: int, num_hidden_layers: int,
                     *, gqa_layers: Sequence[int], num_attention_heads: int,
                     num_key_value_heads: int, head_dim: int,
                     use_gqa_gate: bool = True, linear_num_heads: int,
                     linear_head_dim: int, short_conv_kernel_size: int = 4,
                     gate_rank: Optional[int] = None,
                     allow_neg_eigval: bool = True, chunk_size: int = 64,
                     n_routed_experts: int, num_experts_per_tok: int,
                     moe_intermediate_size: int, n_shared_experts: int = 1,
                     experts_held: Optional[int] = None, expert_offset: int = 0,
                     routed_scaling_factor: float = 1.0,
                     norm_topk_prob: bool = True, working_pairs: int = 0,
                     eps: float = 1e-5, attention_block: int = 512,
                     optimizer=None,
                     compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """Solar-Open2 as an `EmbeddingModel`. Batches: {"sparse": {"token":
    (B, S) int32}, "label": (B, S) int32 next tokens}. `num_hidden_layers`:
    the layers held here, layer l a softmax layer where l is in `gqa_layers`
    (the published list; entries past the layers held are ignored);
    `num_attention_heads` / `num_key_value_heads` / `linear_num_heads`: the
    heads HELD of each sub-layer (module docstring); `experts_held` /
    `expert_offset`: the routed experts this program holds, [offset, offset +
    held) of `n_routed_experts` (default: all); `gate_rank`: the rank of the
    two low-rank gates (default: `linear_head_dim`); `vocabulary`: the rows
    of the table and of the head held here."""
    held = n_routed_experts if experts_held is None else experts_held
    if not 0 < held <= n_routed_experts - expert_offset:
        raise ValueError(f"experts [{expert_offset}, {expert_offset + held}) "
                         f"are not among {n_routed_experts}")
    if num_attention_heads % num_key_value_heads:
        raise ValueError("query heads must divide by key/value heads")
    dims = Dims(
        hidden_size=hidden_size, num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        use_gqa_gate=bool(use_gqa_gate), linear_num_heads=linear_num_heads,
        linear_head_dim=linear_head_dim,
        short_conv_kernel_size=short_conv_kernel_size,
        gate_rank=linear_head_dim if gate_rank is None else gate_rank,
        allow_neg_eigval=bool(allow_neg_eigval), chunk_size=chunk_size,
        n_routed_experts=n_routed_experts,
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        n_shared_experts=n_shared_experts, experts_held=held,
        expert_offset=expert_offset,
        routed_scaling_factor=routed_scaling_factor,
        norm_topk_prob=norm_topk_prob, working_pairs=working_pairs, eps=eps,
        attention_block=attention_block)
    softmax_layers = tuple(int(i) for i in gqa_layers
                           if int(i) < num_hidden_layers)
    module = SolarOpen2(num_layers=num_hidden_layers,
                        softmax_layers=softmax_layers, vocabulary=vocabulary,
                        dims=dims, compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=1.0),
                    optimizer=optimizer)
    config = dict(family="solar_open2", vocabulary=vocabulary,
                  num_hidden_layers=num_hidden_layers,
                  gqa_layers=list(softmax_layers),
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(module, [emb], loss_fn=softmax_xent, config=config)
