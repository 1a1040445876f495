"""Ouro (a looped language model, arXiv:2510.25741: a dense decoder stack
walked `total_ut_steps` times over the SAME weights, an exit gate a pass, the
expected loss over the exits through one head) on the normal train path.

Token ids are the sparse feature: the token embedding is an `Embedding`
variable (packed pull, dedup, fused sparse apply), the walk is the dense
module `Trainer` trains. No bias in any projection; every norm is an RMSNorm.

h_0 = the pulled token rows. Pass t = 1..T, the same parameters in each:
- x = h_{t-1}; layer l = 1..L (sandwich norms, four a layer):
  x <- x + RMSNorm(Attn(RMSNorm(x))); x <- x + RMSNorm(MLP(RMSNorm(x))).
- Attn: q, k, v = u W_q, u W_k, u W_v (heads of `head_dim`); half-rotation
  rotary on ALL dims of q and k (`zaya1.rope_half`, pairs half a head apart,
  angle pos * theta^(-2i/d)); causal softmax(q k^T / sqrt(d)) v
  (`nemotron_h.blockwise_causal_attention`); W_o.
- MLP: down(silu(gate u) * (up u)) (`nemotron_h._expert_mlp`).
- h_t = RMSNorm(x): the model's final norm INSIDE the walk, the next pass
  reads the normed state.
- Exit t: logits z_t = h_t W_head (f32, untied), l_t the per-token
  cross-entropy of z_t against the next token; gate lam_t = sigmoid(h_t . w_g
  + b_g) (f32).
Exit distribution a token (`exit_distribution`): p_t = lam_t prod_{s<t}(1 -
lam_s) for t < T, p_T the rest of the mass. loss = mean over tokens of
[sum_t p_t l_t - beta H(p)] (`expected_exit_loss`; the paper's first-stage
objective, a uniform prior), gradients through p and l alike.

The walk is ONE traced body, an `nn.scan` over the passes with the parameters
broadcast and the state the carry: the program holds L layer bodies whatever
T is, and the scan's transpose SUMS each leaf's gradient over its T uses,
which is the weight sharing. The head, its cross-entropy and the gate run
INSIDE the pass, where the labels are (`takes_labels`, `model.TARGETS_KEY`),
and are made again in the backward pass: one exit's (B, S, vocabulary) logits
are alive at a time, and what leaves the walk is (T, B, S) losses and gate
values. A layer application keeps its input and the fused core's output and
log-sum-exp for the backward pass and makes the rest again (T x L
applications of activations beside the state). The LAST exit's logits are
made once more after the walk for whoever keeps them (`Trainer`'s step
metrics, eval); `train_many` drops them and the compiler the product.

Stage names (`utils/trace.py`): `attn.{qkv,rope,core,out}`, `mlp.dense`,
`loop.norm` (the final norm between passes), `lm.{head,loss}`, `loop.gate`
(the gate and the exit distribution). Counters: `loop.passes{path="scan"}`
(trace time, one a traced walk), `attn.cores{path=}` as `nemotron_h`;
`window_stats` `loop.exit_entropy` (H(p): ln T when flat), `loop.last_exit_mass`
(p_T: the share of the loss's weight on the full walk), `loop.first_exit_loss`,
`loop.last_exit_loss`.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..embedding import Embedding
from ..initializers import Normal
from ..model import TARGETS_KEY, EmbeddingModel
from ..utils import metrics as _metrics
from ..utils import trace as _trace
from .nemotron_h import (TOKEN, _expert_mlp, _kept_by_name,
                         blockwise_causal_attention, rms_norm, token_xent,
                         weighted_mean)
from .zaya1 import rope_half


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size of a decoder layer, as the published config names them
    (`attention_block`: this program's own)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    eps: float
    attention_block: int


class Attention(nn.Module):
    """Causal attention with rotary positions over the whole head."""

    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        c = self.dims
        B, S, _ = u.shape

        def proj(name, heads):
            y = nn.Dense(heads * c.head_dim, use_bias=False, dtype=self.dtype,
                         name=name)(u)
            return y.reshape(B, S, heads, c.head_dim)

        with _trace.scope("attn", "qkv"):
            q = proj("q_proj", c.num_attention_heads)
            k = proj("k_proj", c.num_key_value_heads)
            v = proj("v_proj", c.num_key_value_heads)
        with _trace.scope("attn", "rope"):
            pos = jnp.arange(S)
            q = rope_half(q, pos, c.rope_theta, c.head_dim)
            k = rope_half(k, pos, c.rope_theta, c.head_dim)
        with _trace.scope("attn", "core"):
            o = blockwise_causal_attention(q, k, v, block=c.attention_block)
        with _trace.scope("attn", "out"):
            return nn.Dense(c.hidden_size, use_bias=False, dtype=self.dtype,
                            name="o_proj")(o.reshape(B, S, -1))


class DecoderLayer(nn.Module):
    """x + RMSNorm(Attn(RMSNorm(x))), then x + RMSNorm(MLP(RMSNorm(x)))."""

    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.dims
        B, S, D = x.shape

        def norm(name, y):
            return rms_norm(y, self.param(name, nn.initializers.ones, (D,)),
                            c.eps)

        h = Attention(c, self.dtype, name="attn")(norm("attn_norm_scale", x))
        x = x + norm("attn_post_norm_scale", h.astype(x.dtype))
        h = norm("ffn_norm_scale", x)
        init = nn.initializers.lecun_normal()
        with _trace.scope("mlp", "dense"):
            h = _expert_mlp(
                h.reshape(B * S, D),
                self.param("mlp_gate", init, (D, c.intermediate_size)),
                self.param("mlp_up", init, (D, c.intermediate_size)),
                self.param("mlp_down", init, (c.intermediate_size, D)),
                self.dtype).reshape(B, S, D)
        return x + norm("ffn_post_norm_scale", h.astype(x.dtype))


def exit_logits(h, head, dtype):
    """One exit's (B, S, vocabulary) f32 logits of the normed state."""
    with _trace.scope("lm", "head"):
        return jnp.dot(h, head.astype(dtype),
                       preferred_element_type=jnp.float32)


@functools.partial(jax.checkpoint, static_argnums=(6, 7))
def _norm_and_exit(x, norm_scale, head, gate_kernel, gate_bias, labels, eps,
                   dtype):
    """The end of a pass: (the stack's output x) -> (h = RMSNorm(x), the
    exit's per-token cross-entropy (B, S), the gate's value (B, S)). Kept for
    the backward pass: x alone; the logits are made again there."""
    with _trace.scope("loop", "norm"):
        h = rms_norm(x, norm_scale, eps)
    logits = exit_logits(h, head, dtype)
    with _trace.scope("lm", "loss"):
        per_token = token_xent(logits, labels)
    with _trace.scope("loop", "gate"):
        lam = jax.nn.sigmoid(
            jnp.dot(h.astype(jnp.float32), gate_kernel.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
            + gate_bias.astype(jnp.float32))
    return h, per_token, lam


class Pass(nn.Module):
    """One walk of the stack: (h_{t-1}; the final norm's scale, the head, the
    gate, the labels: the same in every pass) -> (h_t, (l_t, lam_t))."""

    num_layers: int
    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, norm_scale, head, gate_kernel, gate_bias, labels):
        # a layer application keeps its input and the fused core's output
        # and log-sum-exp, and makes the rest again in the backward pass
        layer = nn.remat(DecoderLayer, policy=_kept_by_name())
        x = h
        for i in range(self.num_layers):
            x = layer(self.dims, self.dtype, name=f"layers_{i}")(x)
        h, per_token, lam = _norm_and_exit(
            x, norm_scale, head, gate_kernel, gate_bias, labels,
            self.dims.eps, self.dtype)
        return h, (per_token, lam)


class Ouro(nn.Module):
    """The walk over pulled token rows -> (the LAST exit's (B, S, vocabulary)
    f32 logits, every exit's per-token cross-entropy (T, B, S), every pass's
    gate value (T, B, S))."""

    num_layers: int
    total_ut_steps: int
    vocabulary: int
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # the exits' cross-entropy runs inside the walk: the module reads the
    # labels (`model.TARGETS_KEY`)
    takes_labels = True
    # per-step stats (`expected_exit_loss` hands them over) -> how a
    # `train_many` window folds them (`Trainer`)
    window_stats = (("loop.exit_entropy", "avg"), ("loop.last_exit_mass", "avg"),
                    ("loop.first_exit_loss", "avg"),
                    ("loop.last_exit_loss", "avg"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None):
        c, dt = self.dims, self.compute_dtype
        D = c.hidden_size
        labels = embedded[TARGETS_KEY]["label"]
        norm_scale = self.param("norm_f_scale", nn.initializers.ones, (D,))
        head = self.param("lm_head", nn.initializers.lecun_normal(),
                          (D, self.vocabulary))
        gate_kernel = self.param("exit_gate_kernel",
                                 nn.initializers.normal(D ** -0.5), (D,))
        gate_bias = self.param("exit_gate_bias", nn.initializers.zeros, (1,))
        _metrics.observe("loop.passes", 1, "sum", labels={"path": "scan"})
        walk = nn.scan(Pass, variable_broadcast="params",
                       split_rngs={"params": False}, in_axes=nn.broadcast,
                       length=self.total_ut_steps)
        h, (per_token, lam) = walk(self.num_layers, c, dt, name="walk")(
            embedded[TOKEN].astype(dt), norm_scale, head, gate_kernel,
            gate_bias[0], labels)
        return exit_logits(h, head, dt), per_token, lam

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (outputs, {}): the step's `window_stats` are the loss's terms
        (`expected_exit_loss`)."""
        return self.apply(variables, embedded, dense_inputs), {}


def exit_distribution(lam):
    """lam (T, ...) the gates' values -> p (T, ...), the probability of
    leaving at each exit: p_t = lam_t * prod_{s<t} (1 - lam_s) for t < T, and
    p_T what is left (lam_T is not read). Sums to 1 over T."""
    survive, out = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * survive)
        survive = survive * (1.0 - lam[t])
    return jnp.stack(out + [survive])


def expected_exit_loss(outputs, labels, weight=None, *,
                       entropy_weight: float = 0.1):
    """`Ouro`'s `loss_fn`: the mean over tokens (weighted by `weight` (B,) or
    (B, S) where given) of sum_t p_t l_t - `entropy_weight` * H(p), p the
    exit distribution of the gates' values. `labels` were read where the
    exits' cross-entropy ran, inside the walk. -> (loss, the step's
    `loop.*` stats)."""
    _, per_token, lam = outputs
    with _trace.scope("loop", "gate"):
        p = exit_distribution(lam.astype(jnp.float32))
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    with _trace.scope("lm", "loss"):
        mean = functools.partial(weighted_mean, weight=weight)
        loss = mean(jnp.sum(p * per_token, axis=0) - entropy_weight * entropy)
        return loss, {"loop.exit_entropy": mean(entropy),
                      "loop.last_exit_mass": mean(p[-1]),
                      "loop.first_exit_loss": mean(per_token[0]),
                      "loop.last_exit_loss": mean(per_token[-1])}


def make_ouro(vocabulary: int, hidden_size: int, num_hidden_layers: int, *,
              total_ut_steps: int = 4, num_attention_heads: int,
              num_key_value_heads: int, head_dim: int, intermediate_size: int,
              rope_theta: float = 1_000_000.0, eps: float = 1e-6,
              exit_entropy_weight: float = 0.1, attention_block: int = 512,
              optimizer=None, compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """Ouro as an `EmbeddingModel`. Batches: {"sparse": {"token": (B, S)
    int32}, "label": (B, S) int32 next tokens}. `num_hidden_layers`: the
    layers held here, walked `total_ut_steps` times a sequence;
    `exit_entropy_weight`: beta of the loss; `vocabulary`: the rows of the
    table and of the head held here."""
    if total_ut_steps < 1:
        raise ValueError("the stack is walked at least once")
    if num_attention_heads % num_key_value_heads or head_dim % 2:
        raise ValueError("query heads must divide by key/value heads, and "
                         "rotary positions turn pairs: head_dim is even")
    dims = Dims(hidden_size=hidden_size,
                num_attention_heads=num_attention_heads,
                num_key_value_heads=num_key_value_heads, head_dim=head_dim,
                intermediate_size=intermediate_size,
                rope_theta=float(rope_theta), eps=eps,
                attention_block=attention_block)
    module = Ouro(num_layers=num_hidden_layers, total_ut_steps=total_ut_steps,
                  vocabulary=vocabulary, dims=dims,
                  compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=1.0),
                    optimizer=optimizer)
    config = dict(family="ouro", vocabulary=vocabulary,
                  num_hidden_layers=num_hidden_layers,
                  total_ut_steps=total_ut_steps,
                  exit_entropy_weight=exit_entropy_weight,
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(
        module, [emb], config=config,
        loss_fn=functools.partial(expected_exit_loss,
                                  entropy_weight=exit_entropy_weight))
