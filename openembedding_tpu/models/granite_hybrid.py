"""Granite 4.0-H (`granitemoehybrid` without experts: nine Mamba-2 layers in
ten, the tenth grouped-query attention without positions, a SwiGLU after
EVERY mixer, four muP scalars, the token table tied to the head) on the
normal train path, trained on documents PACKED into a sequence.

Token ids are the sparse feature, but the token table trains DENSELY: it is
the output head too (`tie_word_embeddings`), so the `Embedding` is
`sparse_as_dense` and the module reads the whole table beside the looked-up
rows (`takes_tables`, `model.TABLES_KEY`; `zaya1.py` has the path): ONE
parameter, one Adagrad accumulator, one step on the sum of the lookup's and
the head's gradients.

A batch carries, beside ids and labels, `dense` = starts (B, S): nonzero where
a document begins (position 0 always does). n_t, the running count of starts,
is the document of position t (`nemotron_h.segment_ids`); no `dense`: one
document a sequence. r is the residual stream (the compute dtype):

- r = `embedding_multiplier` * E[x].
- Layer i: r <- r + `residual_multiplier` * Mixer_i(RMSNorm(r)), then
  r <- r + `residual_multiplier` * W_out(silu(a) * b), [a ; b] = W_in
  RMSNorm(r) (no bias), the scalars and the sums in f32.
- `layer_types[i] == "mamba"`: `nemotron_h.Mamba2Mixer` with the starts: a
  convolution tap reads a position of its own document alone, the state is
  zero before every start (`causal_conv`, `ssd_chunked`); B and C in
  `mamba_n_groups` groups, the gate norm over each group.
- `"attention"`: `nemotron_h.Attention`, no positions, scores times
  `attention_multiplier` (NOT head_dim^-1/2), a query sees the keys k <= q
  of its own document (`blockwise_causal_attention`: on a TPU at a shape
  the fused kernel's tiling takes, the kernel, which skips the key blocks
  no query's document reaches).
- Head: logits = RMSNorm(r_L) E^T / `logits_scaling`, f32; mean cross-entropy
  over every position (`softmax_xent`; no mask at document ends).

Stage names (`utils/trace.py`): `ssm.{in_proj,conv,scan,gate_norm,out_proj}`,
`attn.{qkv,core,out}`, `mlp.dense`, `lm.{head,loss}`, `pack.segments` (starts
-> n, wherever a mask needs it) and `pack.stats`. Counters:
`pack.resets{site="ssd"|"conv"|"attn"}` (trace time, one a traced call site
given starts: 19 a trace of ten layers), `attn.cores{path=}` as `nemotron_h`;
`window_stats` `pack.documents` (documents a sequence),
`pack.longest_doc_share` (the longest document over S) and
`attn.key_block_share` (the key blocks the fused kernel walks over the causal
key blocks, at the block its tiling chose; 1.0 where it refuses the shape),
means over a window.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..embedding import Embedding
from ..initializers import Normal
from ..model import TABLES_KEY, EmbeddingModel
from ..utils import trace as _trace
from .nemotron_h import (TOKEN, Attention, Mamba2Mixer, _flash,
                         _keep_products, _swiglu, rms_norm, softmax_xent)

KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size and scalar of a layer, as the published config names them
    (`head_dim`: hidden_size / num_attention_heads where the config has no
    key; `attention_block`: this program's own)."""

    hidden_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_chunk_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    shared_intermediate_size: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    eps: float
    attention_block: int


def _scaled_add(r, y, by: float):
    """r + by * y in f32, r's dtype out."""
    return (r.astype(jnp.float32) + by * y.astype(jnp.float32)).astype(r.dtype)


class DecoderLayer(nn.Module):
    """The mixer sub-layer, then the SwiGLU sub-layer, each behind its own
    RMSNorm and added times `residual_multiplier`."""

    kind: str
    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, r, starts=None):
        c = self.dims
        B, S, D = r.shape
        ones = nn.initializers.ones
        u = rms_norm(r, self.param("mixer_norm_scale", ones, (D,)), c.eps)
        if self.kind == "mamba":
            y = Mamba2Mixer(D, c.mamba_n_heads, c.mamba_d_head,
                            c.mamba_n_groups, c.mamba_d_state, c.mamba_d_conv,
                            c.mamba_chunk_size, c.eps, self.dtype,
                            name="mixer")(u, starts)
        else:
            y = Attention(D, c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim, c.attention_block, self.dtype,
                          scale=c.attention_multiplier, name="mixer")(u, starts)
        r = _scaled_add(r, y, c.residual_multiplier)
        u = rms_norm(r, self.param("mlp_norm_scale", ones, (D,)), c.eps)
        init = nn.initializers.lecun_normal()
        F = c.shared_intermediate_size
        with _trace.scope("mlp", "dense"):
            w_in = self.param("mlp_in", init, (D, 2 * F))
            w_out = self.param("mlp_out", init, (F, D))
            a, b = jnp.split(jnp.dot(u.reshape(B * S, D),
                                     w_in.astype(self.dtype)), 2, axis=-1)
            y = jnp.dot(_swiglu(a, b), w_out.astype(self.dtype),
                        preferred_element_type=jnp.float32)
        return _scaled_add(r, y.reshape(B, S, D), c.residual_multiplier)


def packing_stats(starts, block=None):
    """-> {`pack.documents`: documents a sequence, `pack.longest_doc_share`:
    the longest document's share of the sequence, `attn.key_block_share`:
    the key blocks the fused attention kernel walks over the causal key
    blocks at `block` (None: the kernel refuses the shape, and every causal
    block is computed)}, means over the batch; `starts` None: one document a
    sequence."""
    one = jnp.ones((), jnp.float32)
    if starts is None:
        return {"pack.documents": one, "pack.longest_doc_share": one,
                "attn.key_block_share": one}
    with _trace.scope("pack", "stats"):
        seq = starts.shape[1]
        at = jnp.arange(seq, dtype=jnp.int32)
        began = jax.lax.cummax(jnp.where(starts != 0, at, 0), axis=1)
        longest = jnp.max(at - began + 1, axis=1).astype(jnp.float32)
        docs = jnp.sum(starts != 0, axis=1).astype(jnp.float32)
        share = one if block is None else _flash().key_block_share(began, block)
        return {"pack.documents": jnp.mean(docs),
                "pack.longest_doc_share": jnp.mean(longest) / seq,
                "attn.key_block_share": share}


class GraniteHybrid(nn.Module):
    """The decoder stack over looked-up token rows, the table itself and the
    batch's document starts -> (B, S, vocabulary) f32 logits."""

    layer_types: Sequence[str]
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # the module reads the token table whole: it is its head (`model.TABLES_KEY`)
    takes_tables = True
    # per-step stats -> how a `train_many` window folds them (`Trainer`)
    window_stats = (("pack.documents", "avg"), ("pack.longest_doc_share", "avg"),
                    ("attn.key_block_share", "avg"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        c, dt = self.dims, self.compute_dtype
        starts = dense_inputs
        table = embedded[TABLES_KEY][TOKEN]
        r = (embedded[TOKEN].astype(jnp.float32)
             * c.embedding_multiplier).astype(dt)
        # a layer keeps its input and its plain products' outputs for the
        # backward pass and makes the rest again (as `nemotron_h.NemotronH`)
        layer = nn.remat(DecoderLayer, policy=_keep_products)
        for i, kind in enumerate(self.layer_types):
            r = layer(kind, c, dt, name=f"layers_{i}")(r, starts)
        with _trace.scope("lm", "head"):
            scale = self.param("norm_f_scale", nn.initializers.ones,
                               (c.hidden_size,))
            logits = jnp.einsum("bsd,vd->bsv", rms_norm(r, scale, c.eps),
                                table.astype(dt),
                                preferred_element_type=jnp.float32)
            logits = logits / c.logits_scaling
        if not with_stats:
            return logits
        block = _flash().tiling(r.shape[1], c.head_dim, c.head_dim,
                                c.num_attention_heads, c.num_key_value_heads)
        return logits, packing_stats(starts, block)

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (logits, {stat name: scalar}): the step's `window_stats`."""
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


def make_granite_hybrid(vocabulary: int, hidden_size: int,
                        num_hidden_layers: int, *,
                        layer_types: Sequence[str], mamba_n_heads: int,
                        mamba_d_head: int, mamba_n_groups: int = 1,
                        mamba_d_state: int = 128, mamba_d_conv: int = 4,
                        mamba_chunk_size: int = 256, num_attention_heads: int,
                        num_key_value_heads: int,
                        head_dim: Optional[int] = None,
                        shared_intermediate_size: int,
                        attention_multiplier: float,
                        embedding_multiplier: float = 1.0,
                        residual_multiplier: float = 1.0,
                        logits_scaling: float = 1.0, eps: float = 1e-5,
                        attention_block: int = 512,
                        table_init_stddev: float = 0.02,
                        compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """Granite 4.0-H as an `EmbeddingModel`. Batches: {"sparse": {"token":
    (B, S) int32}, "dense": (B, S) document starts (nonzero where a document
    begins; None: one document a sequence), "label": (B, S) int32 next
    tokens}. `num_hidden_layers`: the layers held here, the first of
    `layer_types` ("mamba" / "attention" a layer, the published list);
    `vocabulary`: the rows of the token table held here, which is the head
    too: it trains densely (`sparse_as_dense`), with the trainer's
    optimizer."""
    kinds = tuple(layer_types)[:num_hidden_layers]
    if len(kinds) != num_hidden_layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {tuple(layer_types)!r}: one of {KINDS} "
                         f"for each of the {num_hidden_layers} layers held")
    if num_attention_heads % num_key_value_heads or mamba_n_heads % mamba_n_groups:
        raise ValueError("query heads must divide by key/value heads and "
                         "mamba heads by groups")
    dims = Dims(
        hidden_size=hidden_size, mamba_n_heads=mamba_n_heads,
        mamba_d_head=mamba_d_head, mamba_n_groups=mamba_n_groups,
        mamba_d_state=mamba_d_state, mamba_d_conv=mamba_d_conv,
        mamba_chunk_size=mamba_chunk_size,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads,
        head_dim=head_dim or hidden_size // num_attention_heads,
        shared_intermediate_size=shared_intermediate_size,
        attention_multiplier=float(attention_multiplier),
        embedding_multiplier=float(embedding_multiplier),
        residual_multiplier=float(residual_multiplier),
        logits_scaling=float(logits_scaling), eps=eps,
        attention_block=attention_block)
    module = GraniteHybrid(layer_types=kinds, dims=dims,
                           compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=table_init_stddev),
                    sparse_as_dense=True)
    config = dict(family="granite_hybrid", vocabulary=vocabulary,
                  num_hidden_layers=num_hidden_layers,
                  layer_types=list(kinds),
                  table_init_stddev=table_init_stddev,
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(module, [emb], loss_fn=softmax_xent, config=config)
