"""Hybrid state-space / mixture-of-experts decoder (NemotronH: Nemotron-3-Nano).

A decoder-only language model behind the SAME sparse path as every CTR model
here: the token embedding is an `Embedding` (ids (B, S), row width = hidden)
pulled, deduplicated and updated by `Trainer`'s table path; the flax module is
the decoder stack, the final RMSNorm and the untied output head; `label` is the
(B, S) next-token ids and the loss is mean softmax cross-entropy over the
vocabulary held (`softmax_xent`). bf16 compute on f32 parameters; decays,
softmax, router, logits and loss in f32.

Every block is `x + mixer(RMSNorm(x))`; the pattern's letters pick the mixer:

- `M`, Mamba-2: `in_proj` -> [z | xBC | dt]; depthwise causal conv (with bias)
  and SiLU over xBC; x (heads x head_dim), B and C (groups x state);
  dt = softplus(dt + dt_bias); A = -exp(A_log);
  h_t = exp(dt A) h_{t-1} + dt B_t (x) x_t; y_t = C_t . h_t + D x_t, computed
  chunk by chunk (`ssd_chunked`: on a TPU one fused kernel a direction,
  `ops/ssd_scan.py`); y = grouped RMSNorm(y * SiLU(z)); `out_proj`.
- `*`, causal attention with grouped key/value heads and no rotary embedding
  (`blockwise_causal_attention`: no S x S array; on a TPU one fused kernel,
  `ops/flash_attention.py`, elsewhere query block by query block over the
  keys a block can see; shared with `joyai_flash.py`'s latent attention,
  whose keys are wider than its values).
- `E`, routed experts: f32 router, s = sigmoid(logits), the top k of
  s + correction bias (a buffer: no gradient reaches it), weights = chosen s
  over their sum, times `routed_scaling_factor`; expert = down(relu(up(x))^2)
  (`MoE(gated=True)`: down(silu(gate(x)) * up(x)), three weights an expert);
  plus one shared expert of the same form, always on. The layer is TOLD WHICH
  EXPERTS IT HOLDS (`experts_held`, `expert_offset`): it routes over all
  `n_routed_experts` and adds only its own experts' terms; what absent experts
  would add is left out, and that partial sum goes on to the next layer (one
  chip's share of an expert-parallel deployment; on one chip there is no
  exchange and nothing stands in for one). (token, choice) pairs are sorted
  by expert, the pairs held here are compacted to a static working size by
  the exchange's own owner view (`parallel/sharded._owner_view`: the same
  sort / count / compact / `fits`), every held expert's slots are laid out
  from a block boundary (blocks of `BLOCK_ROWS` rows) and the experts run
  over the blocks: as batched products, each block's weights picked by a
  one-hot product, or as one grouped product a weight over the experts'
  own weights (`EXPERT_TILE`), and a step whose pairs do not fit runs the
  SAME function over all T x k pairs (`lax.cond`): no token is dropped at
  any imbalance.

Stage names (`utils/trace.py`): `ssm.{in_proj,conv,scan,gate_norm,out_proj}`,
`attn.{qkv,core,gate,out}` (`gate`: `Attention(gate=True)`, `solar_open2.py`),
`moe.{route,dispatch,experts,combine,shared}`, `lm.{head,loss}`. Counters: the
module hands `Trainer` per-step `moe.*` stats (`apply_with_stats`,
`window_stats`); `attn.cores{path=}` counts the traced attention cores by
what their shape allows (`blockwise_causal_attention`); `moe.products{path=
"grouped"|"picked"}` the traced runs of a routed layer by its held count;
`ssm.scans{path="fused"|"chunked"}` the traced chunked scans by what their
shape allows (`ssd_chunked`).

Documents packed into a sequence (`granite_hybrid.py`): `ssd_chunked`,
`causal_conv` and `blockwise_causal_attention` take `starts` (B, S), nonzero
where a document begins, and reset the state, the taps and the mask there;
`segment_ids` (scope `pack.segments`) makes the document ids every mask is a
pair of; `pack.resets{site=}` counts the traced call sites given starts.
With none given each traces the program it traced before it could.
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
from ..utils import metrics as _metrics
from ..utils import trace as _trace

TOKEN = "token"
NEG_INF = -1e30
BLOCK_ROWS = 256  # rows of one expert's block in the routed layer's layout
# A block takes its expert's weights by a one-hot product where the held
# experts fill whole tiles of this many. The product beats a gather of the
# block's weights (v5e, one traced pair a cell: with the gather Nemotron's
# step read 314.19 ms for 282.87, 8 experts; JoyAI's 350.24 for 327.59, 16:
# the compiler runs a batched product over GATHERED weights as loops over
# slices; PERF.md 7). But it makes the compiler keep the experts' weights,
# gradients AND accumulators with the expert axis second-minor all through a
# `train_many` scan, as a second copy of that state PADDED to the tile: at 10
# experts of 4096 x 1280, 24 copies of 320 MB for 200 MB of values, and the
# scan needs 20.71 of the chip's 15.75 GiB (PERF.md 4, 7). Where the held
# experts fill no whole tiles, a TPU lowering runs the layer's blocks through
# ONE grouped product a weight over the experts' own weights
# (`ops/grouped_matmul.py`: no block's weights are made, the weights'
# gradients are summed by expert in the kernel); other platforms gather.
EXPERT_TILE = 8


def token_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token softmax cross-entropy (B, S) of (B, S, V) logits, in f32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return lse - picked


def xent(logits: jax.Array, labels: jax.Array, weight=None) -> jax.Array:
    """Mean softmax cross-entropy of (B, S, V) f32 logits against (B, S) ids.
    `weight` (B,) or (B, S) turns the mean into a weighted mean."""
    return weighted_mean(token_xent(logits, labels), weight)


def weighted_mean(per: jax.Array, weight=None) -> jax.Array:
    """The mean of a per-token (B, S) quantity; with `weight` (B,) or (B, S),
    the weighted mean."""
    if weight is None:
        return jnp.mean(per)
    w = jnp.asarray(weight, per.dtype)
    w = jnp.broadcast_to(w.reshape(w.shape + (1,) * (per.ndim - w.ndim)),
                         per.shape)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def softmax_xent(logits: jax.Array, labels: jax.Array, weight=None) -> jax.Array:
    """`xent` as a model's `loss_fn`, under the stage name `lm.loss`."""
    with _trace.scope("lm", "loss"):
        return xent(logits, labels, weight)


def rms_norm(x, scale, eps, groups: int = 1):
    """RMSNorm in f32 over the last axis (over each of `groups` equal slices
    of it), times `scale`; the input's dtype out."""
    x32 = x.astype(jnp.float32)
    g = x32.reshape(x32.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return (g.reshape(x32.shape) * scale).astype(x.dtype)


def _mm(spec, a, b, dtype):
    """einsum with `dtype` inputs and f32 accumulation and result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def segment_ids(starts):
    """starts (B, S), nonzero where a document begins -> n (B, S) int32, the
    document a position belongs to (the running count of starts: 1.. where
    position 0 starts one). Two positions see each other where their n is
    equal; every mask of a packed sequence is made of such pairs."""
    with _trace.scope("pack", "segments"):
        return jnp.cumsum((starts != 0).astype(jnp.int32), axis=1)


def _count_reset(site: str):
    """`pack.resets{site=}`: one a traced call site that was given starts."""
    _metrics.observe("pack.resets", 1, "sum", labels={"site": site})


def ssd_chunked(x, dt, A, B, C, chunk: int, dtype=jnp.float32, starts=None):
    """The Mamba-2 recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t,
    y_t = C_t . h_t, chunk by chunk (state-space duality: inside a chunk a
    masked matrix product, between chunks the recurrence on chunk states).
    x (Bt, L, H, P); dt (Bt, L, H) f32, already positive; A (H,) f32 negative;
    B, C (Bt, L, G, N), H % G == 0. -> (Bt, L, H, P) f32. L need not be a
    multiple of `chunk`: the tail is padded with dt = 0, which neither decays
    nor feeds the state. Decays are f32; matrix products take `dtype` inputs.
    `starts` (Bt, L), nonzero where a document begins: the state is zero
    before each (h_t = dt_t B_t (x) x_t there). The reset is a mask of
    PAIRS, never a -inf added to a cumulated exponent (two positions after a
    start would then differ by the rounding of 1e30): with n the document of
    a position (`segment_ids`), inside a chunk the pair (l, s) counts where
    n_l = n_s; what a chunk leaves keeps the positions of its last
    document; chunk c's state enters chunk z where nothing starts between
    their ends, and a position reads it where it is still in that document.

    The entry to two bodies: at a shape the kernel takes
    (`ops/ssd_scan.takes`) a process whose default backend is a TPU traces
    the kernel (the whole chunk loop, a chunk's decays and the carried state
    in VMEM only) and every other process the plain body; at any other shape
    the plain body everywhere. The body is chosen when the scan is traced,
    not when it is lowered: `jax.lax.platform_dependent` would trace and
    differentiate both, 3-4 s more of every warm set-up at Granite's nine
    layers. `ssm.scans{path=}`, counted here once a traced call site, speaks
    for the SHAPE: "fused" = the kernel would take it, "chunked" = it
    refuses it (both series exist once one does)."""
    Bt, L, H, P = x.shape
    G, N = B.shape[2:]
    pad = (-L) % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    c = (L + pad) // chunk
    n = None
    if starts is not None:
        _count_reset("ssd")
        n = jnp.pad(segment_ids(starts), ((0, 0), (0, pad)), mode="edge")
        n = n.reshape(Bt, c, chunk)
    fused = _ssd_kernel().takes(chunk, H, G, P, N)
    for path in ("fused", "chunked"):
        _metrics.observe("ssm.scans", int(fused == (path == "fused")), "sum",
                         labels={"path": path})
    body = _ssd_fused if fused and _traces_for_tpu() else _ssd_plain
    return body(x, dt, A, B, C, n, chunk, dtype)[:, :L]


def _ssd_plain(x, dt, A, B, C, n, chunk, dtype):
    """The plain body, (Bt, c Q, H, P) f32: every pair's decay an element
    of an f32 (Bt, c, Q, Q, G, r) array, the chunks' states joined by a
    (c x c) product."""
    Bt, Lp, H, P = x.shape
    G, N = B.shape[2:]
    r = H // G
    c = Lp // chunk
    if n is not None:
        n_end = n[:, :, -1]                                  # (Bt,c)
        # the document the state ENTERING chunk z belongs to: chunk z-1's
        # last (nothing enters chunk 0, whatever this says there)
        n_in = jnp.pad(n_end[:, :-1], ((0, 0), (1, 0)))
    dt = dt.astype(jnp.float32).reshape(Bt, c, chunk, G, r)
    xd = (x.astype(jnp.float32).reshape(Bt, c, chunk, G, r, P) * dt[..., None])
    Bc = B.reshape(Bt, c, chunk, G, N)
    Cc = C.reshape(Bt, c, chunk, G, N)
    a = dt * A.astype(jnp.float32).reshape(G, r)
    acs = jnp.cumsum(a, axis=2)                              # (Bt,c,Q,G,r)
    # inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(acs_l - acs_s) dt_s x_s
    seg = acs[:, :, :, None] - acs[:, :, None, :]            # (Bt,c,l,s,G,r)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    if n is not None:
        tri = tri & (n[:, :, :, None] == n[:, :, None, :])[..., None, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = _mm("bclgn,bcsgn->bclsg", Cc, Bc, dtype)
    y = _mm("bclsgr,bcsgrp->bclgrp", cb[..., None] * decay, xd, dtype)
    # what a chunk leaves behind: sum_s exp(acs_end - acs_s) dt_s B_s (x) x_s
    left = jnp.exp(acs[:, :, -1:] - acs)                     # (Bt,c,Q,G,r)
    if n is not None:
        left = jnp.where((n == n_end[:, :, None])[..., None, None], left, 0.0)
    states = _mm("bcsgn,bcsgrp->bcgrpn", Bc, xd * left[..., None], dtype)
    # between chunks: the state entering chunk z is sum_{c<z} exp(sum of the
    # chunk totals strictly between) states_c (a (c, c) lower-triangular product)
    tot = acs[:, :, -1]                                      # (Bt,c,G,r)
    run = jnp.cumsum(tot, axis=1)
    between = (run - tot)[:, :, None] - run[:, None, :]      # (Bt,z,c,G,r)
    low = jnp.tril(jnp.ones((c, c), bool), -1)[:, :, None, None]
    if n is not None:
        low = low & (n_in[:, :, None] == n_end[:, None, :])[..., None, None]
    carry = jnp.exp(jnp.where(low, between, -jnp.inf))
    entering = _mm("bzcgr,bcgrpn->bzgrpn", carry, states, dtype)
    read = _mm("bclgn,bcgrpn->bclgrp", Cc, entering, dtype)
    reach = jnp.exp(acs)                                     # (Bt,c,Q,G,r)
    if n is not None:
        reach = jnp.where((n == n_in[:, :, None])[..., None, None], reach, 0.0)
    y = y + read * reach[..., None]
    return y.reshape(x.shape)


def _ssd_fused(x, dt, A, B, C, n, chunk, dtype):
    """The kernel's body, (Bt, c Q, H, P) f32: the cumulated decays as the
    plain body makes them, every operand in the model's own layout but dt
    and the decays, a row a head (the kernel makes xd = dt x)."""
    Bt, Lp, H, P = x.shape
    G, N = B.shape[2:]
    c = Lp // chunk
    dt = dt.astype(jnp.float32).reshape(Bt, c, chunk, H)
    acs = jnp.cumsum(dt * A.astype(jnp.float32), axis=2)      # (Bt,c,Q,H)
    Bc, Cc = (t.reshape(Bt, c, chunk, G * N).astype(dtype) for t in (B, C))
    docs = None if n is None else n.astype(jnp.int32)
    y = _ssd_kernel().scan(x.reshape(Bt, c, chunk, H * P),
                           jnp.moveaxis(dt, 2, 3), jnp.moveaxis(acs, 2, 3),
                           Bc, Cc, G, docs)
    return y.reshape(x.shape)


def blockwise_causal_attention(q, k, v, *, block: int = 512, scale=None,
                               starts=None, denoise=None):
    """Causal softmax attention: q (B, S, Hq, D), k (B, S, Hkv, D), v
    (B, S, Hkv, Dv), Hq % Hkv == 0 (grouped key/value heads; Dv need not be
    D) -> (B, S, Hq, Dv); scores times `scale` (None: D^-1/2); softmax in
    f32; nothing of size S x S is ever kept. `starts` (B, S), nonzero where
    a document begins: a query sees the keys of its own document alone
    (`segment_ids`, made once a call for either body). The entry to two
    bodies that share no logic: at a shape the fused kernel's tiling takes
    (`ops/flash_attention.tiling`) a TPU lowering runs the kernel (its own
    blocks, scores in VMEM only, the key blocks no query's document reaches
    skipped) and every other platform the plain body; at any other shape
    (`block` queries a block) the plain body everywhere. Both are traced and
    the platform is settled when the program is lowered, so
    `attn.cores{path=}`, counted here once a traced call site, speaks for the
    SHAPE alone: "fused" = the kernel would take it, "blockwise" = it
    refuses it. That the kernel ran is the device trace's to say (a custom
    call under `attn.core`). Both series exist once one does (the other
    reads 0).

    `denoise` (block diffusion, `sdar_moe.py`): the sequence is a noised copy
    and the clean one, S / 2 each, and pairs are seen by denoising blocks of
    `denoise` positions (`diffusion_pairs`; no `starts` with it). Counted as
    `attn.cores{path="block_diffusion"}` where the kernel's block-diffusion
    walk takes the shape (`flash_attention.diffusion_block`), else as
    "blockwise"."""
    (_, S, Hq, D), Hkv, Dv = q.shape, k.shape[2], v.shape[3]
    if denoise is not None:
        return _diffusion_entry(q, k, v, block, scale, starts, denoise)
    fused = _flash().tiling(S, D, Dv, Hq, Hkv) is not None
    for path in ("fused", "blockwise"):
        _metrics.observe("attn.cores", int(fused == (path == "fused")), "sum",
                         labels={"path": path})
    n = None
    if starts is not None:
        _count_reset("attn")
        n = segment_ids(starts)

    def plain(q, k, v, n):
        return _blockwise_causal_attention(q, k, v, block=block, scale=scale,
                                           n=n)
    if not fused:
        return plain(q, k, v, n)

    def kernel(q, k, v, n):
        return _flash().causal_attention(q, k, v, scale=scale, segment_ids=n)
    return jax.lax.platform_dependent(q, k, v, n, tpu=kernel, default=plain)


def _diffusion_entry(q, k, v, block, scale, starts, denoise):
    """`blockwise_causal_attention`'s block-diffusion call."""
    if starts is not None:
        raise ValueError("a block-diffusion call takes no document starts")
    (_, S, Hq, D), Hkv, Dv = q.shape, k.shape[2], v.shape[3]
    if S % 2:
        raise ValueError(f"a noised and a clean half of one length: S = {S}")
    took = "block_diffusion" if _flash().diffusion_block(
        S, D, Dv, Hq, Hkv, denoise) is not None else "blockwise"
    for path in ("fused", "blockwise", "block_diffusion"):
        _metrics.observe("attn.cores", int(path == took), "sum",
                         labels={"path": path})

    def plain(q, k, v):
        return _diffusion_attention(q, k, v, block=block, scale=scale,
                                    denoise=denoise)
    if took == "blockwise":
        return plain(q, k, v)

    def kernel(q, k, v):
        return _flash().causal_attention(q, k, v, scale=scale, denoise=denoise)
    return jax.lax.platform_dependent(q, k, v, tpu=kernel, default=plain)


def diffusion_pairs(qpos, kpos, half: int, denoise: int):
    """Which (query, key) pairs block diffusion sees, positions in a sequence
    of a noised half [0, half) then a clean one [half, 2 half) (broadcasting
    integer arrays): the denoising block of a position is `pos // denoise`
    within its half; a noisy query sees the noisy keys of its own block and
    the clean keys of earlier blocks, a clean query the clean keys of its own
    block and earlier ones."""
    q_noisy, k_noisy = qpos < half, kpos < half
    qb, kb = (qpos % half) // denoise, (kpos % half) // denoise
    noisy_query = (k_noisy & (kb == qb)) | (~k_noisy & (kb < qb))
    return (q_noisy & noisy_query) | (~q_noisy & ~k_noisy & (kb <= qb))


def _diffusion_attention(q, k, v, *, block, scale=None, denoise):
    """The plain body of a block-diffusion call: each half's queries a block
    of `block` at a time against the keys the block can see (the clean
    positions up to the end of its last denoising block, and for noisy
    queries the noisy positions of their own denoising blocks), masked by
    `diffusion_pairs`, each block rematerialised in the backward pass."""
    B, S, Hq, D = q.shape
    Hkv, Dv, half = k.shape[2], v.shape[3], S // 2
    qg = q.reshape(B, S, Hkv, Hq // Hkv, D)
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    @jax.checkpoint
    def one(qb, kb, vb, seen):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, vb,
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    out = []
    for start in (0, half):
        for lo in range(start, start + half, block):
            hi = min(lo + block, start + half)
            reach = min(half, -(-(hi - start) // denoise) * denoise)
            spans = [(half, half + reach)]
            if start == 0:
                spans.append(((lo // denoise) * denoise, reach))
            kpos = np.concatenate([np.arange(a, b) for a, b in spans])
            seen = diffusion_pairs(np.arange(lo, hi)[:, None], kpos[None, :],
                                   half, denoise)
            keys = [jnp.concatenate([t[:, a:b] for a, b in spans], axis=1)
                    for t in (k, v)]
            out.append(one(qg[:, lo:hi], *keys, jnp.asarray(seen)))
    return jnp.concatenate(out, axis=1).reshape(B, S, Hq, Dv)


def _flash():
    """`ops/flash_attention.py`, imported when a model first traces its
    attention: Pallas is half a second of import, which the models that have
    no attention (and their set-up times) are spared."""
    from ..ops import flash_attention
    return flash_attention


def _traces_for_tpu() -> bool:
    """Whether this process lowers for a TPU: its default backend (tests of
    the kernel's path on the CPU patch this)."""
    return jax.default_backend() == "tpu"


def _ssd_kernel():
    """`ops/ssd_scan.py`, imported when a model first traces a chunked scan
    (Pallas, as `_flash`)."""
    from ..ops import ssd_scan
    return ssd_scan


def _blockwise_causal_attention(q, k, v, *, block, scale=None, n=None):
    """The plain body: one block of queries at a time against the keys it
    can see (keys [0, block end)), each block rematerialised in the backward
    pass, the blocks above the diagonal never computed; the f32 scores of a
    block are an array of the program. `n` (B, S): the document of each
    position; a (query, key) pair of two documents is masked like one above
    the diagonal."""
    B, S, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, D)
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    @jax.checkpoint
    def one(qb, kb, vb, lo, *docs):
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        qpos = lo + jnp.arange(qb.shape[1])[:, None]
        seen = qpos >= jnp.arange(kb.shape[1])[None, :]
        if docs:
            nq, nk = docs
            seen = seen & (nq[:, :, None] == nk[:, None, :])[:, None, None]
        s = jnp.where(seen, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, vb,
                          preferred_element_type=jnp.float32).astype(qb.dtype)

    def docs(lo):
        return () if n is None else (n[:, lo:lo + block], n[:, :lo + block])

    out = [one(qg[:, lo:lo + block], k[:, :lo + block], v[:, :lo + block], lo,
               *docs(lo))
           for lo in range(0, S, block)]
    return jnp.concatenate(out, axis=1).reshape(B, S, Hq, Dv)


def causal_conv(x, w, bias=None, starts=None):
    """Depthwise causal convolution over time: x (B, S, C), w (K, C) ->
    (B, S, C) f32; tap j reads position t - (K - 1) + j, positions before
    the sequence read 0. Shared with `solar_open2.py`'s linear layers.
    `starts` (B, S), nonzero where a document begins: a tap that would read
    another document's position reads 0 (`segment_ids`)."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    acc = 0.0 if bias is None else bias.astype(jnp.float32)
    if starts is not None:
        _count_reset("conv")
        n = segment_ids(starts)
        before = jnp.pad(n, ((0, 0), (K - 1, 0)))
    for j in range(K):
        tap = padded[:, j:j + S].astype(jnp.float32)
        if starts is not None and j < K - 1:
            tap = jnp.where((before[:, j:j + S] == n)[..., None], tap, 0.0)
        acc = acc + tap * w[j]
    return acc


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = -(1, 2, ..., H), a head (the HF Mamba-2 mixer's own start)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=1e-1):
    """softplus^-1 of time steps spaced evenly in log between `lo` and `hi`
    (the published `time_step_min` / `max`; a draw there, a ramp here)."""
    dt = jnp.exp(jnp.linspace(math.log(lo), math.log(hi), shape[0], dtype=dtype))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    hidden: int
    num_heads: int
    head_dim: int
    n_groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, starts=None):
        H, P, G, N, K = (self.num_heads, self.head_dim, self.n_groups,
                         self.state, self.conv_kernel)
        inner, bc = H * P, G * N
        conv_dim = inner + 2 * bc
        with _trace.scope("ssm", "in_proj"):
            zxbcdt = nn.Dense(inner + conv_dim + H, use_bias=False,
                              dtype=self.dtype, name="in_proj")(x)
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        with _trace.scope("ssm", "conv"):
            w = self.param("conv_kernel", nn.initializers.normal(K ** -0.5),
                           (K, conv_dim))
            b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
            xbc = jax.nn.silu(causal_conv(xbc, w, b, starts)).astype(
                self.dtype)
            xs, Bm, Cm = jnp.split(xbc, [inner, inner + bc], axis=-1)
        with _trace.scope("ssm", "scan"):
            dt_bias = self.param("dt_bias", _dt_bias_init, (H,))
            A_log = self.param("A_log", _a_log_init, (H,))
            D = self.param("D", nn.initializers.ones, (H,))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            xh = xs.reshape(xs.shape[:2] + (H, P))
            y = ssd_chunked(xh, dt, -jnp.exp(A_log.astype(jnp.float32)),
                            Bm.reshape(Bm.shape[:2] + (G, N)),
                            Cm.reshape(Cm.shape[:2] + (G, N)),
                            self.chunk, self.dtype, starts)
            y = y + xh.astype(jnp.float32) * D[:, None]
            y = y.reshape(xs.shape)
        with _trace.scope("ssm", "gate_norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (inner,))
            y = y * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, scale, self.eps, groups=G).astype(self.dtype)
        with _trace.scope("ssm", "out_proj"):
            return nn.Dense(self.hidden, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y)


class Attention(nn.Module):
    hidden: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    block: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    # an element-wise sigmoid gate as wide as the core's output, from the
    # layer's input, before the output projection (`solar_open2.py`)
    gate: bool = False
    # what the scores are multiplied by; None: head_dim^-1/2
    scale: Optional[float] = None

    @nn.compact
    def __call__(self, x, starts=None):
        B, S, _ = x.shape
        Hq, Hkv, D = self.num_heads, self.num_kv_heads, self.head_dim

        def proj(name, heads):
            y = nn.Dense(heads * D, use_bias=False, dtype=self.dtype,
                         name=name)(x)
            return y.reshape(B, S, heads, D)

        with _trace.scope("attn", "qkv"):
            q, k, v = proj("q_proj", Hq), proj("k_proj", Hkv), proj("v_proj", Hkv)
        with _trace.scope("attn", "core"):
            o = blockwise_causal_attention(q, k, v, block=self.block,
                                           scale=self.scale, starts=starts)
        o = o.reshape(B, S, Hq * D)
        if self.gate:
            with _trace.scope("attn", "gate"):
                g = nn.Dense(Hq * D, use_bias=False, dtype=self.dtype,
                             name="g_proj")(x)
                o = (jax.nn.sigmoid(g.astype(jnp.float32))
                     * o.astype(jnp.float32)).astype(self.dtype)
        with _trace.scope("attn", "out"):
            return nn.Dense(self.hidden, use_bias=False, dtype=self.dtype,
                            name="o_proj")(o)


def _relu2(h):
    """relu(h)^2 in f32, handed on in the dtype it came in."""
    return jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(h.dtype)


def _swiglu(g, u):
    """silu(g) * u in f32, handed on in the dtype it came in."""
    return (jax.nn.silu(g.astype(jnp.float32))
            * u.astype(jnp.float32)).astype(u.dtype)


def _expert_mlp(x, gate, up, down, dtype):
    """down(relu(up x)^2), or with a `gate` down(silu(gate x) * (up x))."""
    h = jnp.dot(x, up.astype(dtype))
    h = _relu2(h) if gate is None else _swiglu(
        jnp.dot(x, gate.astype(dtype)), h)
    return jnp.dot(h, down.astype(dtype), preferred_element_type=jnp.float32)


class MoE(nn.Module):
    hidden: int
    n_routed_experts: int
    top_k: int
    expert_width: int
    shared_width: int
    experts_held: int
    expert_offset: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # static working size in (token, choice) pairs; 0 = twice what a balanced
    # router sends the held experts, rounded up to a block (an untrained
    # router sent the held experts 1.0-1.2 x the balanced load, the fullest
    # of them 2.6-3.3 x the mean: v5e, PR 28; the split among the experts
    # costs nothing, `run`)
    working_pairs: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    # the expert's form: down(relu(up x)^2), or gated (SwiGLU, three weights
    # an expert and the shared one): down(silu(gate x) * (up x))
    gated: bool = False

    def _working_size(self, tokens: int) -> int:
        if self.working_pairs:
            return int(self.working_pairs)
        mean = tokens * self.top_k * self.experts_held / self.n_routed_experts
        return int(-(-2.0 * mean // BLOCK_ROWS) * BLOCK_ROWS)

    def _route(self, xt):
        """The built-in router: f32 linear map, sigmoid, the top k of score +
        correction bias, weights = chosen scores (over their sum), scaled.
        -> (chosen (T, k), gate (T, k))."""
        init = nn.initializers.lecun_normal()
        router = self.param("router_kernel", init,
                            (xt.shape[-1], self.n_routed_experts))
        bias = self.param("router_correction_bias", nn.initializers.zeros,
                          (self.n_routed_experts,))
        logits = jnp.dot(xt.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        score = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(bias),
                                  self.top_k)
        gate = jnp.take_along_axis(score, chosen, axis=-1)
        if self.norm_topk_prob:
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
        return chosen, gate * self.routed_scaling_factor

    @nn.compact
    def __call__(self, x, routing=None):
        from ..parallel import sharded
        B, S, D = x.shape
        T, k, E = B * S, self.top_k, self.experts_held
        xt = x.reshape(T, D)
        init = nn.initializers.lecun_normal()
        up = self.param("experts_up", init, (E, D, self.expert_width))
        down = self.param("experts_down", init, (E, self.expert_width, D))
        gate_w = self.param("experts_gate", init, (
            E, D, self.expert_width)) if self.gated else None

        with _trace.scope("moe", "route"):
            if routing is None:
                chosen, gate = self._route(xt)
            else:
                chosen, gate = routing
            gate = gate.reshape(T * k)

        with _trace.scope("moe", "dispatch"):
            # (token, choice) pairs sorted by held expert; pairs of experts
            # held elsewhere sort last and are this layer's empty slots
            local = chosen.reshape(T * k) - self.expert_offset
            key = jnp.where((local >= 0) & (local < E), local, E)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            held = key[order] < E
            loads = jnp.sum(key[:, None] == jnp.arange(E)[None, :], axis=0,
                            dtype=jnp.int32)
            total = jnp.sum(loads, dtype=jnp.int32)
            tokens = jnp.where(held, order // k, -1)[None]
            view = sharded._owner_view(tokens, held[None],
                                       self._working_size(T))

        ends = jnp.cumsum(loads)
        rows = BLOCK_ROWS

        def run(tokens, valid, pairs, start=0):
            """The held experts over one run of slots sorted by expert (the
            slots [start, start + n) of the sorted order). Every expert's
            slots are laid out from a block boundary (blocks of `rows` rows,
            so n / rows + E blocks hold any split of the run among the
            experts), and up (with gate, where gated) / the activation / down
            run over the blocks: batched products over each block's weights
            picked by a one-hot product, or one grouped product a weight
            (`EXPERT_TILE`); then weigh and add to the tokens. The cost is
            the same at any imbalance."""
            n = tokens.shape[0]
            blocks = -(-n // rows) + E
            grouped = E % EXPERT_TILE != 0
            for path in ("grouped", "picked"):
                _metrics.observe("moe.products",
                                 int(grouped == (path == "grouped")), "sum",
                                 labels={"path": path})
            with _trace.scope("moe", "dispatch"):
                first = jnp.clip(ends - loads - start, 0, n)     # in the run
                size = jnp.clip(ends - start, 0, n) - first
                took = -(-size // rows)                          # blocks each
                upto = jnp.cumsum(took)
                owner = jnp.minimum(jnp.searchsorted(
                    upto, jnp.arange(blocks, dtype=jnp.int32), side="right"),
                    E - 1).astype(jnp.int32)                      # (blocks,)
                lane = jnp.arange(blocks * rows, dtype=jnp.int32).reshape(
                    blocks, rows) - ((upto - took) * rows)[owner][:, None]
                at = jnp.clip(first[owner][:, None] + lane, 0, n - 1)
                ok = (lane >= 0) & (lane < size[owner][:, None]) & valid[at]
                token = jnp.where(ok, tokens[at], T)
                xs = xt[jnp.minimum(token, T - 1)]               # (blocks, rows, D)
                if grouped:
                    # an expert's blocks lie together: its group of rows,
                    # the trailing empty blocks the last expert's (`owner`)
                    sizes = ((took.at[E - 1].add(blocks - upto[E - 1]))
                             * rows).astype(jnp.int32)
                else:
                    pick = jax.nn.one_hot(owner, E, dtype=self.dtype)

            def batched(xs, of_block, *weights):
                """The batched products over the blocks, each block's
                weights (blocks, a, b) made by `of_block` from (E, a, b)."""
                up_w, down_w, *gate = weights
                up_b = of_block(up_w)
                down_b = of_block(down_w)
                h = jnp.einsum("brd,bdf->brf", xs, up_b)
                h = _relu2(h) if not gate else _swiglu(jnp.einsum(
                    "brd,bdf->brf", xs, of_block(gate[0])), h)
                return jnp.einsum("brf,bfd->brd", h, down_b,
                                  preferred_element_type=jnp.float32)

            def gathered(xs, sizes, owner, *weights):
                """A block's weights gathered from its expert's."""
                return batched(xs, lambda w: w.astype(self.dtype)[owner],
                               *weights)

            def grouped_products(xs, sizes, owner, *weights):
                """The layer's rows through ONE grouped product a weight
                over the experts' own weights (`ops/grouped_matmul.py`)."""
                # Pallas: imported where a layer first traces this path
                from ..ops.grouped_matmul import grouped_matmul
                up_w, down_w, *gate = weights
                x2 = xs.reshape(blocks * rows, D)
                h = grouped_matmul(x2, up_w, sizes, self.dtype)
                h = _relu2(h) if not gate else _swiglu(
                    grouped_matmul(x2, gate[0], sizes, self.dtype), h)
                return grouped_matmul(h, down_w, sizes, jnp.float32).reshape(
                    blocks, rows, D)

            weights = (up, down) + (() if gate_w is None else (gate_w,))
            with _trace.scope("moe", "experts"):
                if grouped:
                    ys = jax.lax.platform_dependent(
                        xs, sizes, owner, *weights, tpu=grouped_products,
                        default=gathered)
                else:
                    # the one-hot product's transpose sums the blocks'
                    # gradients by expert
                    ys = batched(xs, lambda w: jnp.einsum(
                        "ne,eab->nab", pick, w.astype(self.dtype)), *weights)
            with _trace.scope("moe", "combine"):
                w = jnp.where(ok, gate[pairs[at]], 0.0).astype(jnp.float32)
                out = jnp.zeros((T, D), jnp.float32).at[token].add(
                    ys * w[..., None], mode="drop")
            return out, jnp.sum(ok, dtype=jnp.int32)

        def compact():
            pairs = sharded._compact(order[None], view.offsets,
                                     view.valid.shape[0])
            return run(view.ids, view.valid, pairs)

        def full_size():
            """All T x k slots, a working size at a time through the same
            `run`; each pass rematerialised, a pass with no held pair
            skipped: the memory of the compact path at any load."""
            n = view.valid.shape[0]
            pad = (-(T * k)) % n
            feed = (jnp.pad(tokens[0], (0, pad), constant_values=-1),
                    jnp.pad(held, (0, pad)), jnp.pad(order, (0, pad)))

            @jax.checkpoint  # keeps `start` alone; the pass is made again
            def one(start):
                tk, vd, pr = (jax.lax.dynamic_slice_in_dim(f, start, n)
                              for f in feed)
                return jax.lax.cond(
                    vd[0], lambda: run(tk, vd, pr, start),
                    lambda: (jnp.zeros((T, D), jnp.float32),
                             jnp.zeros((), jnp.int32)))

            def add(acc, start):
                out, did = one(start)
                return (acc[0] + out, acc[1] + did), None

            zero = (jnp.zeros((T, D), jnp.float32), jnp.zeros((), jnp.int32))
            return jax.lax.scan(add, zero, jnp.arange(
                0, T * k + pad, n, dtype=jnp.int32))[0]

        if view is None:  # the working size holds every pair there is
            routed, done = run(tokens[0], held, order)
            full = jnp.zeros((), jnp.int32)
        else:
            routed, done = jax.lax.cond(
                view.fits, compact, sharded._full_size_scope(full_size))
            full = (~view.fits).astype(jnp.int32)

        shared = None
        if self.shared_width:
            with _trace.scope("moe", "shared"):
                shared = _expert_mlp(
                    xt, self.param("shared_gate", init, (D, self.shared_width))
                    if self.gated else None,
                    self.param("shared_up", init, (D, self.shared_width)),
                    self.param("shared_down", init, (self.shared_width, D)),
                    self.dtype)
        stats = {"pairs_here": total.astype(jnp.float32),
                 "load_max_over_mean": jnp.max(loads).astype(jnp.float32) * E
                 / jnp.maximum(total, 1).astype(jnp.float32),
                 "full_steps": full, "dropped": total - done}
        if shared is not None:  # after the stats: the op order of the older scans
            routed = routed + shared
        return routed.reshape(B, S, D), stats


@dataclasses.dataclass(frozen=True)
class Dims:
    """Every size of the stack, as the published config names them."""

    hidden_size: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    experts_held: int
    expert_offset: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    working_pairs: int
    eps: float
    attention_block: int


class Block(nn.Module):
    """x + mixer(RMSNorm(x)) -> (x, the mixer's step stats)."""

    kind: str
    dims: Dims
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.dims
        scale = self.param("norm_scale", nn.initializers.ones, (c.hidden_size,))
        h = rms_norm(x, scale, c.eps)
        stats = {}
        if self.kind == "M":
            h = Mamba2Mixer(c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
                            c.n_groups, c.ssm_state_size, c.conv_kernel,
                            c.chunk_size, c.eps, self.dtype, name="mixer")(h)
        elif self.kind == "*":
            h = Attention(c.hidden_size, c.num_attention_heads,
                          c.num_key_value_heads, c.head_dim,
                          c.attention_block, self.dtype, name="mixer")(h)
        elif self.kind == "E":
            h, stats = MoE(c.hidden_size, c.n_routed_experts, c.num_experts_per_tok,
                           c.moe_intermediate_size,
                           c.moe_shared_expert_intermediate_size,
                           c.experts_held, c.expert_offset,
                           c.routed_scaling_factor, c.norm_topk_prob,
                           c.working_pairs, self.dtype, name="mixer")(h)
        else:
            raise ValueError(f"unknown layer kind {self.kind!r} (M, E or *)")
        return x + h.astype(x.dtype), stats


@functools.cache
def _kept_by_name():
    return jax.checkpoint_policies.save_only_these_names(
        _flash().OUT_NAME, _flash().LSE_NAME)


def _keep_products(prim, *avals, **params):
    """Remat policy: a layer keeps the outputs of its plain matrix
    products (no batch dims) for its backward pass, but never a routed
    block's weights picked by the one-hot product (a (blocks, E) x (E, D, F)
    product: 300 MB a pick, made again from the weights in no time); and it
    keeps the fused attention core's output and log-sum-exp (by name: the
    output projection's backward pass reads the one, the kernel's the
    other), so the kernel's forward pass is not made again."""
    if (prim is jax.lax.dot_general_p and avals[0].ndim == 2
            and avals[1].ndim == 3):
        return False
    return (jax.checkpoint_policies.dots_with_no_batch_dims_saveable(
        prim, *avals, **params) or _kept_by_name()(prim, *avals, **params))


class NemotronH(nn.Module):
    """The decoder stack over pulled token rows -> (B, S, vocabulary) f32
    logits. `pattern` holds one letter a layer (M, E, *)."""

    pattern: str
    vocabulary: int
    dims: Dims
    compute_dtype: jnp.dtype = jnp.bfloat16

    # per-step stats -> how a `train_many` window folds them (`Trainer`)
    window_stats = (("moe.pairs_here", "avg"), ("moe.load_max_over_mean", "max"),
                    ("moe.full_steps", "sum"), ("moe.dropped", "sum"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        x = embedded[TOKEN].astype(self.compute_dtype)
        # a layer keeps its input and its plain products' outputs for the
        # backward pass and makes the rest again: 8,192 tokens x 9 layers of
        # activations beside 8 GB of state (v5e: 13.7 GiB in all; 17.9 with
        # everything kept)
        block = nn.remat(Block, policy=_keep_products)
        per_layer = []
        for i, kind in enumerate(self.pattern):
            x, stats = block(kind, self.dims, self.compute_dtype,
                             name=f"layers_{i}")(x)
            if stats:
                per_layer.append(stats)
        with _trace.scope("lm", "head"):
            scale = self.param("norm_f_scale", nn.initializers.ones,
                               (self.dims.hidden_size,))
            x = rms_norm(x, scale, self.dims.eps)
            head = self.param("lm_head", nn.initializers.lecun_normal(),
                              (self.dims.hidden_size, self.vocabulary))
            logits = jnp.dot(x, head.astype(self.compute_dtype),
                             preferred_element_type=jnp.float32)
        if not with_stats:
            return logits
        return logits, _fold_layers(per_layer)

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        """-> (logits, {stat name: scalar}): the step's `window_stats`."""
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


def _fold_layers(per_layer):
    """One step's `moe.*` over its expert layers: pairs a layer (mean), the
    worst layer's load ratio, 1 if any layer ran full size, drops summed."""
    if not per_layer:
        return {}
    col = {k: jnp.stack([s[k] for s in per_layer]) for k in per_layer[0]}
    return {"moe.pairs_here": jnp.mean(col["pairs_here"]),
            "moe.load_max_over_mean": jnp.max(col["load_max_over_mean"]),
            "moe.full_steps": jnp.max(col["full_steps"]),
            "moe.dropped": jnp.sum(col["dropped"])}


def make_nemotron_h(vocabulary: int, hidden_size: int, pattern: str, *,
                    mamba_num_heads: int, mamba_head_dim: int, n_groups: int,
                    ssm_state_size: int, conv_kernel: int = 4,
                    chunk_size: int = 128, num_attention_heads: int,
                    num_key_value_heads: int, head_dim: int,
                    n_routed_experts: int, num_experts_per_tok: int,
                    moe_intermediate_size: int,
                    moe_shared_expert_intermediate_size: int,
                    experts_held: Optional[int] = None, expert_offset: int = 0,
                    routed_scaling_factor: float = 1.0,
                    norm_topk_prob: bool = True, working_pairs: int = 0,
                    eps: float = 1e-5, attention_block: int = 512,
                    optimizer=None,
                    compute_dtype=jnp.bfloat16) -> EmbeddingModel:
    """NemotronH language model as an `EmbeddingModel`. Batches:
    {"sparse": {"token": (B, S) int32}, "label": (B, S) int32 next tokens}.
    `experts_held` / `expert_offset`: the routed experts this program holds,
    [offset, offset + held) of `n_routed_experts` (default: all of them);
    `vocabulary`: the rows of the table and of the head held here."""
    held = n_routed_experts if experts_held is None else experts_held
    if not 0 < held <= n_routed_experts - expert_offset:
        raise ValueError(f"experts [{expert_offset}, {expert_offset + held}) "
                         f"are not among {n_routed_experts}")
    if num_attention_heads % num_key_value_heads or mamba_num_heads % n_groups:
        raise ValueError("query heads must divide by key/value heads and "
                         "mamba heads by groups")
    if set(pattern) - set("ME*"):
        raise ValueError(f"pattern {pattern!r}: one of M, E, * a layer")
    dims = Dims(
        hidden_size=hidden_size, mamba_num_heads=mamba_num_heads,
        mamba_head_dim=mamba_head_dim, n_groups=n_groups,
        ssm_state_size=ssm_state_size, conv_kernel=conv_kernel,
        chunk_size=chunk_size, num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        n_routed_experts=n_routed_experts,
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        moe_shared_expert_intermediate_size=moe_shared_expert_intermediate_size,
        experts_held=held, expert_offset=expert_offset,
        routed_scaling_factor=routed_scaling_factor,
        norm_topk_prob=norm_topk_prob, working_pairs=working_pairs, eps=eps,
        attention_block=attention_block)
    module = NemotronH(pattern=pattern, vocabulary=vocabulary, dims=dims,
                       compute_dtype=compute_dtype)
    emb = Embedding(vocabulary, hidden_size, name=TOKEN,
                    embeddings_initializer=Normal(stddev=1.0),
                    optimizer=optimizer)
    config = dict(family="nemotron_h", vocabulary=vocabulary, pattern=pattern,
                  compute_dtype=jnp.dtype(compute_dtype).name,
                  **dataclasses.asdict(dims))
    return EmbeddingModel(module, [emb], loss_fn=softmax_xent, config=config)
