"""Causal attention as ONE fused TPU kernel, forward and backward: a block of
scores lives in VMEM only.

`causal_attention(q, k, v, scale=, segment_ids=)`: q (B, S, Hq, D), k
(B, S, Hkv, D), v (B, S, Hkv, Dv), Hq % Hkv == 0, Dv need not be D ->
(B, S, Hq, Dv); scores times `scale` (None: D^-1/2 with D the KEY width).
What `models/nemotron_h.py`'s plain blockwise body computes, by another
schedule:

- forward (`_fwd_kernel`): one grid step holds a block of queries of one head
  and ALL keys and values of its key/value head in VMEM, walks the key blocks
  up to the diagonal (the blocks above it are never touched, the diagonal
  block is masked) with a running maximum and sum, and returns the output and
  the log-sum-exp of every query row;
- backward (`_bwd_kernel`): the same walk makes each block of scores again
  from q, k and the log-sum-exp and adds up dq (over the key blocks of one
  grid step), dk and dv (in f32 VMEM scratch over the query blocks and over
  the query heads of a key/value group): five products a block pair.

Documents packed into a sequence (`segment_ids` (B, S), each document ONE run
of equal ids, as `models/nemotron_h.segment_ids` makes them): a query sees
the keys from where its document began up to itself. Both kernels then get
where each query's document began (`document_starts`) and, prefetched into
scalar memory, a table of the first key block each query block's walk
visits (`first_key_blocks`: the block holding where its FIRST query's
document began; no later query's document began earlier). Blocks below it
are never touched, in the forward pass, in dq, or in dk / dv's sums; inside
a visited block every pair of two documents is masked beside the diagonal's
causal mask. A row that a visited block masks whole reads p = 1 there under
the running maximum's MASKED; the first real score makes its alpha 0. With
no `segment_ids` the kernels are traced as they were before they took any
(a static branch, not a mask of ones).

Block diffusion (`denoise` = the denoising block B_k; `models/sdar_moe.py`):
the sequence is two halves of L = S / 2, a noised copy then the clean one,
and a pair is seen by denoising block (`pos // B_k` within its half): a
noisy query sees the noisy keys of its own denoising block and the clean keys
of earlier ones, a clean query the clean keys of its own and earlier ones
(`models/nemotron_h.diffusion_pairs`). Each query block's walk
(`diffusion_key_blocks`) visits the whole clean blocks before its position,
the clean block AT its position (masked by denoising block), and for a noisy
query block its own noisy block (masked to its own denoising blocks): n^2 + 2n
of the n(2n + 1) blocks a causal walk over 2L visits, n = L / block. No
block above that walk is touched, in the forward pass, in dq or in dk / dv's
sums. The block is `tiling`'s, halved until it divides L (`diffusion_block`).
Without `denoise` the kernels are traced as they were before it existed.

Precision: the operands of every product keep the dtype they came in (bf16 in
the benchmark's cells), every product accumulates in f32; scale, mask,
maximum, exponent, sum, log-sum-exp, delta = rowsum(dO * O) and
dS = P * (dP - delta) are f32; P and dS are rounded to the operand dtype only
as operands of the next product. P is rounded BEFORE it is normalised (the
plain body rounds the normalised softmax): the one difference in rounding.

The kernel is head-major, (B, H, S, D); the entry transposes (XLA copies;
PERF.md section 5 has what they cost beside the kernel in each cell). Its
blocks come from the shapes alone (`tiling`). `interpret=True` runs the Pallas interpreter
(CPU tests reach it through `_flash`; the program never passes it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = np.float32(-1e30)          # a masked score (finite: exp gives 0)
VMEM_LIMIT_BYTES = 64 << 20         # of a v5e core's 128 MiB
VMEM_BUDGET_BYTES = 48 << 20        # what `tiling` lets the backward pass plan
# names a remat policy can keep (`models/nemotron_h._keep_products`)
OUT_NAME, LSE_NAME = "attn.core.out", "attn.core.lse"

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _lanes(width: int) -> int:
    return -(-width // 128) * 128


def tiling(S: int, D: int, Dv: int, Hq: int, Hkv: int):
    """The block of queries (= of keys) the kernel would use at these shapes,
    or None where it takes none: S must split into blocks of 512, 256 or 128
    rows; the key width must be a multiple of 64 from 128 (192: a lane tile
    and a half) with the value width a multiple of 128, or both widths 64
    (half a lane tile each); and the backward pass's residents (all keys and
    values of one key/value head, their f32 gradient sums) must fit the VMEM
    it may plan with."""
    narrow = D == Dv == 64
    if Hq % Hkv or not narrow and (D < 128 or D % 64 or Dv % 128):
        return None
    block = next((b for b in (512, 256, 128) if S % b == 0), None)
    if block is None:
        return None
    # k, v and dk, dv (two buffers each) + the f32 sums, counted at 4 bytes an
    # element whatever the dtype will be and at the lanes VMEM gives a row;
    # a block pair's scores and products
    resident = S * (_lanes(D) + _lanes(Dv)) * (2 * 2 * 4 + 4)
    temporaries = 6 * block * block * 4 + 6 * block * _lanes(D) * 4
    return block if resident + temporaries <= VMEM_BUDGET_BYTES else None


def document_starts(segment_ids):
    """(B, S) segment ids, each document one run -> (B, S) int32: the
    position at which each position's document began."""
    S = segment_ids.shape[1]
    new = jnp.pad(segment_ids[:, 1:] != segment_ids[:, :-1], ((0, 0), (1, 0)),
                  constant_values=True)
    return jax.lax.cummax(jnp.where(new, jnp.arange(S, dtype=jnp.int32), 0),
                          axis=1)


def first_key_blocks(began, block: int):
    """(B, S) document starts (`document_starts`) -> (B, S // block) int32:
    the first key block each query block's walk visits."""
    return (began[:, ::block] // block).astype(jnp.int32)


def key_block_share(began, block: int):
    """Key blocks the kernel walks over the causal key blocks (1.0: none
    skipped), mean over the batch; `began` as `first_key_blocks` takes it."""
    first = first_key_blocks(began, block)
    n = first.shape[1]
    walked = jnp.sum(jnp.arange(1, n + 1, dtype=jnp.int32) - first, axis=1)
    return jnp.mean(walked.astype(jnp.float32)) / (n * (n + 1) / 2)


def diffusion_block(S: int, D: int, Dv: int, Hq: int, Hkv: int, denoise: int):
    """The block of a block-diffusion call (module docstring): `tiling`'s,
    halved until it divides the half L = S / 2; None where there is none of
    128 rows or more, or where it is no multiple of the denoising block (a
    power of two)."""
    block = tiling(S, D, Dv, Hq, Hkv)
    if block is None or S % 2 or denoise < 1 or denoise & (denoise - 1):
        return None
    while block >= 128 and (S // 2) % block:
        block //= 2
    return block if block >= 128 and block % denoise == 0 else None


def diffusion_key_blocks(i: int, n: int):
    """The key blocks query block `i` of a block-diffusion call walks, `n`
    blocks a half: the clean blocks up to the one at its position, then, for
    a noisy query block, its own."""
    clean = list(range(n, n + i % n + 1))
    return clean + [i] if i < n else clean


def diffusion_key_block_share(S: int, block: int) -> float:
    """Key blocks a block-diffusion call's walk visits over those a causal
    walk over all S positions would: (n + 2) / (2n + 1), n = S / 2 / block."""
    n = S // 2 // block
    walked = sum(len(diffusion_key_blocks(i, n)) for i in range(2 * n))
    return walked / (2 * n * (2 * n + 1) / 2)


def _causal(shape, query_dim: int):
    """Within a diagonal block: key position <= query position."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, query_dim)
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_dim)
    return kpos <= qpos


def _in_document(shape, query_dim: int, lo, began):
    """Within the block of keys from position `lo`: key position >= where the
    query's document began (`began` broadcasts along the keys)."""
    kpos = lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_dim)
    return kpos >= began


def _by_denoise_block(op, shift):
    """Within a visited block of a block-diffusion call: op(key's denoising
    block, query's), the blocks by `pos >> shift` from the block's start
    (its two sides begin on one denoising block boundary of their halves)."""
    def seen(shape, query_dim):
        qb = jax.lax.broadcasted_iota(jnp.int32, shape, query_dim) >> shift
        kb = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_dim) >> shift
        return op(kb, qb)
    return seen


def _diffusion_walk(i, n, shift, step, carry):
    """Query block `i`'s walk of a block-diffusion call (`diffusion_key_blocks`),
    `n` blocks a half: `step(j, carry, diagonal, seen)` a visited key block."""
    c = jnp.where(i < n, i, i - n)
    carry = jax.lax.fori_loop(n, n + c, functools.partial(step, diagonal=False),
                              carry)

    def noisy(carry):
        carry = step(n + c, carry, False, _by_denoise_block(jnp.less, shift))
        return step(i, carry, False, _by_denoise_block(jnp.equal, shift))

    def clean(carry):
        return step(i, carry, False, _by_denoise_block(jnp.less_equal, shift))
    return jax.lax.cond(i < n, noisy, clean, carry)


def _fwd_kernel(*refs, block, scale, docs, denoise=None):
    if docs:
        first_ref, q_ref, k_ref, v_ref, began_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    i = pl.program_id(3)
    q = q_ref[...]

    def step(j, carry, diagonal, seen=None):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        s = jax.lax.dot_general(q, k_ref[rows, :], _NT,
                                preferred_element_type=jnp.float32) * scale
        if seen is not None:
            s = jnp.where(seen(s.shape, 0), s, MASKED)
        elif docs:
            seen = _in_document(s.shape, 0, j * block, began_ref[...])
            if diagonal:
                seen = seen & _causal(s.shape, 0)
            s = jnp.where(seen, s, MASKED)
        elif diagonal:
            s = jnp.where(_causal(s.shape, 0), s, MASKED)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v_ref.dtype), v_ref[rows, :],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (jnp.full((block, 1), MASKED, jnp.float32),
             jnp.zeros((block, 1), jnp.float32),
             jnp.zeros((block, v_ref.shape[-1]), jnp.float32))
    if denoise:
        m, l, acc = _diffusion_walk(i, np.int32(k_ref.shape[0] // 2 // block),
                                    np.int32(denoise.bit_length() - 1), step,
                                    carry)
    else:
        start = first_ref[pl.program_id(0), i] if docs else 0
        carry = jax.lax.fori_loop(start, i,
                                  functools.partial(step, diagonal=False), carry)
        m, l, acc = step(i, carry, True)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # the backward pass reads the log-sum-exp along lanes (a row a block)
    lse = jnp.broadcast_to(m + jnp.log(l), (block, 128))
    lse_ref[...] = lse.T[:1]


def _bwd_kernel(*refs, block, scale, docs, denoise=None):
    if docs:
        (first_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, began_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    r, i = pl.program_id(2), pl.program_id(3)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, do = q_ref[...], do_ref[...]
    lse, delta = lse_ref[...], delta_ref[...]                # (1, block) rows

    # scores are made transposed, (keys, queries): the row statistics then
    # broadcast along sublanes and only dq needs a transposed operand
    def step(j, dq, diagonal, seen=None):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        k, v = k_ref[rows, :], v_ref[rows, :]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if seen is not None:
            s = jnp.where(seen(s.shape, 1), s, MASKED)
        elif docs:
            seen = _in_document(s.shape, 1, j * block, began_ref[...])
            if diagonal:
                seen = seen & _causal(s.shape, 1)
            s = jnp.where(seen, s, MASKED)
        elif diagonal:
            s = jnp.where(_causal(s.shape, 1), s, MASKED)
        p = jnp.exp(s - lse)
        dv_acc[rows, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[rows, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(ds, k, _TN,
                                        preferred_element_type=jnp.float32)

    if denoise:
        dq = _diffusion_walk(i, np.int32(k_ref.shape[0] // 2 // block),
                             np.int32(denoise.bit_length() - 1), step,
                             jnp.zeros(q.shape, jnp.float32))
    else:
        start = first_ref[pl.program_id(0), i] if docs else 0
        dq = jax.lax.fori_loop(start, i, functools.partial(step, diagonal=False),
                               jnp.zeros(q.shape, jnp.float32))
        dq = step(i, dq, True)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)

    @pl.when((r == pl.num_programs(2) - 1) & (i == pl.num_programs(3) - 1))
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _scale(D: int, scale=None):
    return np.float32(1.0 / math.sqrt(D) if scale is None else scale)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _specs(q, k, v, denoise=None):
    """Grid (batch, key/value head, query head of its group, query block) and
    the three kinds of block: a block of queries of one query head, a row of
    per-query statistics of that block, the whole sequence of the key/value
    head (fetched once a head: its index does not change within a group); the
    kernels see them without the two leading dimensions. An index map takes
    and ignores the prefetched table of first key blocks, where there is
    one. `denoise`: a block-diffusion call's denoising block (its blocks are
    `diffusion_block`'s)."""
    B, Hq, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    block = (diffusion_block(S, D, Dv, Hq, Hkv, denoise) if denoise
             else tiling(S, D, Dv, Hq, Hkv))
    group = Hq // Hkv
    zero = np.int32(0)          # (a Python 0 is an i64 under jax_enable_x64)
    per_q = lambda width: pl.BlockSpec(
        (None, None, block, width),
        lambda b, g, r, i, *_: (b, g * group + r, i, zero))
    row = pl.BlockSpec((None, None, 1, block),
                       lambda b, g, r, i, *_: (b, g * group + r, zero, i))
    whole = lambda width: pl.BlockSpec((None, None, S, width),
                                       lambda b, g, r, i, *_: (b, g, zero, zero))
    return block, (B, Hkv, group, S // block), per_q, row, whole


def _grid(grid, in_specs, out_specs, docs, scratch_shapes=()):
    """pallas_call's grid arguments; with documents the first key blocks
    are prefetched into scalar memory ahead of the operands."""
    if docs is None:
        return dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                    scratch_shapes=scratch_shapes)
    return dict(grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes))


def _forward(q, k, v, interpret, scale=None, docs=None, denoise=None):
    """Head-major q (B, Hq, S, D), k (B, Hkv, S, D), v (B, Hkv, S, Dv) ->
    o (B, Hq, S, Dv), log-sum-exp (B, Hq, S) f32. `docs`: None, or (first key
    blocks (B, S / block), document starts (B, S)) int32. `denoise`: None, or
    a block-diffusion call's denoising block (module docstring)."""
    (B, Hq, S, D), Dv = q.shape, v.shape[3]
    block, grid, per_q, row, whole = _specs(q, k, v, denoise)
    in_specs, operands = [per_q(D), whole(D), whole(Dv)], (q, k, v)
    if docs is not None:
        first, began = docs
        # where each query's document began, a column of its block
        in_specs.append(pl.BlockSpec((None, block, 1),
                                     lambda b, g, r, i, *_: (b, i, np.int32(0))))
        operands = (first,) + operands + (began[:, :, None],)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block=block,
                          scale=_scale(D, scale),
                          docs=docs is not None, denoise=denoise),
        **_grid(grid, in_specs, [per_q(Dv), row], docs),
        out_shape=[jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_fwd", interpret=interpret)(*operands)
    return o, lse.reshape(B, Hq, S)


def _backward(q, k, v, o, lse, do, interpret, scale=None, docs=None,
              denoise=None):
    (B, Hq, S, D), Dv = q.shape, v.shape[3]
    block, grid, per_q, row, whole = _specs(q, k, v, denoise)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = lambda x: x.reshape(B, Hq, 1, S)      # a row a block of queries
    in_specs = [per_q(D), whole(D), whole(Dv), per_q(Dv), row, row]
    operands = (q, k, v, do, rows(lse), rows(delta))
    if docs is not None:
        first, began = docs
        # where each query's document began, a row (the scores are transposed)
        in_specs.append(pl.BlockSpec((None, 1, block),
                                     lambda b, g, r, i, *_: (b, np.int32(0), i)))
        operands = (first,) + operands + (began[:, None, :],)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block,
                          scale=_scale(D, scale),
                          docs=docs is not None, denoise=denoise),
        **_grid(grid, in_specs, [per_q(D), whole(D), whole(Dv)], docs,
                [pltpu.VMEM((S, D), jnp.float32),
                 pltpu.VMEM((S, Dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        name="flash_attention_bwd", interpret=interpret)(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 6))
def _flash(q, k, v, interpret=False, scale=None, docs=None, denoise=None):
    """The head-major core (shapes: `_forward`)."""
    return _forward(q, k, v, interpret, scale, docs, denoise)[0]


def _flash_fwd(q, k, v, interpret, scale, docs, denoise):
    o, lse = _forward(q, k, v, interpret, scale, docs, denoise)
    o, lse = checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return o, (q, k, v, o, lse, docs)


def _flash_bwd(interpret, scale, denoise, residuals, do):
    *residuals, docs = residuals
    return _backward(*residuals, do, interpret, scale, docs, denoise) + (None,)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_attention(q, k, v, *, scale=None, segment_ids=None, denoise=None):
    """(B, S, H, D)-major in and out (module docstring); the shapes must be
    ones `tiling` takes. `scale`: a number (None: D^-1/2); `segment_ids`
    (B, S): each document one run of equal ids; `denoise`: the denoising
    block of a block-diffusion call (a shape `diffusion_block` takes; no
    `segment_ids` with it)."""
    major = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    if denoise:
        if segment_ids is not None:
            raise ValueError("a block-diffusion call takes no segment ids")
        return major(_flash(major(q), major(k), major(v), False, scale, None,
                            denoise))
    docs = None
    if segment_ids is not None:
        (B, S, Hq, D), Hkv, Dv = q.shape, k.shape[2], v.shape[3]
        began = document_starts(segment_ids)
        docs = (first_key_blocks(began, tiling(S, D, Dv, Hq, Hkv)), began)
    return major(_flash(major(q), major(k), major(v), False, scale, docs))
