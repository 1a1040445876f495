"""Causal attention as ONE fused TPU kernel, forward and backward: a block of
scores lives in VMEM only.

`causal_attention(q, k, v)`: q (B, S, Hq, D), k (B, S, Hkv, D), v
(B, S, Hkv, Dv), Hq % Hkv == 0, Dv need not be D -> (B, S, Hq, Dv); scale
D^-1/2 with D the KEY width. What `models/nemotron_h.py`'s plain blockwise
body computes, by another schedule:

- forward (`_fwd_kernel`): one grid step holds a block of queries of one head
  and ALL keys and values of its key/value head in VMEM, walks the key blocks
  up to the diagonal (the blocks above it are never touched, the diagonal
  block is masked) with a running maximum and sum, and returns the output and
  the log-sum-exp of every query row;
- backward (`_bwd_kernel`): the same walk makes each block of scores again
  from q, k and the log-sum-exp and adds up dq (over the key blocks of one
  grid step), dk and dv (in f32 VMEM scratch over the query blocks and over
  the query heads of a key/value group): five products a block pair.

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
    rows, the key width must be a multiple of 64 (192: a lane tile and a
    half) and the value width of 128, and the backward pass's residents (all
    keys and values of one key/value head, their f32 gradient sums) must fit
    the VMEM it may plan with."""
    if Hq % Hkv or D < 128 or D % 64 or Dv % 128:
        return None
    block = next((b for b in (512, 256, 128) if S % b == 0), None)
    if block is None:
        return None
    # k, v and dk, dv (two buffers each) + the f32 sums, counted at 4 bytes an
    # element whatever the dtype will be; a block pair's scores and products
    resident = S * (_lanes(D) + Dv) * (2 * 2 * 4 + 4)
    temporaries = 6 * block * block * 4 + 6 * block * _lanes(D) * 4
    return block if resident + temporaries <= VMEM_BUDGET_BYTES else None


def _causal(shape, query_dim: int):
    """Within a diagonal block: key position <= query position."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, shape, query_dim)
    kpos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_dim)
    return kpos <= qpos


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block, scale):
    i = pl.program_id(3)
    q = q_ref[...]

    def step(j, carry, diagonal):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        s = jax.lax.dot_general(q, k_ref[rows, :], _NT,
                                preferred_element_type=jnp.float32) * scale
        if diagonal:
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
    carry = jax.lax.fori_loop(0, i, functools.partial(step, diagonal=False),
                              carry)
    m, l, acc = step(i, carry, True)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # the backward pass reads the log-sum-exp along lanes (a row a block)
    lse = jnp.broadcast_to(m + jnp.log(l), (block, 128))
    lse_ref[...] = lse.T[:1]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block, scale):
    r, i = pl.program_id(2), pl.program_id(3)

    @pl.when((r == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, do = q_ref[...], do_ref[...]
    lse, delta = lse_ref[...], delta_ref[...]                # (1, block) rows

    # scores are made transposed, (keys, queries): the row statistics then
    # broadcast along sublanes and only dq needs a transposed operand
    def step(j, dq, diagonal):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        k, v = k_ref[rows, :], v_ref[rows, :]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if diagonal:
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

    dq = jax.lax.fori_loop(0, i, functools.partial(step, diagonal=False),
                           jnp.zeros(q.shape, jnp.float32))
    dq_ref[...] = (step(i, dq, True) * scale).astype(dq_ref.dtype)

    @pl.when((r == pl.num_programs(2) - 1) & (i == pl.num_programs(3) - 1))
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _scale(D: int):
    return np.float32(1.0 / math.sqrt(D))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _specs(q, k, v):
    """Grid (batch, key/value head, query head of its group, query block) and
    the three kinds of block: a block of queries of one query head, a row of
    per-query statistics of that block, the whole sequence of the key/value
    head (fetched once a head: its index does not change within a group); the
    kernels see them without the two leading dimensions."""
    B, Hq, S, D = q.shape
    Hkv, Dv = k.shape[1], v.shape[3]
    block, group = tiling(S, D, Dv, Hq, Hkv), Hq // Hkv
    zero = np.int32(0)          # (a Python 0 is an i64 under jax_enable_x64)
    per_q = lambda width: pl.BlockSpec(
        (None, None, block, width),
        lambda b, g, r, i: (b, g * group + r, i, zero))
    row = pl.BlockSpec((None, None, 1, block),
                       lambda b, g, r, i: (b, g * group + r, zero, i))
    whole = lambda width: pl.BlockSpec((None, None, S, width),
                                       lambda b, g, r, i: (b, g, zero, zero))
    return block, (B, Hkv, group, S // block), per_q, row, whole


def _forward(q, k, v, interpret):
    """Head-major q (B, Hq, S, D), k (B, Hkv, S, D), v (B, Hkv, S, Dv) ->
    o (B, Hq, S, Dv), log-sum-exp (B, Hq, S) f32."""
    (B, Hq, S, D), Dv = q.shape, v.shape[3]
    block, grid, per_q, row, whole = _specs(q, k, v)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block=block, scale=_scale(D)),
        grid=grid, in_specs=[per_q(D), whole(D), whole(Dv)],
        out_specs=[per_q(Dv), row],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, S), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_fwd", interpret=interpret)(q, k, v)
    return o, lse.reshape(B, Hq, S)


def _backward(q, k, v, o, lse, do, interpret):
    (B, Hq, S, D), Dv = q.shape, v.shape[3]
    block, grid, per_q, row, whole = _specs(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = lambda x: x.reshape(B, Hq, 1, S)      # a row a block of queries
    return pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, scale=_scale(D)),
        grid=grid,
        in_specs=[per_q(D), whole(D), whole(Dv), per_q(Dv), row, row],
        out_specs=[per_q(D), whole(D), whole(Dv)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32),
                        pltpu.VMEM((S, Dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        name="flash_attention_bwd", interpret=interpret)(
            q, k, v, do, rows(lse), rows(delta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, interpret=False):
    """The head-major core (shapes: `_forward`)."""
    return _forward(q, k, v, interpret)[0]


def _flash_fwd(q, k, v, interpret):
    o, lse = _forward(q, k, v, interpret)
    o, lse = checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return o, (q, k, v, o, lse)


def _flash_bwd(interpret, residuals, do):
    return _backward(*residuals, do, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_attention(q, k, v):
    """(B, S, H, D)-major in and out (module docstring); the shapes must be
    ones `tiling` takes."""
    major = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return major(_flash(major(q), major(k), major(v)))
