"""The pack and the unpack of a narrow table's line form, one pass each (TPU,
Pallas).

`ops/sparse.py` "FOUR ROWS A LANE LINE" holds a packed table of 17 to 32
columns as `(L, 128)` lines inside `train_many`'s scan: line l = the rows l,
l + L, l + 2L, l + 3L, each at 32 lanes of its own. The compiler stores the
table's arrays (weights, each optimizer slot) rows-in-lanes, so in its terms
the pack is: take each `(columns, 4L)` array, cut it into its four quarters
`(columns, L)`, stack all of them into `(128, L)`, 32 sublanes a quarter,
and transpose. Written in XLA that is three to four passes over 3 to 4 GiB
each with 8 GiB of temporaries (2.43 + 0.82 ms a step of a 16-step scan in
`deepfm9.train_zipf`, 2.52 the unpack; with the transposes alone as kernels
and the stacking left to XLA 1.44 + 3.64; my chip runs, PR 42). Here each is
ONE pass with nothing between the split arrays and the lines: the pack's
grid step reads a block of `block` lines' worth of every quarter of every
array (the pipeline's own block copies), stacks them in VMEM, transposes
there and writes `(block, 128)` lines; the unpack's step transposes a block
of lines and writes each array's four quarter blocks, which XLA then joins
lane after lane (one copy an array). No row math, no index: data movement.

Which tables take it is `ops/sparse.takes_lines`' choice: every table in the
line form (the rule lets in only line counts that are multiples of 128, the
smallest block here) on a TPU lowering; the other lowerings run the same
movement as `jax.numpy` slices and a transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, PLACES = 128, 4
STRIDE = LANES // PLACES
ZERO = np.int32(0)  # int32 in every index map: under x64 a Python int is int64
BLOCKS = (2048, 1024, 512, 256, 128)  # lines a grid step: the largest that divides


def block_for(lines: int) -> int:
    """Lines a grid step, or 0 where no block divides `lines` (no table the
    rule lets into the line form: `ops/sparse.LINE_BLOCK`)."""
    return next((b for b in BLOCKS if lines % b == 0), 0)


def _pack_kernel(*refs, offsets):
    """`refs`: for each array its four quarter blocks (columns, block), rows
    in lanes; then `out` (block, 128) and `stage` (128, block) in VMEM, whose
    sublanes no array fills are zeroed once."""
    *quarters, out, stage = refs

    @pl.when(pl.program_id(0) == 0)
    def _():
        stage[...] = jnp.zeros_like(stage)

    for j, off in enumerate(offsets):
        for k in range(PLACES):
            quarter = quarters[PLACES * j + k]
            at = k * STRIDE + off
            stage[at:at + quarter.shape[0], :] = quarter[...]
    out[...] = stage[...].T


def pack_lines(*arrays: jax.Array, offsets, interpret: bool = False
               ) -> jax.Array:
    """Arrays `(columns_j, 4L)`, rows in lanes -> (L, 128) lines: row r of
    the table at lanes `32 * (r // L)` of line `r % L`, array j's columns
    from lane `offsets[j]` of that place on, every other lane 0."""
    rows = arrays[0].shape[1]
    lines = rows // PLACES
    block = block_for(lines)
    assert block and len(offsets) == len(arrays), (lines, offsets)
    steps = lines // block
    return pl.pallas_call(
        functools.partial(_pack_kernel, offsets=tuple(offsets)),
        grid=(steps,),
        in_specs=[pl.BlockSpec((a.shape[0], block), functools.partial(
            lambda first, i: (ZERO, first + i), np.int32(k * steps)))
            for a in arrays for k in range(PLACES)],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, ZERO)),
        out_shape=jax.ShapeDtypeStruct((lines, LANES), arrays[0].dtype),
        scratch_shapes=[pltpu.VMEM((LANES, block), arrays[0].dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),  # `stage` is zeroed at step 0
        name="pack_lines", interpret=interpret,
    )(*[a for a in arrays for _ in range(PLACES)])


def _unpack_kernel(lines, *outs, offsets):
    """`lines` (block, 128); `outs`: for each array its four quarter blocks
    (columns_j, block), rows in lanes."""
    by_place = lines[...].T
    for j, off in enumerate(offsets):
        for k in range(PLACES):
            out, at = outs[PLACES * j + k], k * STRIDE + off
            out[...] = by_place[at:at + out.shape[0], :]


def unpack_lines(lines: jax.Array, *, columns, offsets,
                 interpret: bool = False):
    """(L, 128) lines -> for each array its four quarters `(columns[j], L)`,
    rows in lanes (quarter k = the table's rows `k * L` on): `pack_lines`'
    inverse up to the callers' concatenation of a quarter's lanes (a window
    of 10 sublanes is no end of a copy Mosaic takes, in VMEM or in HBM, so
    the quarters are arrays of their own and the pipeline writes them)."""
    n = lines.shape[0]
    block = block_for(n)
    assert block and len(offsets) == len(columns), (n, offsets)
    quarters = [c for c in columns for _ in range(PLACES)]
    return tuple(pl.pallas_call(
        functools.partial(_unpack_kernel, offsets=tuple(offsets)),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block, LANES), lambda i: (i, ZERO))],
        out_specs=[pl.BlockSpec((c, block), lambda i: (ZERO, i))
                   for c in quarters],
        out_shape=[jax.ShapeDtypeStruct((c, n), lines.dtype)
                   for c in quarters],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="unpack_lines", interpret=interpret,
    )(lines))
