"""The apply's scatter as row DMAs kept in flight (TPU, Pallas).

`ops/sparse.scatter_rows` writes the step's sorted, unique rows back into the
table. XLA's scatter does that one row after the other (93 ns a valid row
into the 2^22 x 128 packed table, ledger PR 40, where its gather of the SAME
rows issues one every 9 ns). Here the table and the new rows stay in HBM, the
table is the output in place (`input_output_aliases`), and every slot whose
target is in range starts ONE async copy `new_rows[i] -> table[idx[i]]` on a
shared DMA semaphore; a block's copies are all started before the block
before it is waited for, and every copy is waited for before the kernel ends.
No row math: the optimizer stays the XLA fusion it is.

What bounds it is the scalar core, which issues every descriptor and every
wait, not the DMAs in flight (my chip runs, PR 41, 71,685 valid rows of
79,872 slots, ns a valid row; PERF.md section 6 has every reading): 93.7 XLA's
scatter; 42.5 / 42.7 a ring of 8 / 32 semaphores, each slot waiting out its
occupant; 27.1 one semaphore, a checked start a slot, the waits a block
behind in a loop; 22.6 with blocks of padding skipped and no check in a block
that is in range whole; 16.4 with both hot loops unrolled by 16 (an inner
`fori_loop(unroll=True)` in their place read 30.3: each iteration stays a
region of its own and the rows no longer interleave). So a block of targets
is read whole or not at all where the ascending order allows. Set-up pays for
what the unrolled bodies hold, every traced operation 16 times: they are kept
thin, the one block a scatter that is in range in part stays a rolled loop,
and the packed apply calls ONE instance after its switch.

Which tables take it is `ops/sparse.scatter_rows`'s choice, from the shape
(`takes_row_dmas`): a row has to be one 128-lane line of 4-byte elements, or
a one-row slice of the tiled HBM array is no DMA Mosaic can describe. Since
PR 42 that is dim 64's 2^22 x 128 table AND the narrow tables the scan holds
four rows a lane line (`ops/sparse.py` "FOUR ROWS A LANE LINE": the dim-9
cells' 2^25 x 20 as 2^23 x 128), whose apply hands over each slot's merged
LINE: the targets then never decrease but may repeat, every slot of a run of
equal targets carrying the same 512 bytes. Nothing here reads more of the
order than a block's two ends, and two copies of equal bytes to one line
leave those bytes whichever lands last (`scatter_rows(runs=True)` is the
promise; XLA's scatter, on the other lowerings, is told another truth there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 1024  # slots a grid step (a 1-D int32 block in SMEM comes in 1,024s)
UNROLL = 16   # by hand: an inner `fori_loop(unroll=True)` ran 0.2 ms slower


def _kernel(idx, new_rows, table_in, table, sem, started, *, block, n_rows):
    """Grid step g starts block g's copies and lands block g - 1's; the last
    step has no block of its own. `idx` (block,) int32 in SMEM, ascending;
    `new_rows` (W, C) and `table` (R, C) in HBM; `started` (1,) int32 in
    SMEM: the copies the block before this one started."""
    del table_in  # the same buffer as `table`
    g, blocks = pl.program_id(0), pl.num_programs(0) - 1
    zero = jnp.int32(0)  # int32 throughout: under x64 a Python bound is int64
    base = g * block  # this block's first slot

    def copy(i, row):
        return pltpu.make_async_copy(new_rows.at[pl.ds(base + i, 1), :],
                                     table.at[pl.ds(row, 1), :], sem)

    def checked(i, count):
        row = idx[i]
        ok = (row >= 0) & (row < n_rows)
        pl.when(ok)(copy(i, row).start)
        return count + ok.astype(jnp.int32)

    # the two unrolled bodies hold as few traced operations as will do: what
    # they hold is traced UNROLL times, and set-up pays for it (PERF.md 6)
    top = jnp.int32(n_rows - 1)

    def unchecked(i, count):  # clamped: a broken promise must not fault
        copy(i, jax.lax.clamp(zero, idx[i], top)).start()
        return count

    one_row = copy(0, 0)  # every copy moves one row: any descriptor waits

    def landed(i, count):
        one_row.wait()
        return count

    def unrolled(one):  # over a whole block; Mosaic unrolls no loop in part
        def some(j, count):
            for u in range(UNROLL):  # straight-line code: 16 rows interleave
                count = one(j * UNROLL + u, count)
            return count
        return jax.lax.fori_loop(zero, jnp.int32(block // UNROLL), some, zero)

    before = jnp.where(g == 0, zero, started[0])
    started[0] = zero
    # ascending targets: the block's ends say whether all, none or some of
    # its slots are in range (padding and invalid slots sort past the table)
    first, final = idx[0], idx[block - 1]
    mine = g < blocks
    whole = mine & (first >= 0) & (final < n_rows)
    some = mine & jnp.logical_not(whole | (first >= n_rows) | (final < 0))

    @pl.when(whole)
    def _():
        unrolled(unchecked)
        started[0] = jnp.int32(block)

    @pl.when(some)  # one block a scatter: rolled
    def _():
        started[0] = jax.lax.fori_loop(zero, jnp.int32(block), checked, zero)

    @pl.when(before == block)
    def _():
        unrolled(landed)

    @pl.when(before != block)
    def _():
        jax.lax.fori_loop(zero, before, landed, zero)


def scatter_rows(table: jax.Array, idx: jax.Array, new_rows: jax.Array, *,
                 block: int = BLOCK, interpret: bool = False) -> jax.Array:
    """`table.at[idx].set(new_rows, mode="drop")` for `idx` ascending, in
    place: duplicate-free, or with equal rows wherever a target repeats;
    slots whose target is out of `[0, R)` (the routed apply's invalid and
    padding slots) write nothing."""
    n_rows, n = table.shape[0], idx.shape[0]
    assert block % UNROLL == 0, block
    if n == 0:
        return table
    blocks = -(-n // block)
    # whole blocks of targets: the tail reads out of range and starts nothing
    idx = jnp.pad(idx.astype(jnp.int32), (0, blocks * block - n),
                  constant_values=n_rows)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, n_rows=n_rows),
        grid=(blocks + 1,),  # the last step lands the last block's copies
        in_specs=[pl.BlockSpec((block,), lambda g: (jnp.minimum(g, blocks - 1),),
                               memory_space=pltpu.SMEM), hbm, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(()), pltpu.SMEM((1,), jnp.int32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        name="scatter_rows_dma", interpret=interpret,
    )(idx, new_rows, table)
