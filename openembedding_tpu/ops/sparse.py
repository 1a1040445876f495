"""Single-shard sparse gather / scatter / fused-optimizer-apply.

Counterpart of the reference's server-side hot path on one shard:
`EmbeddingOptimizerVariable::pull_weights` (table read, `EmbeddingOptimizerVariable.h:
242-266`) and `update_weights` (commit + reduce + per-unique-row optimizer update,
`:273-297`). Here a "shard" is just the rows of the table a device owns; the ops are
plain XLA, but for two movements whose kernel the shape chooses where the program
is lowered for a TPU: `ops/pallas_scatter.py` (`takes_row_dmas`) and
`ops/pallas_lines.py` (`takes_lines`).

Scatter correctness under static shapes: padding slots of the unique-id buffer are
scattered with out-of-bounds indices and `mode='drop'`, so they can never corrupt row 0.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..utils import metrics as _metrics
from ..utils import trace as _trace
from .dedup import UniqueResult, unique_with_counts


def lookup_rows(weights: jax.Array, rows: jax.Array,
                valid: jax.Array = None, *, sorted_unique: bool = False
                ) -> jax.Array:
    """Gather rows (table read; reference `pull_weights` fast path). Out-of-range or
    invalid row indices return zeros — consistent with the gradient path, which drops
    them, so a buggy id pipeline can't create train/serve skew.

    `sorted_unique`: caller guarantees `rows` is ascending with no in-range
    duplicates (the dedup output) — lets XLA use the vectorized gather path."""
    with _trace.scope("sparse", "pull"):
        return _gather_rows(weights, rows, valid, sorted_unique=sorted_unique)


def _gather_rows(weights, rows, valid=None, *, sorted_unique=False,
                 ascending=False):
    """`lookup_rows` without its stage name: the fused applies read the rows
    they update through this, so that read counts under `sparse.apply`.
    `ascending`: `rows` never decreases but may repeat (the LINES of sorted
    unique rows, "FOUR ROWS A LANE LINE" below)."""
    n_rows = weights.shape[0]
    in_range = (rows >= 0) & (rows < n_rows)
    if valid is not None:
        in_range = in_range & valid
    # fill-mode gather: positive out-of-bounds indices read 0 WITHOUT clipping
    # (clipping would collapse distinct OOB sentinels onto row n_rows-1 and break
    # the unique_indices promise); negative indices wrap in jax, so the explicit
    # in_range mask below still zeroes those
    out = weights.at[rows].get(mode="fill", fill_value=0,
                               indices_are_sorted=sorted_unique or ascending,
                               unique_indices=sorted_unique)
    return jnp.where(in_range.reshape(in_range.shape + (1,) * (out.ndim - in_range.ndim)),
                     out, jnp.zeros_like(out))


def scatter_rows(weights: jax.Array, rows: jax.Array, values: jax.Array,
                 valid: jax.Array = None, *, sorted_unique: bool = False,
                 runs: bool = False) -> jax.Array:
    """Overwrite rows; invalid slots are dropped via out-of-bounds scatter.

    `valid=None` means `rows` is already fully routed (invalid entries already
    carry out-of-bounds indices). `sorted_unique`: rows genuinely ascending and
    duplicate-free — TPU scatters serialize without these hints; this is the
    difference between a vectorized update and a row loop over every slot.
    `runs` beside it: `rows` never decreases but a target may repeat, every
    slot of such a run carrying the SAME values (the packed apply's merged
    lines). The row DMAs write equal bytes twice; XLA's scatter is told the
    truth: the slots after a run's first go out of bounds, each to a row of
    its own, and the order is no longer promised.

    Under that promise a table whose rows are one lane line is written by
    row DMAs kept in flight where the program is lowered for a TPU
    (`ops/pallas_scatter.py`; `takes_row_dmas`: the choice is the shape's
    alone); `sparse.scatters{path=}` counts, once a traced scatter under the
    promise, 1 on the path the shape chose ("dma" / "xla") and 0 on the other."""
    n_rows = weights.shape[0]
    if valid is None:
        target = rows
    else:
        target = jnp.where(valid, rows, n_rows)  # out of bounds -> dropped

    def xla(weights, target, values):
        if runs:
            head = jnp.concatenate([jnp.ones((1,), bool),
                                    target[1:] != target[:-1]])
            target = jnp.where(
                head & (target < n_rows), target,
                n_rows + jnp.arange(target.shape[0], dtype=target.dtype))
        return weights.at[target].set(values, mode="drop",
                                      indices_are_sorted=sorted_unique and not runs,
                                      unique_indices=sorted_unique)

    if sorted_unique:
        dma = takes_row_dmas(weights)
        for path, chosen in (("dma", dma), ("xla", not dma)):
            _metrics.observe("sparse.scatters", int(chosen), "sum",
                             labels={"path": path})
        if dma:
            # Pallas is half a second of import and more (PERF.md section
            # 7): paid where such a table first traces its scatter
            from . import pallas_scatter
            return jax.lax.platform_dependent(
                weights, target, values, tpu=pallas_scatter.scatter_rows,
                default=xla)
    return xla(weights, target, values)


def takes_row_dmas(table: jax.Array) -> bool:
    """A row of `table` is ONE 128-lane line of 4-byte elements, 512 bytes
    that lie together in the tiled HBM array: the one-row slice Mosaic takes
    as a DMA's end. A row of two lines and more, or of 2-byte elements, it
    refuses ("slice shape along dimension 0 must be aligned to tiling (8)":
    compiles for the described v5e at widths 256, 512, 8192 and bf16 x 128,
    PR 41), so the language models' token tables keep XLA's scatter."""
    return (table.ndim == 2 and table.dtype.itemsize == 4
            and table.shape[1] == 128)


# ---------------------------------------------------------------------------
# packed table layout (weights + optimizer slots in ONE array)
# ---------------------------------------------------------------------------
#
# The fused apply is LATENCY-bound: gather and scatter pay per index, hardly
# per byte, and what an index costs is set by how the compiler stores a row.
# On the v5e (PERF.md sections 5-6): a table `f32[2^25, 20]` is stored with
# the ROWS in lanes, one row is 20 separate places, and XLA's scatter costs
# 105 ns a SLOT of the unique buffer, padding routed out of bounds included
# (11.2 / 8.5 / 5.7 ms over 106,496 / 79,872 / 53,248 slots, PR 29's probe),
# its sorted gather 28 ns a slot. A row that is ONE 128-lane line of f32
# (dim 64's 2^22 x 128) is gathered at 9 ns a slot and, since PR 41, written
# back by row DMAs kept in flight at 9-16 ns a VALID row (`scatter_rows`,
# `ops/pallas_scatter.py`; XLA's scatter took 93 there). Since PR 42 the
# narrow table is held so that its unit of reading and writing is such a
# line too: "FOUR ROWS A LANE LINE" below. Storing
# weights and slots separately pays one gather/scatter pair PER ARRAY;
# concatenating them column-wise into one (rows, dim+Σslot) array pays ONE
# pair. The packed form
# only exists inside `Trainer.train_many`'s scan (pack at entry, unpack at
# exit, amortized over K steps) so checkpoints, serving, offload and the
# sharded protocol all keep the split layout. What the pair runs over is
# "WHAT THE APPLY WORKS OVER", further down.
#
# Width gate: XLA's gather for 32 < width < 128 materializes a 128-lane-padded
# 2.0x temp copy of the WHOLE table every scan iteration (measured via
# compiled.memory_analysis(); PERF.md "dim-64 single-chip HBM budget"), so
# packing only engages when the packed width stays in the sublane-packed
# regime (<= 32) or is lane-exact (% 128 == 0).
#
# FOUR ROWS A LANE LINE (the LINE FORM of a narrow packed table). Where
# `takes_lines` holds, `pack_table` pads the packed row with zero columns to
# LINE_STRIDE = 32 and hands the scan `(L, 128)` f32, L = R / 4: line l holds
# the rows l, l + L, l + 2L, l + 3L, row r at lanes `32 * (r // L)` of line
# `r % L` (no padding rows: the rule takes whole blocks of lines alone, so an
# id is in range in one form exactly where it is in the other).
# `unpack_table` is the inverse; the padding columns are 0 at entry and stay
# 0. Nothing says which form an array is in but its shape beside its layout:
# 128 columns under a layout whose total is less (`in_lines`), so whoever
# reads a packed array is given the layout's width (no default anywhere: a
# line-form table read as L rows of 128 would be wrong rows in silence), and
# whoever needs the ROW count asks `packed_rows`.
# - WHY QUARTERS and not four neighbouring rows a line: the TPU compiler
#   stores `f32[R, 10]` with the rows in lanes, so a line of rows 4l..4l+3
#   needs every fourth LANE of the source: it lowers `x[k::4]` to four
#   gathers of R / 4 indices each and the plain reshape to a 16 GiB padded
#   copy (compiles for the described v5e, PR 42). By quarters the pack is
#   four contiguous slices of each array stacked on sublanes and ONE 2-D
#   transpose, and the unpack its mirror: where the program is lowered for
#   a TPU, one pass each, straight between the split arrays and the lines
#   (`ops/pallas_lines.py`, which has the readings: XLA's own passes cost
#   5.8 ms a step of a 16-step scan and 8 GiB of temporaries, these 2.4; so
#   the rule takes only a table whose lines those kernels' blocks divide,
#   and a TPU lowering of the line form never means XLA's passes).
# - Inside the plan and the apply a row goes by its LINE-MAJOR id
#   `4 * line + place` (`_line_major`, one elementwise pass over the
#   positions before the dedup): the unique buffer is then sorted by line,
#   the rows of one line are neighbours in it, `idx >> 2` is a slot's line
#   and `idx & 3` its place. A row's duplicates keep their order among
#   themselves under either id, so every sum adds in the order it did.
# - The pull gathers the LINES of the step's sorted unique rows (`idx >> 2`:
#   ascending, not duplicate-free, and XLA is told so) and picks each slot's
#   own 32 lanes (`idx & 3`); the plan hands on both, the rows for the
#   expansion and the row math, the lines for the apply.
# - The apply's row math is the row form's, on (W, 32) rows whose padding
#   columns pass through as zeros. Then each slot's NEW LINE: the line it
#   gathered with every row the step updated in it put in. The unique buffer
#   is sorted by line, so those rows are a run of at most 4 adjacent slots:
#   one elementwise pass that looks 3 slots to either side (`_merge_lines`;
#   ONE, over all n slots after the apply's switch, the rungs handing on
#   their (W, 32) new rows: a merge a rung was four to trace and lower, and
#   set-up is an end-to-end metric). Every slot of a run so holds the SAME
#   merged line and writes it to the same target: the targets stay
#   ascending, which is all the row-DMA kernel's block test reads, and bytes
#   written twice are equal bytes (`scatter_rows(runs=True)`). Same values in
#   the same places: the table after a scan is the row form's bit for bit.
# - Which tables (ADAPT, on the shape alone; `takes_lines`): f32 weights and
#   slots (`packed_layout`); 16 < packed width <= 32, so a line holds
#   exactly 4 rows and a merge looks at 3 neighbours; FAST_MEMORY_BYTES and
#   more, i.e. the table lives in HBM and pays the latency per slot (the
#   ladder's own rule; a smaller one stays in fast memory, where XLA's
#   scatter costs 40 ns a slot and a conditional would take it out); the
#   PADDED bytes within PACKED_MAX_BYTES; whole blocks of lines (R a multiple
#   of 4 x LINE_BLOCK = 512: no padding row, and the pack's and the unpack's
#   kernels take it). The criteo-1TB dim-9 table (10 + 10 columns, 2^25 rows
#   a chip: 2.7 GB as rows, 4 GiB as lines) is the one the benchmark holds;
#   a dim-64 model's first-order table (width 2, 32 MiB), every table of
#   rows 128 wide and wider and every other table keep the row form.
# - Counted: `sparse.packed_tables{form="lines"|"rows"}` (trace time, one a
#   packed table a trace); `sparse.line_mates{table=}` (of a step's valid
#   unique rows, the share whose line holds another of them: how often the
#   merge does anything, and how many of the DMAs are repeats; the window's
#   largest step, beside `sparse.apply_fill`; 0 for a table of one row a
#   line).

PACKED_MAX_SUBLANE_WIDTH = 32
# pack/unpack at the scan boundary transiently holds BOTH layouts (~2x the
# packed bytes); tables whose packed form exceeds this skip packing so the
# boundary cannot OOM a chip whose steady state fits (bytes, per shard).
PACKED_MAX_BYTES = 4 << 30
LINE_LANES = 128                        # a lane line of 4-byte elements
LINE_ROWS = 4                           # rows of a narrow packed table a line
LINE_STRIDE = LINE_LANES // LINE_ROWS   # lanes a row
LINE_BLOCK = 128                        # lines: the smallest block of the
#                                         pack's and the unpack's kernels


def packed_layout(dim: int, slots: Dict[str, jax.Array],
                  weights_dtype=jnp.float32):
    """Static column layout ((name, width), ...) for a packable table, or None
    when packing is unsafe/unprofitable (no slots; non-f32 weights or slots; a
    packed width in XLA's padded-copy regime; a packed size whose scan-entry
    boundary would risk OOM — see PACKED_MAX_BYTES).

    Non-f32 weights are refused, not upcast: a bf16 table packed as f32 would
    (a) double its HBM footprint for the whole scan and (b) skip the
    round-to-storage-dtype that the split path applies on every scatter,
    breaking bit-parity between train_many and K train_step calls."""
    if not slots:
        return None  # SGD-like: weights alone are already one array
    if jnp.dtype(weights_dtype) != jnp.float32:
        return None
    names = sorted(slots)
    widths = [int(slots[n].shape[1]) for n in names]
    total = dim + sum(widths)
    if not (total <= PACKED_MAX_SUBLANE_WIDTH or total % 128 == 0):
        return None
    if any(slots[n].dtype != jnp.float32 for n in names):
        return None
    rows = int(next(iter(slots.values())).shape[0])
    if rows * total * 4 > PACKED_MAX_BYTES:
        return None
    return tuple(zip(names, widths))


def packed_width(dim: int, layout) -> int:
    """Columns of a packed row: the weights' and every slot's."""
    return dim + sum(w for _, w in layout)


def takes_lines(rows: int, width: int) -> bool:
    """A packed f32 table of `rows` x `width` is held four rows a lane line
    inside the scan ("FOUR ROWS A LANE LINE" above, which gives the reasons
    for each term)."""
    return (LINE_STRIDE // 2 < width <= LINE_STRIDE
            and rows % (LINE_ROWS * LINE_BLOCK) == 0
            and rows * width * 4 >= FAST_MEMORY_BYTES
            and rows * LINE_STRIDE * 4 <= PACKED_MAX_BYTES)


def in_lines(packed: jax.Array, width: int) -> bool:
    """`packed`, whose rows are `width` columns by their layout, is in the
    line form: read from the shape, as the form is stated nowhere else."""
    return width < LINE_LANES and packed.shape[1] == LINE_LANES


def packed_rows(packed: jax.Array, width: int) -> int:
    """Rows of a packed table in either form: the first row index that is
    out of range."""
    return packed.shape[0] * (LINE_ROWS if in_lines(packed, width) else 1)


def pack_table(weights: jax.Array, slots: Dict[str, jax.Array],
               layout) -> jax.Array:
    """-> (rows, dim+Σwidths) f32; column order: weights, then layout order.
    Where `takes_lines` holds -> (rows / 4, 128), four rows a line."""
    with _trace.scope("sparse", "pack"):
        columns = [weights.astype(jnp.float32)] + [slots[name]
                                                   for name, _ in layout]
        rows, width = weights.shape[0], sum(c.shape[1] for c in columns)
        lines = takes_lines(rows, width)
        for form, chosen in (("lines", lines), ("rows", not lines)):
            _metrics.observe("sparse.packed_tables", int(chosen), "sum",
                             labels={"form": form})
        if not lines:
            return jnp.concatenate(columns, axis=1)
        # the compiler holds a narrow array rows-in-lanes, so each as
        # (columns, 4L) is no data moved; then a quarter of the rows a place
        n = rows // LINE_ROWS
        arrays = [c.T for c in columns]
        offsets = _place_offsets([c.shape[1] for c in columns])

        def stacked(*arrays):  # (128, L) of the quarters, ONE transpose
            return jnp.concatenate(
                [jnp.pad(jnp.concatenate([a[:, k * n:(k + 1) * n]
                                          for a in arrays], axis=0),
                         ((0, LINE_STRIDE - width), (0, 0)))
                 for k in range(LINE_ROWS)], axis=0).T
        return _on_tpu("pack_lines", stacked, *arrays, offsets=offsets)


def _place_offsets(widths) -> Tuple[int, ...]:
    """Where each array's columns begin inside a row's 32 lanes: one after
    the other, the layout's order."""
    return tuple(sum(widths[:j]) for j in range(len(widths)))


def _on_tpu(kernel: str, plain, *args, **static):
    """`plain(*args)`, and where the program is lowered for a TPU
    `ops/pallas_lines.py`'s `kernel(*args, **static)` in its place: the same
    movement in one pass (its docstring has the readings; `takes_lines` lets
    in no table whose lines its blocks do not divide)."""
    from . import pallas_lines
    return jax.lax.platform_dependent(
        *args, default=plain,
        tpu=functools.partial(getattr(pallas_lines, kernel), **static))


def unpack_table(packed: jax.Array, layout, dim: int, weights_dtype
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`pack_table`'s inverse, from either form."""
    with _trace.scope("sparse", "unpack"):
        widths = (dim,) + tuple(w for _, w in layout)
        offsets = _place_offsets(widths)
        if in_lines(packed, sum(widths)):
            def quarters(p):  # every array's four (columns, L) quarters
                by_place = p.T
                return tuple(
                    by_place[k * LINE_STRIDE + off:k * LINE_STRIDE + off + w]
                    for off, w in zip(offsets, widths)
                    for k in range(LINE_ROWS))
            parts = _on_tpu("unpack_lines", quarters, packed,
                            columns=widths, offsets=offsets)
            columns = [jnp.concatenate(parts[LINE_ROWS * j:LINE_ROWS * (j + 1)],
                                       axis=1).T
                       for j in range(len(widths))]
        else:
            columns = [packed[:, off:off + w]
                       for off, w in zip(offsets, widths)]
        return columns[0].astype(weights_dtype), {
            name: c for (name, _), c in zip(layout, columns[1:])}


def _line_major(packed: jax.Array, row_ids: jax.Array) -> jax.Array:
    """Row ids of a table in the line form -> line-major ids, `4 * line +
    place` (line `r % L`, place `r // L`); an id out of range stays as it
    is, out of range."""
    n = packed.shape[0]
    ok = (row_ids >= 0) & (row_ids < LINE_ROWS * n)
    return jnp.where(ok, (row_ids % n) * LINE_ROWS + row_ids // n, row_ids)


def _line(idx: jax.Array) -> jax.Array:
    """The line of a line-major id (`idx >> 2`)."""
    return idx >> (LINE_ROWS.bit_length() - 1)


def _place(idx: jax.Array) -> jax.Array:
    """Which 32 lanes of its line a line-major id stands for (`idx & 3`)."""
    return idx & (LINE_ROWS - 1)


def _pick_rows(lines: jax.Array, idx: jax.Array) -> jax.Array:
    """(m, 128) lines gathered at `idx >> 2` (`idx` line-major) -> (m, 32):
    each slot's own row, the lanes `32 * (idx & 3)` on."""
    k = _place(idx)
    out = lines[:, -LINE_STRIDE:]
    for q in range(LINE_ROWS - 2, -1, -1):
        out = jnp.where((k == q)[:, None],
                        lines[:, q * LINE_STRIDE:(q + 1) * LINE_STRIDE], out)
    return out


def _line_rows(packed: jax.Array, idx: jax.Array, *, ascending: bool = False):
    """The rows of a table in the line form at the line-major ids `idx` (out
    of range: 0) -> ((m, 32) rows, zero padding columns and all; the (m, 128)
    lines they were picked from)."""
    lines = _gather_rows(packed, _line(idx), ascending=ascending)
    return _pick_rows(lines, idx), lines


def gather_packed_rows(packed: jax.Array, width: int, row_ids: jax.Array
                       ) -> jax.Array:
    """The rows of a packed table in either form at `row_ids`, once an id
    (out of range: 0) -> (m, width) in the row form, (m, 32) with zero
    padding columns in the line form: what a pull with no plan to share
    reads (a hash table's, a pipelined step's)."""
    if not in_lines(packed, width):
        return _gather_rows(packed, row_ids)
    return _line_rows(packed, _line_major(packed, row_ids))[0]


def _merge_lines(lines: jax.Array, idx: jax.Array, new_rows: jax.Array
                 ) -> jax.Array:
    """Each slot's NEW LINE: `lines[i]` (gathered at `_line(idx[i])`) with
    `new_rows[j]` (m, 32) put in at the lanes of `_place(idx[j])` for every
    slot j of the same line. `idx` (line-major) is ascending and
    duplicate-free, so those are at most 4 adjacent slots, each with lanes of
    its own: one elementwise pass that looks 3 slots to either side for the
    id that each lane of the line stands for."""
    m, reach = idx.shape[0], LINE_ROWS - 1
    lane_place = jnp.arange(LINE_LANES, dtype=idx.dtype) // LINE_STRIDE
    wanted = (_line(idx) * LINE_ROWS)[:, None] + lane_place[None, :]
    # no id is -1: a neighbour past either end matches nothing
    idx_p = jnp.pad(idx, (reach, reach), constant_values=-1)
    new_p = jnp.pad(jnp.tile(new_rows, (1, LINE_ROWS)),
                    ((reach, reach), (0, 0)))
    out = lines
    for at in range(2 * reach + 1):
        out = jnp.where(idx_p[at:at + m][:, None] == wanted,
                        new_p[at:at + m], out)
    return out


def _route_unique(n_rows: int, row_ids: jax.Array, pre_counts: jax.Array):
    """The dedup of both fused applies and of a packed table's plan: padding
    (count 0) and negative ids sort under the out-of-range key `n_rows` (the
    first of `_dedup_routed`'s invariants)."""
    keep = (row_ids >= 0 if pre_counts is None
            else (pre_counts > 0) & (row_ids >= 0))
    return unique_with_counts(jnp.where(keep, row_ids, n_rows))


def _unique_slots(n_rows: int, uniq, pre_counts: jax.Array):
    """-> (counts, idx) of the unique buffer: what each slot sums of
    `pre_counts` (0 on a sentinel slot; None = one a position, which the
    dedup has counted already) and the row it stands for (invalid slots at
    distinct out-of-bounds rows). Under `sparse.reduce`."""
    n = uniq.order.shape[0]
    counts = (uniq.counts if pre_counts is None
              else uniq.segment_reduce(pre_counts))
    counts = jnp.where(uniq.unique_ids < n_rows, counts, 0)
    idx = jnp.where(counts > 0, uniq.unique_ids,
                    n_rows + jnp.arange(n, dtype=uniq.unique_ids.dtype))
    return counts, idx


def _dedup_routed(n_rows: int, row_ids: jax.Array, grads: jax.Array,
                  pre_counts: jax.Array):
    """Shared dedup/sentinel prologue of both fused applies -> (g, counts, idx).

    Routing invariants (load-bearing — both apply paths depend on them):
    - padding (count==0) AND negative ids route to the out-of-range sort key
      `n_rows` BEFORE dedup: jax wraps negative scatter indices, so id -1
      would otherwise silently train the LAST row and break the sorted/unique
      promises below (mode='drop' only drops the high side);
    - sentinel slots get counts 0 after the segment sums;
    - every invalid unique slot i maps to the DISTINCT out-of-bounds row
      n_rows + i, so `idx` is genuinely ascending and duplicate-free — the
      indices_are_sorted/unique_indices promises hold exactly and XLA emits
      the vectorized gather/scatter instead of a serialized row loop;
    - invalid ids sort LAST under that key, so the valid unique slots are a
      prefix of (`g`, `counts`, `idx`): what `_over_unique_prefix` cuts."""
    if pre_counts is None:
        pre_counts = jnp.ones(row_ids.shape[:1], jnp.int32)
    uniq = _route_unique(n_rows, row_ids, pre_counts)
    # the sums over duplicates get a name of their own: on the owner side of
    # the exchange they run over S times the positions
    with _trace.scope("sparse", "reduce"):
        g = uniq.segment_reduce(grads)
        counts, idx = _unique_slots(n_rows, uniq, pre_counts)
    return g, counts, idx


# ---------------------------------------------------------------------------
# WHAT THE APPLY WORKS OVER: the unique rows it has, not every position.
#
# `_dedup_routed` dedups n positions into a buffer of n slots (static shapes),
# and the gather of the rows to update, the optimizer's row math and the
# scatter back all pay per SLOT, whether the slot holds a row or is padding
# routed out of bounds and dropped (PERF.md section 5; PR 27 measured the same
# scatter at 42.0 ms over 425,984 slots and 11.0 ms over 106,496 with the same
# ~70k rows in it; only the scatter of rows 128 wide and wider gets padding
# nearly free). Under Zipf traffic a third of the slots are padding.
#
# - PREFIX: invalid ids sort last (key `n_rows`), so the valid unique rows are
#   the first `n_valid = sum(counts > 0)` slots of (`idx`, `g`, `counts`) and
#   cutting the buffer to a working size W >= n_valid is the static slice
#   `[:W]`: no copy, no permutation. Padding slots inside the slice keep their
#   distinct out-of-bounds rows, so the sorted/unique promises hold exactly.
# - LADDER: W is the smallest rung of `apply_ladder(n)` that holds n_valid,
#   chosen on the device each step (`lax.switch`). Rungs are quarters of n,
#   each rounded up to a multiple of 128 and clamped to n, equal rungs merged
#   (106,496 -> 26,624 / 53,248 / 79,872 / 106,496). Quarters: Zipf batches
#   land on the 3/4 rung with 7-9% to spare, click logs with many
#   low-cardinality fields on the lower ones; eighths would double the program
#   text for no cell. A buffer too short to split (n <= 128) traces no switch.
# - WHERE: tables of FAST_MEMORY_BYTES and more. A smaller one the TPU
#   compiler keeps in the chip's fast memory across the scan (layout `S(1)`),
#   where the scatter costs 40 ns a slot; a conditional's operands live in
#   HBM, so the switch would take it out: PR 29 measured the dim-64 cell's
#   2^22 x 2 first-order table (32 MiB) at 4.2 -> 6.1 ms a step that way.
# - EXACT: the last rung is n, the code as it was, so no row is ever dropped;
#   a row's update reads only that row, so every rung leaves the same table
#   bit for bit. A step on the last rung runs under `sparse.full_size`.
# - COUNTED: both applies hand back, on request, the step's load
#   {"apply_fill": n_valid / n, "apply_full_steps": 1 on the last of
#   several rungs (a buffer with one rung has nothing to overrun: 0)}; the
#   trainers carry it as `{table}/apply_fill` in the step's stats and fold it
#   to `sparse.apply_fill{table=}` / `sparse.apply_full_steps{table=}`.
# `segment_reduce` pays per input position and is not part of this; the
# dedup is sorts over the positions and holds no such pass (`ops/dedup.py`,
# `_run_heads`). A packed table's forward pull is ("ONE DEDUP AND ONE TABLE
# GATHER A STEP", further down): it reads the step's unique rows at the same
# rung.
# ---------------------------------------------------------------------------

# what a v5e's compiler keeps in fast memory: a 64 and an 80 MiB table yes, a
# 128 MiB one no (compiles for a described v5e, PR 29)
FAST_MEMORY_BYTES = 128 << 20


def apply_ladder(n: int) -> Tuple[int, ...]:
    """The working sizes an apply over a unique buffer of n slots chooses
    from, ascending; the last is always n."""
    return tuple(sorted({min(n, -(-n * q // 512) * 128) for q in (1, 2, 3, 4)}))


def _over_unique_prefix(counts: jax.Array, tables, tail):
    """`tail(W, settle)` (gather, row math and scatter into `tables` over the
    first W slots of what `_dedup_routed` returned) at the smallest rung that
    holds the step's valid unique rows -> (tail's result, the step's load).

    `settle` takes (the tables, their new rows) through on the way to the
    scatter. Inside the switch it is an `optimization_barrier`: it says in the
    program that the scatter's table is the one the gather has finished
    reading. Without it the TPU compiler updates the table in place only in
    the first and the last branch of a conditional and COPIES it in every
    branch between (PR 29's probe: +9.8 ms a step for the 2.7 GB dim-9 table,
    PERF.md section 6; `tests/test_tpu_compile.py` pins it)."""
    n = counts.shape[0]
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(tables))
    ladder = apply_ladder(n) if nbytes >= FAST_MEMORY_BYTES else (n,)
    n_valid = jnp.sum(counts > 0, dtype=jnp.int32)
    load = {"apply_fill": n_valid.astype(jnp.float32) / n,
            "apply_full_steps": jnp.zeros((), jnp.int32)}
    if len(ladder) == 1:  # nothing to choose from, so nothing to overrun
        return tail(n, lambda x: x), load
    rung = jnp.sum(n_valid > jnp.asarray(ladder[:-1], jnp.int32),
                   dtype=jnp.int32)
    load["apply_full_steps"] = (rung == len(ladder) - 1).astype(jnp.int32)
    settle = jax.lax.optimization_barrier

    def full_size():
        with _trace.scope("sparse", "full_size"):
            return tail(n, settle)

    return jax.lax.switch(
        rung, [functools.partial(tail, W, settle) for W in ladder[:-1]]
        + [full_size]), load


# ---------------------------------------------------------------------------
# ONE DEDUP AND ONE TABLE GATHER A STEP (a packed table, whose scan holds
# weights and optimizer slots in one array). The forward pull and the apply
# read the SAME rows of the SAME table: nothing writes it between them (the
# apply's scatter is the step's last op on it). A gather from the table is
# latency-bound per index (28 ns against the 2.7 GB dim-9 table, above), and
# the pull used to pay it once per POSITION (106,496 for about 72,000 rows)
# and the apply once more per unique slot: 3.0 + 2.2 ms of a 19.7 ms step
# (ledger, PR 34). So the step PLANS before it pulls (`plan_packed_rows`):
# the apply's own dedup and routing of the ids, and ONE sorted gather of the
# unique packed rows at the apply's rung. The pull expands the weight columns
# to positions from that small array (by `uniq.inverse`: 0.16 ms for the
# 106,496 positions on the v5e, 1.5 ns each against a table gather's 28) and
# the apply
# (`sparse_apply_packed_table(plan=)`) takes the plan's rows, order and
# segments: it neither dedups nor gathers again. Same values in the same
# places, so the table after a scan is the plan-less one bit for bit.
# - The gather sits under the ladder's conditional and hands back rows padded
#   to n so every branch has one shape; the apply's branch slices `[:W]`. A
#   position whose id is invalid maps to a slot past the valid prefix, whose
#   row reads 0 (out of bounds, or padding), as the per-position pull gave it.
# - The owner side of the exchange plans the same way over the slots it
#   received (`parallel/sharded.py` "THE OWNER PLANS ONCE A STEP": the slots
#   to leave out at row -1 at the serve, the multiplicities at the apply).
#   A caller with no pull to share with (a hash table's apply, a
#   pipelined step whose rows were served a step early) passes no plan and
#   gets the program it had.
# ---------------------------------------------------------------------------


class PackedPlan(NamedTuple):
    """What a packed table's step knows of its ids before the pull, kept for
    the apply (`plan_packed_rows`)."""

    uniq: UniqueResult   # the dedup under the apply's routing (`_route_unique`)
    counts: jax.Array    # (n,) int32: what each unique slot sums of the
    #                      plan's `pre_counts`; 0 = sentinel or padding slot
    idx: jax.Array       # (n,) the row a slot stands for (`_unique_slots`;
    #                      line form: its line-major id, `_line_major`)
    rows: jax.Array      # (n, width) f32: the packed rows of `idx`, gathered
    #                      at the step's rung of `apply_ladder`, 0 past it
    #                      (line form: (n, 32), the padding columns 0)
    lines: jax.Array = None  # line form: (n, 128), the lines of `idx >> 2`
    #                          that `rows` were picked from


def plan_packed_rows(packed: jax.Array, row_ids: jax.Array,
                     pre_counts: jax.Array = None, *, width: int
                     ) -> PackedPlan:
    """Dedup and route `row_ids` as `sparse_apply_packed_table` would and
    gather the unique packed rows once, over the smallest rung of the ladder
    that holds them ("ONE DEDUP AND ONE TABLE GATHER A STEP" above).
    `pre_counts` as the apply's: ones by default, 0 = a position to leave
    out; one that only says WHICH positions count (0 / 1) will do where the
    apply brings the multiplicities. `width`: the layout's columns
    (`packed_width`), by which a table in the line form is known and its
    LINES are gathered."""
    n = row_ids.shape[0]
    n_rows = packed_rows(packed, width)
    lined = in_lines(packed, width)
    if lined:
        row_ids = _line_major(packed, row_ids)
    uniq = _route_unique(n_rows, row_ids, pre_counts)
    with _trace.scope("sparse", "reduce"):
        counts, idx = _unique_slots(n_rows, uniq, pre_counts)

    def gather(W, settle):
        del settle  # nothing is written: the table is read and handed on
        got = (_gather_rows(packed, _line(idx[:W]), ascending=True) if lined
               else _gather_rows(packed, idx[:W], sorted_unique=True))
        return jnp.pad(got, ((0, n - W), (0, 0)))

    rows, _ = _over_unique_prefix(counts, packed, gather)
    lines = None
    if lined:  # one pass over the switch's output, whatever the rung
        lines, rows = rows, _pick_rows(rows, idx)
    # the pull slices the weight columns out of `rows`: left alone the
    # compiler sinks that slice into the branches, a conditional's outputs
    # live in HBM, and the expansion from an HBM array of 10 lanes padded to
    # 128 read 0.885 ms on the v5e against 0.161 from fast memory (probe, PR 35)
    return PackedPlan(uniq, counts, idx, jax.lax.optimization_barrier(rows),
                      lines)


def sparse_apply_packed_table(
    optimizer,
    packed: jax.Array,
    layout,
    dim: int,
    row_ids: jax.Array,
    grads: jax.Array,
    pre_counts: jax.Array = None,
    *,
    plan: PackedPlan = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """`sparse_apply_dense_table` over the packed layout: identical dedup and
    optimizer math, ONE gather + ONE scatter instead of one pair per array.
    The scan's form, so always -> (packed, the step's load); `packed` in
    either form (`in_lines`), the line form gathering, merging and writing
    LINES ("FOUR ROWS A LANE LINE" above).

    `plan`: what `plan_packed_rows` made of the SAME `row_ids` against this
    `packed`, unwritten since: the apply then sums over the plan's segments
    and updates the plan's rows, with no dedup and no gather of its own.
    `pre_counts` given beside a plan are summed anew over its segments (they
    must be positive exactly where the plan's were)."""
    with _trace.scope("sparse", "apply"):
        width = packed_width(dim, layout)
        lined = in_lines(packed, width)
        if plan is None:
            g, counts, idx = _dedup_routed(
                packed_rows(packed, width),
                _line_major(packed, row_ids) if lined else row_ids, grads,
                pre_counts)
        else:
            with _trace.scope("sparse", "reduce"):
                g = plan.uniq.segment_reduce(grads)
                counts, idx = plan.counts, plan.idx
                if pre_counts is not None:
                    counts = jnp.where(counts > 0,
                                       plan.uniq.segment_reduce(pre_counts), 0)

        n = idx.shape[0]
        # row DMAs take any number of slots at one price (padding starts
        # nothing), and a kernel a rung is four to trace and lower: the rungs
        # hand their new rows on, padded to n as the plan's gather pads its
        # own, and ONE scatter follows the switch
        after = takes_row_dmas(packed)

        def tail(W, settle):
            lines = None
            if plan is not None:
                rows = plan.rows[:W]
            elif lined:
                rows, lines = _line_rows(packed, idx[:W], ascending=True)
            else:
                rows = _gather_rows(packed, idx[:W], sorted_unique=True)
            s_rows = {}
            off = dim
            for name, w in layout:
                s_rows[name] = rows[:, off:off + w]
                off += w
            new_w, new_s = optimizer.apply(rows[:, :dim], s_rows,
                                           g[:W].astype(jnp.float32), counts[:W])
            new_rows = jnp.concatenate(
                [new_w] + [new_s[name] for name, _ in layout],
                axis=1).astype(packed.dtype)
            if after:  # with the lines a rung gathered for itself, if any
                return jax.tree_util.tree_map(
                    lambda x: jnp.pad(x, ((0, n - W), (0, 0))),
                    (new_rows, lines))
            table, new_rows = settle((packed, new_rows))
            return scatter_rows(table, idx[:W], new_rows, sorted_unique=True)

        out, load = _over_unique_prefix(counts, packed, tail)
        if after:
            out, lines = out
            if lined:
                # ONE merge, after the switch: a pass over n slots whatever
                # the rung, where a merge a rung was four to trace and lower
                out = _merge_lines(
                    plan.lines if plan is not None else lines, idx,
                    jnp.pad(out, ((0, 0), (0, LINE_STRIDE - width))))
                packed, out = jax.lax.optimization_barrier((packed, out))
            out = scatter_rows(packed, _line(idx) if lined else idx, out,
                               sorted_unique=True, runs=lined)
            load["line_mates"] = (_line_mates(counts, idx) if lined
                                  else jnp.zeros((), jnp.float32))
        return out, load


def _line_mates(counts: jax.Array, idx: jax.Array) -> jax.Array:
    """Of the valid unique rows (`counts > 0`; `idx` line-major, ascending),
    the share whose line holds another of them: its neighbour in the
    buffer."""
    ln = _line(idx)
    same = ln[1:] == ln[:-1]
    no = jnp.zeros((1,), bool)
    mate = (counts > 0) & (jnp.concatenate([no, same])
                           | jnp.concatenate([same, no]))
    return jnp.sum(mate, dtype=jnp.float32) / jnp.maximum(
        jnp.sum(counts > 0, dtype=jnp.float32), 1)


def sparse_apply_dense_table(
    optimizer,
    weights: jax.Array,
    slots: Dict[str, jax.Array],
    row_ids: jax.Array,
    grads: jax.Array,
    pre_counts: jax.Array = None,
    *,
    with_load: bool = False,
):
    """Fused sparse update of a dense (array) table shard -> (weights, slots),
    with `with_load` -> (weights, slots, the step's load: "WHAT THE APPLY
    WORKS OVER" above).

    row_ids: (n,) local row indices (may contain duplicates and padding);
    grads: (n, dim) per-occurrence gradients; pre_counts: (n,) multiplicity already
    accumulated upstream (e.g. summed over workers), default 1 per occurrence, 0 = pad.

    Pipeline (reference `update_weights`, `EmbeddingOptimizerVariable.h:273-297`):
    dedup -> sum gradients/counts over duplicates -> gather rows+slots -> fused
    optimizer apply -> scatter back. Rows not touched stay bit-identical.
    """
    with _trace.scope("sparse", "apply"):
        g, counts, idx = _dedup_routed(weights.shape[0], row_ids, grads, pre_counts)

        def tail(W, settle):
            idx_w, g_w, counts_w = idx[:W], g[:W], counts[:W]
            # Optimizer math always runs in float32, whatever the table dtype: in bf16,
            # beta_2^t rounds to 1.0 (killing Adam's lr_t) and g^2 accumulators lose most of
            # their mantissa. Slots are stored f32 (`SparseOptimizer.init_slots`); weights are
            # upcast for the update and cast back on scatter (TPU-idiomatic mixed precision).
            w_rows = _gather_rows(weights, idx_w,
                                  sorted_unique=True).astype(jnp.float32)
            s_rows = {k: _gather_rows(v, idx_w, sorted_unique=True)
                      for k, v in slots.items()}
            (w, s), (new_w, new_s) = settle(((weights, slots), optimizer.apply(
                w_rows, s_rows, g_w.astype(jnp.float32), counts_w)))
            # idx is fully routed (invalid -> distinct OOB rows): valid=None
            return (scatter_rows(w, idx_w, new_w.astype(w.dtype),
                                 sorted_unique=True),
                    {k: scatter_rows(s[k], idx_w, new_s[k].astype(s[k].dtype),
                                     sorted_unique=True)
                     for k in s})

        (weights, slots), load = _over_unique_prefix(
            counts, (weights, slots), tail)
        return (weights, slots, load) if with_load else (weights, slots)
