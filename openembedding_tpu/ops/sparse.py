"""Single-shard sparse gather / scatter / fused-optimizer-apply.

Counterpart of the reference's server-side hot path on one shard:
`EmbeddingOptimizerVariable::pull_weights` (table read, `EmbeddingOptimizerVariable.h:
242-266`) and `update_weights` (commit + reduce + per-unique-row optimizer update,
`:273-297`). Here a "shard" is just the rows of the table a device owns; the ops are
plain XLA (Pallas variants live in `ops/pallas_*.py`).

Scatter correctness under static shapes: padding slots of the unique-id buffer are
scattered with out-of-bounds indices and `mode='drop'`, so they can never corrupt row 0.
"""

from __future__ import annotations

import os
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..utils import trace as _trace
from .dedup import unique_with_counts


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


def _gather_rows(weights, rows, valid=None, *, sorted_unique=False):
    """`lookup_rows` without its stage name: the fused applies read the rows
    they update through this, so that read counts under `sparse.apply`."""
    if weights.ndim == 2 and rows.ndim == 1:
        from .pallas_sparse import maybe_gather_rows
        out = maybe_gather_rows(weights, rows, valid)
        if out is not None:
            return out
    n_rows = weights.shape[0]
    in_range = (rows >= 0) & (rows < n_rows)
    if valid is not None:
        in_range = in_range & valid
    # fill-mode gather: positive out-of-bounds indices read 0 WITHOUT clipping
    # (clipping would collapse distinct OOB sentinels onto row n_rows-1 and break
    # the unique_indices promise); negative indices wrap in jax, so the explicit
    # in_range mask below still zeroes those
    out = weights.at[rows].get(mode="fill", fill_value=0,
                               indices_are_sorted=sorted_unique,
                               unique_indices=sorted_unique)
    return jnp.where(in_range.reshape(in_range.shape + (1,) * (out.ndim - in_range.ndim)),
                     out, jnp.zeros_like(out))


def scatter_rows(weights: jax.Array, rows: jax.Array, values: jax.Array,
                 valid: jax.Array = None, *, sorted_unique: bool = False
                 ) -> jax.Array:
    """Overwrite rows; invalid slots are dropped via out-of-bounds scatter.

    `valid=None` means `rows` is already fully routed (invalid entries already
    carry out-of-bounds indices). `sorted_unique`: rows genuinely ascending and
    duplicate-free — TPU scatters serialize without these hints; this is the
    difference between a vectorized update and a 106k-iteration row loop (see
    tools/step_bisect.py measurements)."""
    n_rows = weights.shape[0]
    if valid is None:
        target = rows
    else:
        target = jnp.where(valid, rows, n_rows)  # out of bounds -> dropped
    return weights.at[target].set(values, mode="drop",
                                  indices_are_sorted=sorted_unique,
                                  unique_indices=sorted_unique)


# ---------------------------------------------------------------------------
# packed table layout (weights + optimizer slots in ONE array)
# ---------------------------------------------------------------------------
#
# The fused apply is HBM-LATENCY-bound: each gather/scatter pair over k unique
# rows costs ~147 ns/row regardless of row width (PERF.md). Storing weights
# and slots separately pays one pair PER ARRAY (Adagrad: 2 pairs = ~27 ms for
# 106k rows on v5e); concatenating them column-wise into one (rows, dim+Σslot)
# array pays ONE pair (~19 ms measured, 1.44x). The packed form only exists
# inside `Trainer.train_many`'s scan (pack at entry, unpack at exit, amortized
# over K steps) so checkpoints, serving, offload and the sharded protocol all
# keep the split layout.
#
# Width gate: XLA's gather for 32 < width < 128 materializes a 128-lane-padded
# 2.0x temp copy of the WHOLE table every scan iteration (measured via
# compiled.memory_analysis(); PERF.md "dim-64 single-chip HBM budget"), so
# packing only engages when the packed width stays in the sublane-packed
# regime (<= 32) or is lane-exact (% 128 == 0).

PACKED_MAX_SUBLANE_WIDTH = 32
# pack/unpack at the scan boundary transiently holds BOTH layouts (~2x the
# packed bytes); tables whose packed form exceeds this skip packing so the
# boundary cannot OOM a chip whose steady state fits. Override (bytes, per
# shard) via OETPU_PACKED_MAX_BYTES for bigger-HBM parts.
PACKED_MAX_BYTES = int(os.environ.get("OETPU_PACKED_MAX_BYTES",
                                      str(4 << 30)))


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


def pack_table(weights: jax.Array, slots: Dict[str, jax.Array],
               layout) -> jax.Array:
    """-> (rows, dim+Σwidths) f32; column order: weights, then layout order."""
    with _trace.scope("sparse", "pack"):
        return jnp.concatenate(
            [weights.astype(jnp.float32)] + [slots[name] for name, _ in layout],
            axis=1)


def unpack_table(packed: jax.Array, layout, dim: int, weights_dtype
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    with _trace.scope("sparse", "unpack"):
        weights = packed[:, :dim].astype(weights_dtype)
        slots = {}
        off = dim
        for name, w in layout:
            slots[name] = packed[:, off:off + w]
            off += w
        return weights, slots


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
      the vectorized gather/scatter instead of a serialized row loop (the
      difference between 25 ms and sub-ms on v5e; tools/step_bisect.py)."""
    n = row_ids.shape[0]
    if pre_counts is None:
        pre_counts = jnp.ones((n,), jnp.int32)
    uniq = unique_with_counts(jnp.where((pre_counts > 0) & (row_ids >= 0),
                                        row_ids, n_rows))
    # the sums over duplicates get a name of their own: on the owner side of
    # the exchange they run over S times the positions
    with _trace.scope("sparse", "reduce"):
        g = uniq.segment_reduce(grads)
        counts = uniq.segment_reduce(pre_counts)
        counts = jnp.where(uniq.unique_ids < n_rows, counts, 0)
        idx = jnp.where(counts > 0, uniq.unique_ids,
                        n_rows + jnp.arange(n, dtype=uniq.unique_ids.dtype))
    return g, counts, idx


def sparse_apply_packed_table(
    optimizer,
    packed: jax.Array,
    layout,
    dim: int,
    row_ids: jax.Array,
    grads: jax.Array,
    pre_counts: jax.Array = None,
) -> jax.Array:
    """`sparse_apply_dense_table` over the packed layout: identical dedup and
    optimizer math, ONE gather + ONE scatter instead of one pair per array."""
    with _trace.scope("sparse", "apply"):
        g, counts, idx = _dedup_routed(packed.shape[0], row_ids, grads, pre_counts)
        rows = _gather_rows(packed, idx, sorted_unique=True)  # (n, W) f32
        s_rows = {}
        off = dim
        for name, w in layout:
            s_rows[name] = rows[:, off:off + w]
            off += w
        new_w, new_s = optimizer.apply(rows[:, :dim], s_rows,
                                       g.astype(jnp.float32), counts)
        new_rows = jnp.concatenate(
            [new_w] + [new_s[name] for name, _ in layout], axis=1)
        return scatter_rows(packed, idx, new_rows.astype(packed.dtype),
                            sorted_unique=True)


def sparse_apply_dense_table(
    optimizer,
    weights: jax.Array,
    slots: Dict[str, jax.Array],
    row_ids: jax.Array,
    grads: jax.Array,
    pre_counts: jax.Array = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused sparse update of a dense (array) table shard.

    row_ids: (n,) local row indices (may contain duplicates and padding);
    grads: (n, dim) per-occurrence gradients; pre_counts: (n,) multiplicity already
    accumulated upstream (e.g. summed over workers), default 1 per occurrence, 0 = pad.

    Pipeline (reference `update_weights`, `EmbeddingOptimizerVariable.h:273-297`):
    dedup -> sum gradients/counts over duplicates -> gather rows+slots -> fused
    optimizer apply -> scatter back. Rows not touched stay bit-identical.
    """
    with _trace.scope("sparse", "apply"):
        g, counts, idx = _dedup_routed(weights.shape[0], row_ids, grads, pre_counts)

        from .pallas_sparse import maybe_fused_apply
        fused = maybe_fused_apply(optimizer, weights, slots, idx, g, counts)
        if fused is not None:
            return fused

        # Optimizer math always runs in float32, whatever the table dtype: in bf16,
        # beta_2^t rounds to 1.0 (killing Adam's lr_t) and g^2 accumulators lose most of
        # their mantissa. Slots are stored f32 (`SparseOptimizer.init_slots`); weights are
        # upcast for the update and cast back on scatter (TPU-idiomatic mixed precision).
        w_rows = _gather_rows(weights, idx, sorted_unique=True).astype(jnp.float32)
        s_rows = {k: _gather_rows(v, idx, sorted_unique=True)
                  for k, v in slots.items()}
        new_w, new_s = optimizer.apply(w_rows, s_rows, g.astype(jnp.float32), counts)
        # idx is fully routed (invalid -> distinct OOB rows): valid=None
        weights = scatter_rows(weights, idx, new_w.astype(weights.dtype),
                               sorted_unique=True)
        slots = {k: scatter_rows(slots[k], idx,
                                 new_s[k].astype(slots[k].dtype),
                                 sorted_unique=True)
                 for k in slots}
        return weights, slots
