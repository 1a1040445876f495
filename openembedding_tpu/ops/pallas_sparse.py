"""Pallas TPU kernels for the embedding hot path (SURVEY.md §7 step 4).

Counterpart of the reference's server-side hot loops — the table read of
`EmbeddingOptimizerVariable::pull_weights` (`variable/EmbeddingOptimizerVariable.h:
242-266`) and the commit+reduce+update of `update_weights` (`:273-297`,
`variable/EmbeddingOptimizer.h`) — done the TPU way: the table stays in HBM and rows
stream through VMEM via explicit async DMAs instead of XLA's generic gather/scatter.

Two kernels:

- `gather_rows`: B row-DMAs in flight per grid step (memory-level parallelism against
  HBM latency), then one vectorized copy to the output block.
- `fused_sparse_apply`: ONE pass over HBM per unique row — loads the weight row and
  every optimizer slot row, runs the fused optimizer update on the whole block in VMEM,
  and DMAs the results back in place (`input_output_aliases`). The XLA fallback
  (`ops/sparse.py`) instead issues a separate gather + scatter per slot array, i.e.
  2*(1+num_slots) HBM sweeps of the touched rows plus intermediate buffers.

Safety contract (both kernels): row indices may contain padding/invalid entries.
Loads are always issued with the index clamped into range (harmless read); stores are
predicated per-row on `counts > 0`, and callers guarantee `counts > 0` implies a valid,
globally-unique row (the dedup in `ops/sparse.py::sparse_apply_dense_table` provides
uniqueness), so no write ever races another row's write.

MEASURED, and what the later records say. `tools/pallas_microbench.py`
(2026-07, before any line of `PERF_LEDGER.jsonl`) read XLA's gather at 1.9G
rows/s and per-row-DMA Pallas at about 16M rows/s, and concluded that the XLA
path is the fast path in both directions. The ledger disagrees for the
SCATTER: against the 2^22 x 128 packed table XLA's sorted gather issues a slot
every 9 ns, its sorted, unique scatter a valid row every 93 ns (ledger, PR 40,
`deepfm64.train_zipf`), and a store-only kernel with a block's row DMAs in
flight writes one every 16 ns alone and every 9 in that cell's scan (my chip
runs, PR 41): `ops/pallas_scatter.py`, which `ops/sparse.scatter_rows`
chooses from the table's shape, no switch.
The kernels HERE stay DEFAULT OFF behind `OETPU_PALLAS=on`: the ring of
`SEM_RING` = 8 buys nothing over one semaphore (42.5 ns a row with a ring of 8
and of 32 alike, my chip run, PR 41: the per-slot wait and branch on the scalar
core bound it, not the DMAs in flight), Mosaic takes a one-row slice of an HBM
array only at width exactly 128 of a 4-byte dtype (PERF.md section 6, PR 41),
and `fused_sparse_apply` halted the core at 2^21 x (128 + 128) with 79,872
slots (my chip run, PR 41, PERF.md section 7: Design 5's reading).

Mode control: `set_mode("off"|"on"|"interpret")`, env `OETPU_PALLAS`.
"interpret" runs the Pallas interpreter (CPU tests, `tests/test_pallas.py`).
"""

from __future__ import annotations

import functools
import importlib
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


class _AtFirstUse:
    """A module imported when a kernel first reaches into it: Pallas is 0.5-0.9
    s of import, and `ops/sparse.py` imports THIS module in every program that
    gathers a row, for `maybe_gather_rows` to say "off"."""

    def __init__(self, name):
        self._name = name

    def __getattr__(self, attr):
        return getattr(importlib.import_module(self._name), attr)


pl = _AtFirstUse("jax.experimental.pallas")
pltpu = _AtFirstUse("jax.experimental.pallas.tpu")

_VALID_MODES = ("auto", "on", "off", "interpret")


def _env_mode() -> str:
    v = os.environ.get("OETPU_PALLAS", "off")
    if v not in _VALID_MODES:
        import warnings
        warnings.warn(
            f"OETPU_PALLAS={v!r} is not one of {_VALID_MODES}; defaulting to "
            "'off' (use 'on' to enable the Pallas kernels)", RuntimeWarning)
        return "off"
    return v


_MODE = _env_mode()

DEFAULT_BLOCK = 256
# DMA semaphores are a scarce scoped resource (a (2, 256) sem array blew the 2 KB
# sflag budget on v5e); in-flight row DMAs are bounded by a small ring instead.
SEM_RING = 8


def set_mode(mode: str) -> None:
    """"off" (default — XLA path, measured faster), "on", or "interpret"."""
    global _MODE
    if mode not in _VALID_MODES:
        raise ValueError(f"bad pallas mode {mode!r}")
    _MODE = mode


def get_mode() -> str:
    return _MODE


def _resolve() -> Tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if _MODE in ("off", "auto"):  # auto == off: XLA measured faster (module doc)
        return False, False
    if _MODE == "interpret":
        # oelint: disable=trace-hazard -- default_backend() is a host string, not a tracer
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "OETPU_PALLAS=interpret on a TPU backend: the interpreter is "
                "the CPU test mode. Use 'on' (compiled kernels) or 'off'.")
        return True, True
    return True, False


# ---------------------------------------------------------------------------
# gather_rows
# ---------------------------------------------------------------------------


def _gather_kernel(rows_smem, w_hbm, out_ref, scratch, sems, *, block, n_rows):
    """SEM_RING row-DMAs in flight; slot i reuses semaphore i % SEM_RING after
    waiting out its previous occupant."""
    g = pl.program_id(0)

    def copy(i):
        row = rows_smem[g * block + i]
        safe = jnp.clip(row, 0, n_rows - 1)
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(safe, 1), :], scratch.at[pl.ds(i, 1), :],
            sems.at[jax.lax.rem(i, SEM_RING)])

    def start(i, _):
        @pl.when(i >= SEM_RING)
        def _():
            copy(i - SEM_RING).wait()
        copy(i).start()
        return 0

    jax.lax.fori_loop(0, block, start, 0)

    def drain(i, _):
        copy(i).wait()
        return 0

    jax.lax.fori_loop(max(0, block - SEM_RING), block, drain, 0)
    out_ref[:] = scratch[:]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _gather_call(weights, padded_rows, *, block, interpret):
    n_rows, dim = weights.shape
    nb = padded_rows.shape[0] // block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, dim), lambda g, rows: (g, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((block, dim), weights.dtype),
            pltpu.SemaphoreType.DMA((SEM_RING,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, block=block, n_rows=n_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded_rows.shape[0], dim), weights.dtype),
        interpret=interpret,
    )(padded_rows, weights)


def gather_rows(weights: jax.Array, rows: jax.Array,
                valid: Optional[jax.Array] = None, *,
                block: int = DEFAULT_BLOCK,
                interpret: bool = False) -> jax.Array:
    """Pallas `lookup_rows`: out-of-range/invalid rows return zeros."""
    n_rows, _ = weights.shape
    flat = rows.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    block = min(block, max(8, n))
    npad = -(-n // block) * block
    padded = jnp.full((npad,), -1, jnp.int32).at[:n].set(flat)
    out = _gather_call(weights, padded, block=block, interpret=interpret)[:n]
    in_range = (flat >= 0) & (flat < n_rows)
    if valid is not None:
        in_range = in_range & valid.reshape(-1)
    return jnp.where(in_range[:, None], out, jnp.zeros_like(out))


def _require_lane_aligned(kernel: str, rows: int, *widths: int) -> None:
    """Mosaic constraint: per-row HBM DMA slices must cover whole 128-lane tiles, so
    the compiled kernels only take row widths that are multiples of 128. "on"
    means on: an unaligned table (the reference's dim 9/64 benchmarks) is an
    error, never a silent switch to the XLA path."""
    if any(w % 128 for w in widths):
        raise ValueError(
            f"OETPU_PALLAS=on: {kernel} got a {rows}-row table with row "
            f"width(s) {list(widths)}; the compiled kernels need every width "
            "to be a multiple of 128 lanes. Set OETPU_PALLAS=off (the XLA "
            "path, the default) for this model.")


def maybe_gather_rows(weights, rows, valid=None):
    """Dispatch hook for `ops.sparse.lookup_rows`; None = use the XLA path
    (mode off only)."""
    use, interpret = _resolve()
    if not use:
        return None
    if not interpret:
        _require_lane_aligned("gather_rows", weights.shape[0],
                              *weights.shape[1:])
    return gather_rows(weights, rows, valid, interpret=interpret)


# ---------------------------------------------------------------------------
# gather_rows_windows — PERF.md lever #1: multi-row DMA batching
# ---------------------------------------------------------------------------
#
# The per-row kernel above is descriptor-issue-bound (~300 ns/row from the
# scalar core vs XLA's 147 ns/row serialized gather). This variant amortizes
# descriptor issue over WINDOWS of `window` consecutive table rows on a fixed
# grid (window w = table rows [w*W, (w+1)*W)): a prepass buckets the (sorted)
# requested rows by window, the kernel DMAs each DISTINCT window once, and the
# per-row step is a VMEM->VMEM copy (a few cycles, no descriptor).
#
# Issue count per block = #distinct windows, so the win scales with row
# DENSITY: frequency-relabeled Criteo ids (the reference's own preprocessor
# relabels by frequency, `test/criteo_preprocess.cpp`) concentrate unique rows
# in the hot low-id region -> many rows share a window. Worst case (uniform
# hashed ids over 2^24 rows) degenerates to one window per row = per-row DMA
# of W rows: bandwidth still fine (W*row_bytes per descriptor), issue count no
# worse than the per-row kernel. Extra HBM traffic is bounded by W * n rows.
#
# MEASURED 2026-07-30 (v5e, scan-fenced, dim 128, 2^21 rows, 106k pulls —
# PERF.md "On-chip verdict"): REFUTED. XLA gather 2.5-5.0 ms; this kernel
# 18-20 ms at W in {16, 64}, both densities. The DMA amortization works but
# the per-row VMEM emit loop below is a serial scalar-core fori_loop at
# ~170 ns/row — more than the entire XLA gather. Kept as a documented
# negative result; default-off like the rest of the module.


def _window_gather_kernel(bases, nw_arr, slotoff, w_hbm, out_ref, scratch,
                          sems, *, block, nwin, window, n_rows):
    """Prefetched scalars: bases (nb*nwin,), nw (nb,), slotoff (nb*block,).
    Per grid step: DMA the block's distinct windows (predicated on the real
    count), then copy each requested row out of its window's VMEM slot."""
    g = pl.program_id(0)
    nw = nw_arr[g]

    def copy(i):
        base = bases[g * nwin + i]
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(base, window), :],
            scratch.at[pl.ds(i * window, window), :],
            sems.at[jax.lax.rem(i, SEM_RING)])

    def drain(i, _):
        @pl.when(i < nw)
        def _():
            copy(i).wait()
        return 0

    # ring waits only for slots whose DMA really started (i - SEM_RING < nw)
    def start_pred(i, _):
        @pl.when((i >= SEM_RING) & (i - SEM_RING < nw))
        def _():
            copy(i - SEM_RING).wait()

        @pl.when(i < nw)
        def _():
            copy(i).start()
        return 0

    jax.lax.fori_loop(0, nwin, start_pred, 0)
    jax.lax.fori_loop(max(0, nwin - SEM_RING), nwin, drain, 0)

    # per-row VMEM copy: out[i] = scratch[slot*W + off] (no descriptors)
    def emit(i, _):
        so = slotoff[g * block + i]
        out_ref[pl.ds(i, 1), :] = scratch[pl.ds(so, 1), :]
        return 0

    jax.lax.fori_loop(0, block, emit, 0)


@functools.partial(jax.jit, static_argnames=("block", "window", "interpret"))
def _window_gather_call(weights, bases, nw, slotoff, *, block, window,
                        interpret):
    n_rows, dim = weights.shape
    nb = nw.shape[0]
    nwin = bases.shape[0] // nb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, dim), lambda g, *_: (g, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((nwin * window, dim), weights.dtype),
            pltpu.SemaphoreType.DMA((SEM_RING,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_window_gather_kernel, block=block, nwin=nwin,
                          window=window, n_rows=n_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * block, dim), weights.dtype),
        interpret=interpret,
    )(bases, nw, slotoff, weights)


def gather_rows_windows(weights: jax.Array, rows: jax.Array, *,
                        block: int = DEFAULT_BLOCK, window: int = 16,
                        interpret: bool = False) -> jax.Array:
    """Window-batched Pallas gather. `rows` SHOULD be sorted ascending for the
    win (dedup outputs are); correctness holds for any order. Out-of-range
    rows return zeros."""
    n_rows, dim = weights.shape
    if n_rows < window:  # a window would span the whole table; per-row path
        return gather_rows(weights, rows, block=block, interpret=interpret)
    flat = rows.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    if n == 0:
        return jnp.zeros((0, dim), weights.dtype)
    block = min(block, max(8, n))
    npad = -(-n // block) * block
    # padding reuses the LAST row's window so it adds no extra DMA
    pad_val = jnp.clip(flat[-1], 0, n_rows - 1)
    padded = jnp.full((npad,), pad_val, jnp.int32).at[:n].set(
        jnp.clip(flat, 0, n_rows - 1))
    nb = npad // block
    per = padded.reshape(nb, block)
    wid = per // window                       # fixed-grid window per row
    # block-local distinct windows: sorted rows -> adjacent compare; padding
    # slots replicate the last real window
    swid = jnp.sort(wid, axis=1)
    is_new = jnp.concatenate(
        [jnp.ones((nb, 1), bool), swid[:, 1:] != swid[:, :-1]], axis=1)
    slot_of_sorted = jnp.cumsum(is_new, axis=1) - 1   # (nb, block)
    nw = (slot_of_sorted[:, -1] + 1).astype(jnp.int32)
    nwin = block  # worst case: every row its own window
    # window base rows, clamped so base+window never reads past the table
    # (the last partial window shifts down; offsets are computed against the
    # clamped base)
    def wbase(w):
        return jnp.minimum(w * window, n_rows - window).astype(jnp.int32)
    # bases[slot] = clamped base; scatter sorted windows into slots
    bases = jnp.zeros((nb, nwin), jnp.int32)
    bases = jax.vmap(lambda b, s, w: b.at[s].set(wbase(w)))(
        bases, slot_of_sorted, swid)
    # per original row: its slot = slot of its window (searchsorted into the
    # sorted distinct windows of its block)
    def row_slots(swid_b, slot_b, wid_b):
        pos = jnp.searchsorted(swid_b, wid_b)
        return slot_b[jnp.clip(pos, 0, block - 1)]
    slot = jax.vmap(row_slots)(swid, slot_of_sorted, wid)
    off = per - wbase(wid)
    slotoff = (slot * window + off).astype(jnp.int32).reshape(-1)

    out = _window_gather_call(
        weights, bases.reshape(-1), nw, slotoff,
        block=block, window=window, interpret=interpret)[:n]
    in_range = (flat >= 0) & (flat < n_rows)
    return jnp.where(in_range[:, None], out, jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# fused_sparse_apply
# ---------------------------------------------------------------------------


def _apply_kernel(optimizer, slot_names, table_dtype, block, n_rows, *refs):
    """refs = (rows_smem, grads, counts, w_in, *s_in, w_out, *s_out,
               scr_w, *scr_s, sems)."""
    k = len(slot_names)
    rows_smem, grads_ref, counts_ref = refs[0], refs[1], refs[2]
    # refs[3 : 4+k] are the aliased inputs (unused — we read via the out refs,
    # which share their buffers)
    outs = list(refs[4 + k: 5 + 2 * k])      # w_out, *s_out
    scrs = list(refs[5 + 2 * k: 6 + 3 * k])  # scr_w, *scr_s
    sems = refs[6 + 3 * k]                   # DMA sems, shape (1+k, SEM_RING)
    g = pl.program_id(0)

    def copies(i, inward):
        row = rows_smem[g * block + i]
        safe = jnp.clip(row, 0, n_rows - 1)
        dmas = []
        for j, (buf, scr) in enumerate(zip(outs, scrs)):
            hbm = buf.at[pl.ds(safe, 1), :]
            vmem = scr.at[pl.ds(i, 1), :]
            src, dst = (hbm, vmem) if inward else (vmem, hbm)
            dmas.append(pltpu.make_async_copy(
                src, dst, sems.at[j, jax.lax.rem(i, SEM_RING)]))
        return dmas

    # phase 1: load weight row + every slot row, SEM_RING rows in flight
    def start_load(i, _):
        @pl.when(i >= SEM_RING)
        def _():
            for dma in copies(i - SEM_RING, True):
                dma.wait()
        for dma in copies(i, True):
            dma.start()
        return 0

    def drain_load(i, _):
        for dma in copies(i, True):
            dma.wait()
        return 0

    jax.lax.fori_loop(0, block, start_load, 0)
    jax.lax.fori_loop(max(0, block - SEM_RING), block, drain_load, 0)

    # phase 2: fused optimizer update on the whole block (VPU, f32 math)
    counts = counts_ref[:, 0]
    slots = {name: scrs[1 + j][:] for j, name in enumerate(slot_names)}
    new_w, new_slots = optimizer.apply(
        scrs[0][:].astype(jnp.float32), slots,
        grads_ref[:].astype(jnp.float32), counts)
    scrs[0][:] = new_w.astype(table_dtype)
    for j, name in enumerate(slot_names):
        scrs[1 + j][:] = new_slots[name]

    # phase 3: store back — predicated on counts > 0 (padding rows never write);
    # ring waits are predicated on the SAME row's count so we never wait a DMA
    # that was never started
    def start_store(i, _):
        @pl.when((i >= SEM_RING) & (counts_ref[i - SEM_RING, 0] > 0))
        def _():
            for dma in copies(i - SEM_RING, False):
                dma.wait()

        @pl.when(counts_ref[i, 0] > 0)
        def _():
            for dma in copies(i, False):
                dma.start()
        return 0

    def drain_store(i, _):
        @pl.when(counts_ref[i, 0] > 0)
        def _():
            for dma in copies(i, False):
                dma.wait()
        return 0

    jax.lax.fori_loop(0, block, start_store, 0)
    jax.lax.fori_loop(max(0, block - SEM_RING), block, drain_store, 0)


@functools.partial(jax.jit,
                   static_argnames=("optimizer", "slot_names", "block", "interpret"))
def _apply_call(optimizer, slot_names, weights, slot_list, rows, grads, counts,
                *, block, interpret):
    n_rows, dim = weights.shape
    npad = rows.shape[0]
    nb = npad // block
    k = len(slot_names)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block, dim), lambda g, rows: (g, 0),
                         memory_space=pltpu.VMEM),          # grads
            pl.BlockSpec((block, 1), lambda g, rows: (g, 0),
                         memory_space=pltpu.VMEM),          # counts
            any_spec,                                       # weights (aliased)
        ] + [any_spec] * k,                                 # slots (aliased)
        out_specs=[any_spec] * (1 + k),
        scratch_shapes=[
            pltpu.VMEM((block, dim), weights.dtype),
        ] + [
            pltpu.VMEM((block, s.shape[1]), s.dtype) for s in slot_list
        ] + [
            pltpu.SemaphoreType.DMA((1 + k, SEM_RING)),
        ],
    )
    out_shape = [jax.ShapeDtypeStruct(weights.shape, weights.dtype)] + [
        jax.ShapeDtypeStruct(s.shape, s.dtype) for s in slot_list]
    # inputs flatten as (rows, grads, counts, weights, *slots): alias the tables
    # onto the outputs so the update happens in place in HBM
    aliases = {3 + j: j for j in range(1 + k)}
    outs = pl.pallas_call(
        functools.partial(_apply_kernel, optimizer, slot_names, weights.dtype,
                          block, n_rows),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(rows, grads, counts, weights, *slot_list)
    return outs[0], list(outs[1:])


def fused_sparse_apply(optimizer, weights: jax.Array, slots: Dict[str, jax.Array],
                       rows: jax.Array, grads: jax.Array, counts: jax.Array, *,
                       block: int = DEFAULT_BLOCK, interpret: bool = False
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused dedup-free sparse update: `rows` must be unique where counts > 0
    (callers dedup first); counts == 0 marks padding. One HBM read + write per
    touched (row, array) pair."""
    n_rows, dim = weights.shape
    flat = rows.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    counts = counts.reshape(-1).astype(jnp.int32)
    counts = jnp.where((flat >= 0) & (flat < n_rows), counts, 0)
    grads = grads.reshape(n, dim)

    block = min(block, max(8, n))
    npad = -(-n // block) * block
    p_rows = jnp.full((npad,), -1, jnp.int32).at[:n].set(flat)
    p_counts = jnp.zeros((npad, 1), jnp.int32).at[:n, 0].set(counts)
    p_grads = jnp.zeros((npad, dim), jnp.float32).at[:n].set(
        grads.astype(jnp.float32))

    slot_names = tuple(sorted(slots.keys()))
    slot_list = [slots[name] for name in slot_names]
    new_w, new_slots = _apply_call(
        optimizer, slot_names, weights, slot_list, p_rows, p_grads, p_counts,
        block=block, interpret=interpret)
    return new_w, {name: s for name, s in zip(slot_names, new_slots)}


def maybe_fused_apply(optimizer, weights, slots, rows, grads, counts):
    """Dispatch hook for `ops.sparse.sparse_apply_dense_table`; None = XLA path
    (mode off only)."""
    use, interpret = _resolve()
    if not use:
        return None
    if not interpret:
        # e.g. Adam's per-row beta^t slots are width 1: not runnable compiled
        _require_lane_aligned("fused_sparse_apply", weights.shape[0],
                              weights.shape[1],
                              *(s.shape[1] for s in slots.values()))
    return fused_sparse_apply(optimizer, weights, slots, rows, grads, counts,
                              interpret=interpret)
