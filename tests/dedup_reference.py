"""The dedup as it stood before PR 37, kept as the tests' reference: the bodies
of `ops/dedup.unique_with_counts` and `ops/dedup.unique_and_route` with their
per-position passes (`ids[order]`, the run heads' sorted scatter, the segment
sum of ones, `zeros.at[order].set(seg)`). `tests/test_dedup.py` holds every
field of the package's results equal to these; `patch_reference_dedup` puts them
into the package for an end-to-end comparison (`tests/test_packed_layout.py`).
Below them the split routing the exchange had before `unique_and_route`
(`bucket_by_owner`, `unbucket`: a sort, a searchsorted and per-slot scatters),
which no code of the package has called since PR 31 and the tests hold the
fused routing and the block copies against.
Not collected: no test lives here."""

from typing import NamedTuple

import jax
import jax.numpy as jnp

from openembedding_tpu.ops.dedup import (RoutedBuckets, UniqueResult,
                                         expand_blocks)
from openembedding_tpu.utils import trace as _trace


def unique_with_counts(ids: jax.Array) -> UniqueResult:
    """The parent's body, verbatim: argsort, `ids[order]`, a sorted scatter of
    the run heads, a segment sum of ones."""
    with _trace.scope("sparse", "dedup"):
        n = ids.shape[0]
        if ids.ndim == 2:  # split-pair layout
            iota = jnp.arange(n, dtype=jnp.int32)
            s_hi, s_lo, order = jax.lax.sort(
                (ids[:, 0], ids[:, 1], iota), num_keys=2)
            sorted_ids = jnp.stack([s_hi, s_lo], axis=-1)
            is_new = jnp.concatenate(
                [jnp.ones((1,), dtype=bool),
                 (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
        else:
            order = jnp.argsort(ids).astype(jnp.int32)
            sorted_ids = ids[order]
            is_new = jnp.concatenate(
                [jnp.ones((1,), dtype=bool), sorted_ids[1:] != sorted_ids[:-1]])
        seg = (jnp.cumsum(is_new) - 1).astype(jnp.int32)  # ascending segment ids
        num_unique = seg[-1] + 1
        # duplicate writes to one segment all carry the same value, so .set is deterministic
        unique_ids = jnp.zeros(sorted_ids.shape, ids.dtype).at[seg].set(
            sorted_ids, mode="drop", indices_are_sorted=True)
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), seg, num_segments=n,
                                     indices_are_sorted=True)
        # position -> unique slot: `order` is a permutation, so sorting `seg`
        # by it is the map. A second sort, not `zeros.at[order].set(seg)`: the
        # unsorted scatter pays per position (0.49 ms over the benchmark's
        # 106,496 against the sort's 0.13; probe on the v5e, PR 35)
        _, inverse = jax.lax.sort((order.astype(jnp.int32), seg), num_keys=1)
        return UniqueResult(unique_ids, inverse, counts.astype(jnp.int32),
                            num_unique.astype(jnp.int32), order.astype(jnp.int32),
                            seg)


def unique_and_route(ids: jax.Array, valid: jax.Array, num_shards: int,
                     capacity: int, owner=None) -> tuple:
    """The parent's body, verbatim: the (owner, id, iota) sort, then the same
    scatter and segment sum, and `inverse` by an unsorted scatter."""
    with _trace.scope("exchange", "route"):
        n = ids.shape[0]
        S = num_shards
        iota = jnp.arange(n, dtype=jnp.int32)
        if ids.ndim == 2:  # split-pair layout
            from openembedding_tpu.ops.id64 import pair_mod
            owner_in = (pair_mod(ids, S).astype(jnp.int32) if owner is None
                        else owner.astype(jnp.int32))
            owner_in = jnp.where(valid, owner_in, S)
            so, s_hi, s_lo, order = jax.lax.sort(
                (owner_in, ids[:, 0], ids[:, 1], iota), num_keys=3)
            sorted_ids = jnp.stack([s_hi, s_lo], axis=-1)
            id_change = (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])
        else:
            owner_in = ((ids % S).astype(jnp.int32) if owner is None
                        else owner.astype(jnp.int32))
            owner_in = jnp.where(valid, owner_in, S)
            so, sorted_ids, order = jax.lax.sort((owner_in, ids, iota), num_keys=2)
            id_change = sorted_ids[1:] != sorted_ids[:-1]
        is_new = jnp.concatenate(
            [jnp.ones((1,), bool), (so[1:] != so[:-1]) | id_change])
        seg = (jnp.cumsum(is_new) - 1).astype(jnp.int32)
        num_unique = seg[-1] + 1
        unique_ids = jnp.zeros(sorted_ids.shape, ids.dtype).at[seg].set(
            sorted_ids, mode="drop", indices_are_sorted=True)
        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), seg, num_segments=n,
                                     indices_are_sorted=True)
        inverse = jnp.zeros((n,), jnp.int32).at[order].set(seg)
        uniq = UniqueResult(unique_ids, inverse, counts.astype(jnp.int32),
                            num_unique.astype(jnp.int32), order.astype(jnp.int32),
                            seg)

        # uniques and positions per owner, from the sorted owners (`so` is
        # ascending; the pseudo-owner S — invalid and carved-out positions —
        # sorts last and is cut off): owner s's uniques are the range
        # [start[s], start[s] + per_owner[s]) of the unique buffer
        # (S masked reductions, not a segment sum: a scatter-add pays per
        # position even into S + 1 segments)
        mine = so[:, None] == jnp.arange(S, dtype=jnp.int32)
        positions = jnp.sum(mine, axis=0, dtype=jnp.int32)
        per_owner = jnp.sum(mine & is_new[:, None], axis=0, dtype=jnp.int32)
        # unsigned: `dynamic_slice` wraps a signed offset if negative (three
        # scalar ops an offset in the program; no time on the chip, PERF.md)
        start = (jnp.cumsum(per_owner) - per_owner).astype(jnp.uint32)
        count = jnp.minimum(per_owner, capacity)
        overflow = jnp.sum(per_owner - count).astype(jnp.int32)
        # empty bucket slots hold the EMPTY sentinel, NOT zero (id 0 is a real
        # id): validity is then a pure function of the id payload, so the
        # exchange ships ONE all_to_all of ids instead of ids + a bool mask
        # (`bucket_validity`)
        if ids.ndim == 2:
            from openembedding_tpu.ops.id64 import PAIR_EMPTY as empty
        else:
            empty = -1
        with _trace.scope("exchange", "bucket"):
            bucket_ids = expand_blocks(unique_ids, start, count, capacity,
                                       fill=empty)
        return uniq, RoutedBuckets(bucket_ids, start, count, positions,
                                   overflow)


class BucketResult(NamedTuple):
    bucket_ids: jax.Array    # (num_shards, capacity) — ids grouped by owner shard
    bucket_valid: jax.Array  # (num_shards, capacity) bool
    # position of input element i inside its bucket: (owner[i], slot[i])
    owner: jax.Array         # (n,) int32
    slot: jax.Array          # (n,) int32
    overflow: jax.Array      # () int32 — elements dropped because a bucket was full


def bucket_by_owner(ids: jax.Array, valid: jax.Array, num_shards: int,
                    capacity: int) -> BucketResult:
    """Group ids into per-owner-shard buckets of static capacity. The split,
    per-slot form, `ops/dedup.py`'s until PR 43: the exchange routes through
    `unique_and_route`, and this is its independent reference
    (`tests/test_dedup.py`).

    Owner layout matches the reference: `owner = id % num_shards`, row-within-shard
    `id // num_shards` (`EmbeddingPullOperator.cpp:74-84`). Elements beyond a bucket's
    capacity are counted in `overflow` and dropped (the reference's dynamic buffers
    can't overflow; static XLA shapes can — callers size capacity via config and tests
    use capacity == n for exactness).

    NOTE: empty bucket slots are ZERO-filled here with `bucket_valid` as the
    mask; `unique_and_route` (the fused hot path) instead sentinel-fills so
    validity is derivable from the ids alone — do not apply `bucket_validity`
    to THIS function's output.
    """
    with _trace.scope("exchange", "route"):
        n = ids.shape[0]
        if ids.ndim == 2:  # split-pair layout: owner via modular pair arithmetic
            from openembedding_tpu.ops.id64 import pair_mod
            owner = jnp.where(valid, pair_mod(ids, num_shards).astype(jnp.int32),
                              num_shards)
        else:
            owner = jnp.where(valid, (ids % num_shards).astype(jnp.int32),
                              num_shards)
        # stable sort by owner so each bucket preserves input order
        order = jnp.argsort(owner, stable=True)
        sorted_owner = owner[order]
        # index within the owner group = position - start of that owner's run
        group_start = jnp.searchsorted(sorted_owner, sorted_owner, side="left")
        idx_in_group = jnp.arange(n, dtype=jnp.int32) - group_start.astype(jnp.int32)
        slot_sorted = idx_in_group
        in_cap = (slot_sorted < capacity) & (sorted_owner < num_shards)
        overflow = jnp.sum((~in_cap) & (sorted_owner < num_shards)).astype(jnp.int32)
        # scatter (owner, slot) -> id; out-of-capacity and invalid entries drop
        flat_pos = jnp.where(in_cap, sorted_owner * capacity + slot_sorted,
                             num_shards * capacity)
        lanes = ids.shape[1:]  # () single-lane, (2,) split-pair
        bucket_ids = jnp.zeros((num_shards * capacity,) + lanes,
                               ids.dtype).at[flat_pos].set(
            ids[order], mode="drop").reshape((num_shards, capacity) + lanes)
        bucket_valid = jnp.zeros((num_shards * capacity,), bool).at[flat_pos].set(
            True, mode="drop").reshape(num_shards, capacity)
        # per-input-element position (for unbucketing responses)
        owner_out = jnp.zeros((n,), jnp.int32).at[order].set(sorted_owner)
        slot_out = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.where(in_cap, slot_sorted, capacity))
        return BucketResult(bucket_ids, bucket_valid, owner_out, slot_out, overflow)


def unbucket(bucket_rows: jax.Array, owner: jax.Array, slot: jax.Array) -> jax.Array:
    """Inverse of bucket_by_owner for per-id payloads: read back each input element's
    row from its (owner, slot) position. bucket_rows: (num_shards, capacity, ...).
    A gather per slot: the reference the exchange's block copies
    (`compact_blocks`) are tested against, not called by it."""
    with _trace.scope("exchange", "reassemble"):
        num_shards, capacity = bucket_rows.shape[:2]
        flat = bucket_rows.reshape((num_shards * capacity,) + bucket_rows.shape[2:])
        pos = jnp.clip(owner * capacity + slot, 0, num_shards * capacity - 1)
        oob = (owner >= num_shards) | (slot >= capacity)
        out = flat[pos]
        return jnp.where(oob.reshape((-1,) + (1,) * (out.ndim - 1)),
                         jnp.zeros_like(out), out)


def patch_reference_dedup(monkeypatch):
    """Every call site of the package traces the reference from here on
    (until `monkeypatch.undo()`): call sites hold the functions by name."""
    from openembedding_tpu.ops import dedup, sparse
    from openembedding_tpu.parallel import sharded
    for mod in (dedup, sparse, sharded):
        monkeypatch.setattr(mod, "unique_with_counts", unique_with_counts)
    monkeypatch.setattr(dedup, "unique_and_route", unique_and_route)
    monkeypatch.setattr(sharded, "unique_and_route", unique_and_route)
