"""The dedup as it stood before PR 37, kept as the tests' reference: the bodies
of `ops/dedup.unique_with_counts` and `ops/dedup.unique_and_route` with their
per-position passes (`ids[order]`, the run heads' sorted scatter, the segment
sum of ones, `zeros.at[order].set(seg)`). `tests/test_dedup.py` holds every
field of the package's results equal to these; `patch_reference_dedup` puts them
into the package for an end-to-end comparison (`tests/test_packed_layout.py`).
Not collected: no test lives here."""

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


def patch_reference_dedup(monkeypatch):
    """Every call site of the package traces the reference from here on
    (until `monkeypatch.undo()`): call sites hold the functions by name."""
    from openembedding_tpu.ops import dedup, sparse
    from openembedding_tpu.parallel import sharded
    for mod in (dedup, sparse, sharded):
        monkeypatch.setattr(mod, "unique_with_counts", unique_with_counts)
    monkeypatch.setattr(dedup, "unique_and_route", unique_and_route)
    monkeypatch.setattr(sharded, "unique_and_route", unique_and_route)
