"""Static-shape dedup and owner-bucketing primitives.

These are the XLA-friendly counterparts of the reference's client-side hot loops:
`exb_unique_indices` (`entry/c_api.cc:220-231`) and the dedup + shard-scatter in
`EmbeddingPullOperator::generate_request` (`server/EmbeddingPullOperator.cpp:60-112`) /
`EmbeddingPushOperator::generate_request` (`server/EmbeddingPushOperator.cpp:29-62`).

The reference uses CPU `EasyHashMap`s with dynamic sizes; under XLA everything is
sort-based with **static capacities**: a buffer of n ids dedups into a buffer of n slots
with `counts == 0` marking padding. All functions are jit-safe (no data-dependent
shapes).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils import trace as _trace


class UniqueResult(NamedTuple):
    unique_ids: jax.Array   # (n,) — first num_unique slots are the sorted unique ids
    inverse: jax.Array      # (n,) int32 — ids[i] == unique_ids[inverse[i]]
    counts: jax.Array       # (n,) int32 — duplicate multiplicity; 0 = padding slot
    num_unique: jax.Array   # () int32
    # sort permutation + SORTED segment ids: `payload[order]` has ascending segment
    # ids `seg`, so a caller's reduction runs as segment_sum(payload[order], seg,
    # indices_are_sorted=True): a sorted scatter-add still pays per position
    # (1.32 ms for the benchmark's f32[106496,10] gradients, 0.93 for an s32
    # vector; v5e, PR 35), an unsorted one serializes. The dedup's own
    # integers come out of sorts, with no such pass (`_run_heads`)
    order: jax.Array        # (n,) int32
    seg: jax.Array          # (n,) int32, ascending

    def segment_reduce(self, payload: jax.Array) -> jax.Array:
        """Sum per-occurrence `payload` (n, ...) into the unique slots (n, ...)."""
        return jax.ops.segment_sum(payload[self.order], self.seg,
                                   num_segments=self.order.shape[0],
                                   indices_are_sorted=True)


def unique_with_counts(ids: jax.Array) -> UniqueResult:
    """Sort-based unique with inverse mapping and counts, static output size n.

    Reference semantics: gradients of duplicate ids are summed and the count recorded
    (`MpscGradientReducer.h:26-53`); here `inverse`/`segment_reduce` let the caller
    sum per-duplicate gradients into the unique slots.

    `ids` may be single-lane ((n,) int) or the split-pair 63-bit layout
    ((n, 2) uint32, `ops/id64.py`): pairs sort lexicographically with a
    two-key `lax.sort`, everything downstream is lane-count agnostic.
    """
    with _trace.scope("sparse", "dedup"):
        n = ids.shape[0]
        if ids.ndim == 2:  # split-pair layout
            iota = jnp.arange(n, dtype=jnp.int32)
            s_hi, s_lo, order = jax.lax.sort(
                (ids[:, 0], ids[:, 1], iota), num_keys=2)
            lanes = (s_hi, s_lo)
            is_new = jnp.concatenate(
                [jnp.ones((1,), dtype=bool),
                 (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
        else:
            # ONE stable sort gives the sorted ids and the permutation (an
            # `argsort` is this sort with the ids thrown away, and `ids[order]`
            # a gather over the n positions to get them back). Stable: `order`
            # among duplicates fixes the order `segment_reduce` adds f32 in
            sorted_ids, order = jax.lax.sort(
                (ids, jnp.arange(n, dtype=jnp.int32)), num_keys=1,
                is_stable=True)
            lanes = (sorted_ids,)
            is_new = jnp.concatenate(
                [jnp.ones((1,), dtype=bool), sorted_ids[1:] != sorted_ids[:-1]])
        return _run_heads(lanes, is_new, order)


def _run_heads(lanes: tuple, is_new: jax.Array,
               order: jax.Array) -> UniqueResult:
    """Run-length encode sorted ids (`lanes`: the (n,) ids, or a pair's two
    lanes; `is_new` marks each run's first position, `order` is the
    permutation that sorted them) by sorts, a cumsum and elementwise ops: no
    scatter, scatter-add or gather over the n positions, each of which pays
    per position on the TPU where a sort of the same vector costs a fraction
    (0.49 / 0.93 / 0.76 ms against 0.13 over the benchmark's 106,496; v5e,
    PR 35-37).

    One sort on the key `position if head else n` brings the run heads to the
    front in order, each with its id (0 for the rest) riding along. The
    carried ids ARE `unique_ids`, the sorted key is every run's first
    position and then n, and a run ends where the next begins:
    `counts[k] = key[k + 1] - key[k]`, the last run ending at n, 0 past it.
    Ties at n carry equal payloads, so no sort here needs to be stable."""
    n = is_new.shape[0]
    seg = (jnp.cumsum(is_new) - 1).astype(jnp.int32)  # ascending segment ids
    head_pos, *heads = jax.lax.sort(
        (jnp.where(is_new, jnp.arange(n, dtype=jnp.int32), n),)
        + tuple(jnp.where(is_new, x, jnp.zeros((), x.dtype)) for x in lanes),
        num_keys=1, is_stable=False)
    unique_ids = heads[0] if len(heads) == 1 else jnp.stack(heads, axis=-1)
    counts = jnp.concatenate(
        [head_pos[1:], jnp.full((1,), n, jnp.int32)]) - head_pos
    # position -> unique slot: `order` is a permutation, so sorting `seg` by
    # it is the map (`zeros.at[order].set(seg)` is an unsorted scatter)
    _, inverse = jax.lax.sort((order, seg), num_keys=1, is_stable=False)
    return UniqueResult(unique_ids, inverse, counts, seg[-1] + 1, order, seg)


def carry_to_unique(uniq: UniqueResult, values: jax.Array,
                    fill) -> jax.Array:
    """Propagate a per-POSITION value (n,) to its unique slot (n,), riding the
    already-paid fused sort: `values[order]` lines up with the ascending
    segment ids `seg`, so one sorted scatter lands each id's value in its
    unique slot (duplicate writes to a segment all carry the same value when
    `values` is a pure function of the id — the caller's contract). Padding
    slots (>= num_unique) keep `fill`.

    The hot-row membership probe (`parallel/sharded.py`) uses this to turn a
    per-position hot-slot probe into a per-unique-slot one without a second
    probe or sort."""
    with _trace.scope("sparse", "dedup"):
        n = uniq.order.shape[0]
        out = jnp.full((n,), fill, values.dtype)
        return out.at[uniq.seg].set(values[uniq.order], mode="drop",
                                    indices_are_sorted=True)


class RoutedBuckets(NamedTuple):
    """What `unique_and_route` hands the exchange: the S outgoing id buckets
    and where each one lies in the owner-major unique buffer. Owner s's ids are
    the contiguous range `unique_ids[start[s] : start[s] + count[s]]`, in
    order, at bucket slots 0..count[s]-1 — which is all `expand_blocks` /
    `compact_blocks` need to build a payload's buckets or to read returned
    rows back into unique order (S + 1 integers, not a position per slot)."""

    bucket_ids: jax.Array   # (num_shards, capacity[, 2]) — EMPTY past count[s]
    start: jax.Array        # (num_shards,) uint32 — first unique slot of owner s
    count: jax.Array        # (num_shards,) int32 — ids in bucket s (<= capacity)
    positions: jax.Array    # (num_shards,) int32 — id positions (duplicates
    #                         counted) routed to owner s, overflowed or not
    overflow: jax.Array     # () int32 — unique ids dropped because a bucket was full

    @property
    def bucket_valid(self) -> jax.Array:
        """(num_shards, capacity) bool occupancy, derived from the ids."""
        return bucket_validity(self.bucket_ids)


def expand_blocks(y: jax.Array, offsets: jax.Array, counts: jax.Array,
                  cap: int, fill=0) -> jax.Array:
    """(W, ...) rows laid end to end -> (S, cap, ...) blocks: block s is
    `y[offsets[s] : offsets[s] + cap]` with `fill` past its `counts[s]` rows.
    S masked `dynamic_slice`s, never a per-row scatter. `offsets <= W`."""
    S = counts.shape[0]
    # cap rows of slack: a slice taken at offset <= W ends inside the buffer,
    # so `dynamic_slice` never clamps its start
    pad = jnp.concatenate([y, jnp.zeros((cap,) + y.shape[1:], y.dtype)])
    lane = jnp.arange(cap, dtype=jnp.int32).reshape(
        (cap,) + (1,) * (y.ndim - 1))
    return jnp.stack([
        jnp.where(lane < counts[s],
                  jax.lax.dynamic_slice_in_dim(pad, offsets[s], cap, 0),
                  jnp.asarray(fill, y.dtype))
        for s in range(S)])


def compact_blocks(x: jax.Array, offsets: jax.Array, W: int, fill=0,
                   counts=None) -> jax.Array:
    """Inverse of `expand_blocks`: (S, cap, ...) blocks -> (W, ...), block s
    copied whole at `offsets[s]` (ascending), so each later block overwrites
    the tail of the one before: S contiguous block copies, never a per-row
    scatter, and the blocks' rows keep their order. Rows no block covers read
    `fill`; with `counts`, so do the rows of block s past its `counts[s]`
    (without, a block's tail is trusted to hold `fill` already, or to be
    covered by the next block). `offsets <= W`."""
    S, cap = x.shape[:2]
    fill = jnp.asarray(fill, x.dtype)
    if counts is not None:
        lane = jnp.arange(cap, dtype=jnp.int32).reshape(
            (1, cap) + (1,) * (x.ndim - 2))
        x = jnp.where(lane < counts.reshape((S,) + (1,) * (x.ndim - 1)), x,
                      fill)
    # cap rows of slack: a block copied at offset <= W ends inside the
    # buffer, so `dynamic_update_slice` never clamps its start
    buf = jnp.broadcast_to(fill, (W + cap,) + x.shape[2:])
    for s in range(S):
        buf = jax.lax.dynamic_update_slice_in_dim(buf, x[s], offsets[s], 0)
    return buf[:W]


def unique_and_route(ids: jax.Array, valid: jax.Array, num_shards: int,
                     capacity: int, owner=None) -> tuple:
    """Fused dedup + owner routing: ONE multi-key sort orders the positions
    for both, where `unique_with_counts` + `bucket_by_owner` (the split form, now
    the tests' reference: `tests/dedup_reference.py`) pay a sort each,
    a searchsorted and per-slot scatters (the S-invariant protocol compute the
    mesh1 bench surfaces — the reference does this client-side work on CPU off
    the device critical path, `EmbeddingPullOperator.cpp:60-112`; on TPU it
    rides the step).

    Sorting by (owner, id, iota) yields uniques in OWNER-MAJOR id order, and
    that order is a CONTRACT the exchange depends on: owner s's unique ids are
    one contiguous range of `unique_ids`, and its outgoing bucket is that
    range in order. So the buckets are built by S masked block copies
    (`expand_blocks`) and the callers build payload buckets and read returned
    rows back the same way, from `RoutedBuckets.start` / `.count` — no
    searchsorted, no per-slot (owner, slot) position. The unique buffer, the
    counts and `inverse` come out of two more small sorts (`_run_heads`), so
    no scatter or gather over the n positions is left here. `inverse`,
    `counts` and `seg` stay mutually consistent with the owner-major order.
    Returns (UniqueResult, RoutedBuckets).

    `valid` masks per-INPUT-id (invalid ids sort into a trailing pseudo-owner
    `num_shards` and never reach a bucket). `owner = id % num_shards` exactly
    like the split implementation — unless the caller passes an explicit
    per-position `owner` array ((n,) int32 in [0, num_shards]; the owner-
    assignment INDIRECTION of cold-tail re-sharding, `parallel/sharded.py`
    "COLD-TAIL RE-SHARDING"). A passed owner must be a pure function of the
    id (duplicates of one id must agree) and is still masked by `valid`.
    An owner's ids past `capacity` are dropped from its bucket and counted in
    `overflow`; they keep their unique slots."""
    with _trace.scope("exchange", "route"):
        n = ids.shape[0]
        S = num_shards
        iota = jnp.arange(n, dtype=jnp.int32)
        if ids.ndim == 2:  # split-pair layout
            from .id64 import pair_mod
            owner_in = (pair_mod(ids, S).astype(jnp.int32) if owner is None
                        else owner.astype(jnp.int32))
            owner_in = jnp.where(valid, owner_in, S)
            so, s_hi, s_lo, order = jax.lax.sort(
                (owner_in, ids[:, 0], ids[:, 1], iota), num_keys=3)
            lanes = (s_hi, s_lo)
            id_change = (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])
        else:
            owner_in = ((ids % S).astype(jnp.int32) if owner is None
                        else owner.astype(jnp.int32))
            owner_in = jnp.where(valid, owner_in, S)
            so, sorted_ids, order = jax.lax.sort((owner_in, ids, iota), num_keys=2)
            lanes = (sorted_ids,)
            id_change = sorted_ids[1:] != sorted_ids[:-1]
        is_new = jnp.concatenate(
            [jnp.ones((1,), bool), (so[1:] != so[:-1]) | id_change])
        uniq = _run_heads(lanes, is_new, order)

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
            from .id64 import PAIR_EMPTY as empty
        else:
            empty = -1
        with _trace.scope("exchange", "bucket"):
            bucket_ids = expand_blocks(uniq.unique_ids, start, count,
                                       capacity, fill=empty)
        return uniq, RoutedBuckets(bucket_ids, start, count, positions,
                                   overflow)


# ---------------------------------------------------------------------------
# Grouped routing plan: fuse per-table bucket arrays into ONE wire array so a
# dim-group of T tables ships 1 all_to_all of ids instead of T. Per-table
# dedup/routing (unique_and_route) is unchanged — each table keeps its own
# capacity segment at a fixed slot offset, so the table index is POSITION-
# encoded (no tag lanes on the wire) and the receiver recovers each table's
# buckets by slicing. Mixed id layouts widen to a common wire layout via the
# split-pair machinery (`ops/id64.py`); a uniform group pays zero extra bytes.
# ---------------------------------------------------------------------------


def concat_owner_buckets(bucket_ids_list) -> jax.Array:
    """[(S, cap_t[, 2]) sentinel-filled bucket arrays] -> one (S, sum_cap[, 2])
    wire array in the narrowest common layout:

    - all split-pair           -> pair (uint32 lanes) unchanged;
    - any pair + single-lane   -> everything widens to pair (`split_ids`);
    - all single-lane          -> widest int dtype (int64 wins over int32).

    Sentinels survive every conversion (-1 <-> PAIR_EMPTY), so
    `bucket_validity` still works on the fused array and on its slices."""
    from .id64 import split_ids
    if any(b.ndim == 3 for b in bucket_ids_list):
        wire = [b if b.ndim == 3 else split_ids(b) for b in bucket_ids_list]
    else:
        dt = max((b.dtype for b in bucket_ids_list),
                 key=lambda d: jnp.dtype(d).itemsize)
        wire = [b.astype(dt) for b in bucket_ids_list]
    return jnp.concatenate(wire, axis=1)


def split_owner_buckets(wire_ids: jax.Array, templates) -> list:
    """Receiver-side inverse of `concat_owner_buckets` (applied AFTER the
    all_to_all): slice each table's capacity segment and narrow it back to the
    table's native id layout. `templates`: [(cap, pair: bool, dtype)] in
    concatenation order. Valid single-lane ids fit their native dtype by
    construction (array-table ids < input_dim < 2^31; int64 keys only exist
    when the wire is int64 too), and sentinels map back to -1."""
    from .id64 import pair_valid
    outs, off = [], 0
    for cap, pair, dtype in templates:
        seg = wire_ids[:, off:off + cap]
        off += cap
        if wire_ids.ndim == 3:  # pair wire
            if pair:
                outs.append(seg)
            else:
                valid = pair_valid(seg)
                if jnp.dtype(dtype).itemsize >= 8:  # x64-on int64 keys
                    joined = ((seg[..., 0].astype(jnp.int64) << 32)
                              | seg[..., 1].astype(jnp.int64))
                    outs.append(jnp.where(valid, joined, jnp.int64(-1)))
                else:
                    outs.append(jnp.where(valid, seg[..., 1].astype(dtype),
                                          jnp.asarray(-1, dtype)))
        else:
            outs.append(seg.astype(dtype))
    if off != wire_ids.shape[1]:
        raise ValueError(f"templates cover {off} slots, wire has "
                         f"{wire_ids.shape[1]}")
    return outs


def bucket_validity(bucket_ids: jax.Array) -> jax.Array:
    """Occupancy mask of a sentinel-initialized bucket array (see
    `unique_and_route` — NOT the tests' reference `bucket_by_owner`
    (`tests/dedup_reference.py`), whose empty slots are zero-filled):
    derivable on either side of the all_to_all."""
    from .id64 import is_pair, pair_valid
    return pair_valid(bucket_ids) if is_pair(bucket_ids) else bucket_ids >= 0


# ---------------------------------------------------------------------------
# Conflict-set primitives for the software-pipelined train loop
# (`MeshTrainer(pipeline_steps=True)`, `parallel/sharded.py`
# `grouped_conflict_patch`): batch t+1's speculatively prefetched rows are
# valid except where batch t's push updated them, and the intersection rides
# the same fused-sort machinery as the exchange itself — no hash table, no
# data-dependent shapes.
# ---------------------------------------------------------------------------


def member_mask(ref_ids: jax.Array, ref_valid: jax.Array,
                query_ids: jax.Array, query_valid: jax.Array) -> jax.Array:
    """Per-QUERY membership in the valid reference id set, ONE fused sort.

    `ref_ids` (R[, 2]) / `query_ids` (Q[, 2]) share one id layout (single-lane
    int or the split-pair 63-bit layout). Sort the concatenation by id with a
    reference-membership weight riding along; a query is a member iff its id
    segment holds at least one VALID reference entry. Invalid queries are
    never members; invalid reference entries never vouch — so sentinel-filled
    bucket padding on either side can collide harmlessly."""
    R = ref_ids.shape[0]
    n = R + query_ids.shape[0]
    cat = jnp.concatenate([ref_ids, query_ids], axis=0)
    contrib = jnp.concatenate([ref_valid.astype(jnp.int32),
                               jnp.zeros((n - R,), jnp.int32)])
    iota = jnp.arange(n, dtype=jnp.int32)
    if cat.ndim == 2:  # split-pair layout
        s_hi, s_lo, s_contrib, s_idx = jax.lax.sort(
            (cat[:, 0], cat[:, 1], contrib, iota), num_keys=2)
        id_change = (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])
    else:
        s_id, s_contrib, s_idx = jax.lax.sort((cat, contrib, iota),
                                              num_keys=1)
        id_change = s_id[1:] != s_id[:-1]
    is_new = jnp.concatenate([jnp.ones((1,), bool), id_change])
    seg = (jnp.cumsum(is_new) - 1).astype(jnp.int32)
    seg_refs = jax.ops.segment_sum(s_contrib, seg, num_segments=n,
                                   indices_are_sorted=True)
    hit = seg_refs[seg] > 0
    out = jnp.zeros((n,), bool).at[s_idx].set(hit)
    return out[R:] & query_valid


def compact_member_slots(member: jax.Array, pcap: int):
    """Compact a (S, cap) membership mask to per-row slot-index buckets
    (S, pcap) — slot j of row s lands at its rank among row s's members,
    -1 padding. Members beyond `pcap` drop and are counted in the returned
    scalar overflow (the conflict-patch budget knob: an overflowed row keeps
    its one-step-stale speculative value — bounded staleness, gauged)."""
    S, cap = member.shape
    pos = jnp.cumsum(member.astype(jnp.int32), axis=1) - 1
    within = member & (pos < pcap)
    row = jnp.arange(S, dtype=jnp.int32)[:, None]
    flat_tgt = jnp.where(within, row * pcap + pos, S * pcap)
    col = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (S, cap))
    slots = jnp.full((S * pcap,), -1, jnp.int32).at[flat_tgt.reshape(-1)].set(
        col.reshape(-1), mode="drop").reshape(S, pcap)
    overflow = jnp.sum(member & ~within).astype(jnp.int32)
    return slots, overflow
