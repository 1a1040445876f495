"""Sharded pull/push: the reference's PS wire protocol re-expressed as ICI collectives.

These functions run **inside shard_map** on a 1-D mesh of S devices. Each device holds
one table shard (rows where `id % S == shard_index`, the reference's layout,
`EmbeddingPullOperator.cpp:74-84`) and one slice of the batch.

PULL (reference `EmbeddingPullOperator`, client dedup -> per-node RPC -> server gather
-> client reassemble):
  1. dedup + owner-routing ordered by ONE multi-key sort; the unique buffer,
     the counts and the position -> slot map by two more small sorts, no
     scatter or gather over the positions (`ops/dedup.unique_and_route`;
     client-side dedup, `c_api.cc:220-231`)
  2. `all_to_all` id buckets            [the RPC fan-out, now one ICI collective]
     — empty slots carry the EMPTY sentinel, validity derives from the payload
  3. gather rows from the local shard (server hot loop; hash tables lazily insert —
     the reference's `_new_weights` init-on-pull)
  4. `all_to_all` rows back, un-bucket, expand duplicates (client `apply_response`)

PUSH+UPDATE (reference `EmbeddingPushOperator` + `EmbeddingStoreOperator`, collapsed:
SPMD needs no batch-version gate):
  1. reuse the pull's dedup/bucketing/exchange plan (the reference likewise keeps the
     pull request around; recomputing would double the hot-path sort + id all_to_all)
  2. segment-sum local grads + counts into the unique slots (client pre-sum, `:29-62`)
  3. ONE `all_to_all` of grads along the same routes — the duplicate counts ride as
     bitcast lanes of the payload
  4. owner re-dedups across sources (the MPSC reducer, `MpscGradientReducer.h`) and
     applies the fused optimizer once per unique row

Collective budget: exactly 3 all_to_alls per DIM-GROUP per train step (ids, rows,
grads+counts), pinned at the HLO level in `tests/test_dedup.py` /
`tests/test_wire.py`. Tables sharing an embedding dim fuse their exchanges
(`grouped_lookup_train` / `grouped_apply_gradients`): each table's bucket array
occupies a fixed capacity segment of one concatenated wire array (the table
index is position-encoded — see `ops/dedup.concat_owner_buckets`), so a
T-table model with G dim-groups launches 3*G collectives instead of 3*T.
Row/grad payloads optionally travel quantized (bf16 default / int8 opt-in,
`ops/wire.py`, `OETPU_WIRE`) — and since round 13 the encode runs BEFORE the
collective (rows at the owner edge in `_serve_rows`, grads at the client
edge), so the compiled a2a operands themselves are int8/bf16 with the scales
in-band; int8 training adds pull-side error-feedback residuals
(`EmbeddingTableState.ef`, served rows ship q(w+ef)) and stochastic rounding
on the grad push so AUC holds fp32 parity. Id buckets and duplicate-count
lanes are always exact. `S == 1` specializes to identity routing (no
collectives, no buckets, no wire quantization).

Static capacity: each (src, dst) bucket of the WIRE holds `capacity` ids.
`capacity == n` (exact mode, `capacity_factor=0`) can never drop an id and moves
S*n id slots; a capacity_factor sizes the wire's buckets to ~ factor * n / S,
with overflow counters to watch (dropped ids pull zeros / drop grads — divergence
from the reference's unbounded buffers, surfaced in metrics). `capacity_factor`
sizes the wire ONLY: what the owner then works over is the next paragraph's.

WHAT THE OWNER WORKS OVER: the slots it received, not S x capacity. Gather,
scatter and the segment sums pay per POSITION, valid or empty, and in exact
mode at least 1 - 1/S of the S*n received slots are empty on average (83% in
the four-chip benchmark cell). So after the id
all_to_all the plan compacts the receive side once (`_owner_view` ->
`ExchangePlan.owner`) to a static working size W = n, the device's own number
of positions — what a balanced exchange delivers at most on average, and the
size `Trainer` works at. `unique_and_route` gives a unique id the bucket slot
"rank within its owner group", so the valid slots of every received bucket are
a PREFIX; compaction is S contiguous block copies at the running offsets
sum(r_<s) (`_compact`: each later block overwrites the empty tail of the one
before), never a per-position scatter, and it keeps the received source-major
order, so the owner's cross-source reduction adds in the same order and the
result is the same bit for bit. The pull's serve (`_serve_rows`) and the push's
apply (`_owner_apply`, which compacts the received grad payload with the same
offsets) run over that view; served rows go back to the wire's bucket layout by
S masked block copies (`_expand`). Exactness is kept: in a step where the ids
received by a shard do not fit W (ids crowding one owner), that shard runs the
SAME functions over all S * cap slots (a `lax.cond` on `OwnerView.fits`; per
shard, so no collective sits inside). Where S * cap <= n already (S == 1, or a
capacity_factor <= 1) nothing is compacted and no conditional is traced. The
step stats count it (`exchange_load_stats`): `owner_fill` (received ids over
W, the fullest shard) and `owner_full_steps`; on a device profile the block
copies sit under `exchange.compact` and a full-size step's ops under
`exchange.full_size`. The pipelined conflict patch (`grouped_conflict_patch`)
indexes received slots by position and keeps the bucket layout.

THE OWNER PLANS ONCE A STEP (a shard in the scan's packed layout, weights and
optimizer slots in one array; `ops/sparse.py` "ONE DEDUP AND ONE TABLE GATHER A
STEP" is the same mechanism on one chip). The serve and the apply of one step
read the SAME rows of the shard and nothing writes it between them: the serve
is the step's first op on it, the apply's scatter the last. A gather from the
shard is latency-bound per index, and the serve paid it once a received SLOT
(W = n of them for about two thirds as many rows) and the apply once more per
unique slot: 3.03 + 2.22 ms of the four-chip cell's 22.5 ms step (ledger,
PR 38). So the serve of `grouped_lookup_train` plans
(`ops/sparse.plan_packed_rows` over the slots it serves: the apply's own dedup
and routing, and ONE sorted gather of the unique packed rows at the apply's
rung), every slot reads its weight columns out of that small array, and the
plan rides `ExchangePlan.owner_plan` to `grouped_apply_gradients`, whose
`_apply_unique` hands it to `sparse_apply_packed_table(plan=)`: no second dedup
and no second gather. Same values in the same places: the shard after K steps
is the plan-less one bit for bit (`tests/test_packed_layout.py`).
- The plan leaves out what the serve's `main_valid` does (an empty slot; a
  received id that lives in the migration annex): those slots' rows are -1,
  which `plan_packed_rows` routes to its sentinel as it does a negative id,
  so the plan needs no mask and its counts are the dedup's own (a 0 / 1 mask
  would cost a segment sum over the slots: a gather by `order` 0.76 ms and a
  scatter-add 0.93, the price of the apply's sum of the multiplicities). The
  counts the apply brings are positive exactly on the slots the plan kept:
  `recv_valid` and the pushed counts both come from `(uniq.counts > 0) &
  _id_valid`, and the apply zeroes the annex ids' counts after the same
  directory probe. So both pick the same rung of `apply_ladder`, and a hot row
  (never in a bucket) or an annex row (served and applied apart) changes
  nothing in the plan.
- What engages it is visible at trace time, and everything else runs the
  program it had: an array table (a hash table probes per slot); the packed
  layout, handed down by the caller whose apply of the same step follows
  (`grouped_lookup_train(packed_list=)`). `grouped_prefetch` serves step t+1
  BEFORE step t's apply writes the shard, so a plan made there would be stale:
  it passes no layout, serves per slot and hands on no plan; so do serving and
  `train_step` (split layout). `exchange.owner_plans{path="shared" |
  "per_slot"}` counts the choice, once a table a trace.
- The serve and the apply each sit under `lax.cond(view.fits, compact,
  full_size)`. The compact branch carries the real plan; the full-size branch
  serves per slot and returns zeros in the plan's shapes (`_no_plan`), and the
  apply's full-size branch dedups and gathers for itself. Both conditionals
  branch on the same `view.fits`, so a plan is never read in a step that did
  not make it. Where nothing is compacted (`plan.owner is None`) there is no
  conditional and the plan rides as it is.

WHAT THE CLIENT SENDS: what it has, by S block copies, not S x capacity
scatters. `unique_and_route` sorts by (owner, id), so the unique buffer comes
out OWNER-MAJOR: owner s's ids are the contiguous range
`unique_ids[start[s] : start[s] + count[s]]`, in order, and that range IS
bucket s, at slots 0..count[s]-1 (`ops/dedup.RoutedBuckets`: S starts and S
counts, not an (owner, slot) pair per unique slot). So every per-unique-slot
array goes out the same way — the ids inside `unique_and_route`, the encoded
gradient payload in `_to_buckets` — as S masked slices of the array padded by
`cap` slots (`ops/dedup.expand_blocks`: lanes past count[s] hold the fill:
EMPTY for ids, zeros for a payload, exactly what a scatter into a filled
array left there), and what comes back in the bucket layout — the pulled
rows, still encoded, and the conflict patch's stage and mask — is read into
unique order by `_from_buckets`: bucket s copied at its owner's range, each
later block overwriting the tail of the one before
(`ops/dedup.compact_blocks`, the owner's `_compact` with each block masked
past its count). The rows are decoded after that, n of them and not S x cap
(decoding is row by row, so the order does not matter to a bit). Slots that
no bucket holds (hot and invalid positions: the pseudo-owner S sorts last;
an owner's ids past `cap`; padding) read zeros whatever an owner served for
an EMPTY slot. No working size is chosen and no `fits` is needed, unlike
the owner's side and the apply's: a bucket of `cap` slots always holds its
owner's range, or drops the same tail past `cap` that a per-slot scatter
dropped, and counts it in `overflow`. On a device profile the client's copies sit under
`exchange.bucket`, apart from `exchange.route` (the sorts that make the
unique buffer, the duplicate pre-sum, the encode) and from the owner's
`exchange.compact`.
The wire carries the same arrays bit for bit. Tested in
`tests/test_client_buckets.py` against the per-slot scatter it replaced.

SIZING RULE for `capacity_factor` (f): bucket (src, dst) must hold the unique
ids of src's batch slice owned by dst. With u unique ids per device batch of n
and p_max = the hottest shard's share of them, zero-drop needs
    f >= S * p_max * (u / n).
Uniform ids: p_max ~ 1/S, so f >= u/n (<= 1). Zipfian CTR traffic concentrates
2-4x on hot shards after hashing -> start at f in [1, 2], watch
`pull_overflow`/`push_overflow` in the step stats (psum'd per batch) and the
table-level `overflow` counter, raise f while they fire. f = 0 (exact mode,
cap = n) can never drop; it moves S*n id slots per a2a (0.38 ms of the
four-chip step for all three wires, PERF.md), and the owner does not pay for
the empty ones. Tested in `tests/test_capacity_and_migration.py` and
`tests/test_owner_compact.py`.

Out-of-vocab ids (array tables) are masked invalid end to end: they pull zeros and
their gradients are dropped, identical to the single-device path (`ops/sparse.py`).

HOT-ROW REPLICATION (skew-aware hybrid placement, Parallax arXiv:1808.02621):
under Zipf traffic a few thousand ids absorb a large share of `shard_positions`
load, and every access pays the 3-a2a round trip while hot-spotting the owner
shard. When a table carries a replicated hot cache (`EmbeddingTableState.hot`,
`MeshTrainer(hot_rows=...)`), the client route probes each id against the hot
set (a mini open-addressing probe riding the SAME fused sort — one extra
`hash_find` per position, the hot slot carried to unique slots by
`ops/dedup.carry_to_unique`) and partitions hot/cold:

- HOT positions never enter the buckets (they route like invalid ids, to the
  pseudo-owner S): zero a2a bytes, zero owner-shard load. Their rows gather
  LOCALLY from the replicated `hot.weights` and `_reassemble` overlays them.
- COLD positions flow through the unchanged plan/exchange above.
- BACKWARD: per-unique grad sums scatter into the compact (H, dim) hot
  aggregate (SparCML's dense-ified hot payload), reduce across the data axis
  in fixed source order (`_hot_apply` — bit-matching the cold owner's sorted-
  segment reduction at fp32 wire), and the optimizer applies the IDENTICAL
  update on every replica with the replicated `hot.slots`, so replicas never
  diverge.

Owner-shard copies of hot rows go stale while the cache is active; every read
routes through the cache, and `hot_writeback` scatters weights+slots back into
the owner shards (no collective — each shard overwrites the rows it owns) at
snapshot/refresh time, so checkpoints, export and the sync delta feed stay
byte-identical to the hot-off world. `hot_gather`/`build_hot_identity` fill the
cache from the shards (promotion inserts absent hash ids, values copied
bit-exactly via all_gather + owner select, no float reduction). The hot set is
trace-time static (H rows, C = 2H probe slots): promote/demote between steps
(`MeshTrainer.refresh_hot_rows`, fed by the `utils/sketch.py` heavy hitters)
swaps array CONTENTS, never shapes, so nothing re-jits. S == 1 meshes reject
hot state loudly (one device owns everything; a second copy could only skew).

COLD-TAIL RE-SHARDING (owner-assignment indirection, the second half of
Parallax hybrid placement): replication fits only the very head of the Zipf
curve — below it sit ids too cold to replicate but hot enough that hash
placement (`owner = id % S`) leaves their home shards measurably overloaded
(`exchange.shard_imbalance` stays above 1 after the head leaves). When a
table carries a migration directory (`EmbeddingTableState.mig`,
`MeshTrainer(mig_rows=...)`), the client route probes each id against it (a
second mini open-addressing probe riding the SAME fused sort as the hot
probe) and overrides the owner for the M migrated ids — `unique_and_route`
takes the precomputed per-position owner, so the indirection costs one
`hash_find` and changes NOTHING else about the 3-a2a exchange: no extra
collective, no extra wire bytes, identical bucket shapes.

- The DIRECTORY (keys/rank/ids/owners) is replicated so every source routes
  a migrated id to the same assigned owner.
- Each shard carries an M-row ANNEX (`mig.weights`/`mig.slots`, sharded);
  the assigned owner serves a migrated id from annex row `rank` and applies
  its gradients there (the received grads take the exact same source-major
  reduction path as a home row's, so fp32-wire training is bit-exact vs an
  unmigrated run — tests/test_placement.py pins it). The home shard's main-
  table copy goes stale while migrated; the server probe masks migrated ids
  out of the main table so hash tables never re-insert them.
- Lifecycle off the hot path, static shapes, never re-jits: `mig_gather`
  installs a directory and fills the annex from the home shards (one
  all_gather + exact home select — bit copies), `mig_writeback` restores the
  home copies from the assigned owners' annexes (one all_gather + owner
  select), and `MeshTrainer.migrate_rows` composes them. `hot_sync` runs the
  writeback before every checkpoint/export/sync-delta snapshot, so on-disk
  artifacts stay byte-identical to an unmigrated run. Hot and migrated sets
  are DISJOINT by construction (the trainer filters each against the other);
  S == 1 meshes reject migration state exactly like hot state.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..embedding import EmbeddingSpec, EmbeddingTableState, HotRows, MigRows
from ..ops.dedup import (RoutedBuckets, UniqueResult, bucket_validity,
                         carry_to_unique, compact_blocks, expand_blocks,
                         unique_and_route, unique_with_counts)
from ..ops.sparse import (PackedPlan, gather_packed_rows, lookup_rows,
                          packed_rows, packed_width, plan_packed_rows,
                          sparse_apply_dense_table)
from ..utils import metrics as _metrics
from ..utils import trace as _trace
from .mesh import DATA_AXIS

# probe budget of the hot-set membership table (C = 2H slots -> load factor
# <= 0.5, chains stay short); `build_hot_identity` inserts host-side with the
# SAME budget, so a row the device probe cannot reach is never placed
HOT_NUM_PROBES = 16


class OwnerView(NamedTuple):
    """The received ids of one plan compacted to the owner's working size W
    (module doc "WHAT THE OWNER WORKS OVER"): the valid prefixes of the S
    received buckets laid end to end in source-major order, EMPTY after. Made
    once per plan, after the id all_to_all (`_owner_view`), and used by the
    pull's serve and the push's apply of the same step; the serve's
    `PackedPlan` of a packed shard (`ExchangePlan.owner_plan`) is over these
    W slots."""

    ids: jax.Array      # (W[, 2]) valid ids first, source-major; EMPTY after
    valid: jax.Array    # (W,)
    counts: jax.Array   # (S,) int32 r_s: valid ids received from source s
    offsets: jax.Array  # (S,) int32 sum of r over the sources before s (<= W)
    total: jax.Array    # () int32 sum of r_s

    @property
    def fits(self) -> jax.Array:
        """() bool: the received ids fit W; False = this step works full
        size."""
        return self.total <= self.valid.shape[0]


class ExchangePlan(NamedTuple):
    """The routing state shared between a pull and its matching push (reference: the
    cached request/offset maps inside the pull handler reused at apply_response and
    by the push for the same batch)."""

    uniq: UniqueResult
    buckets: RoutedBuckets
    recv_ids: jax.Array    # (S, cap) ids this shard must serve
    recv_valid: jax.Array  # (S, cap)
    cap: int
    # hot-row partition (None/0 when the table has no replicated cache):
    # per-UNIQUE-slot hot-cache row in [0, hot_rows], hot_rows = cold/miss
    hot_slot: Optional[jax.Array] = None
    hot_rows: int = 0
    # per-UNIQUE-slot 1 where the migration directory re-routed the id off
    # its hash home (None when the table has no directory) — pure accounting,
    # folded into the step stats as `mig_unique`/`mig_hits`
    mig_moved: Optional[jax.Array] = None
    # pipelined prefetch only, int8 wire with error feedback: the PRE-serve
    # EF residual this shard gathered for each recv slot, (S, cap, dim) f32
    # (zeros for annex/invalid slots). Local serving-shard state — never on
    # the wire — that `grouped_conflict_patch` replays so the patched rows
    # AND the post-patch residuals are bit-identical to the serial schedule
    ef_stash: Optional[jax.Array] = None
    # the received ids at the owner's working size; None where the receive
    # side is no larger than that (S == 1, or S * cap <= n) and the owner
    # works over `recv_ids` as they are
    owner: Optional[OwnerView] = None
    # what the owner's serve knows of this step's rows, kept for the same
    # step's apply (module doc "THE OWNER PLANS ONCE A STEP"); None where the
    # serve gathered per slot
    owner_plan: Optional[PackedPlan] = None


def _flat_recv(plan: ExchangePlan):
    """The whole receive buffer as one flat run of slots: (S * cap[, 2]) ids
    (split-pair buckets keep their lane dim) and their (S * cap,) validity."""
    ids = plan.recv_ids
    return (ids.reshape(-1, 2) if ids.ndim == 3 else ids.reshape(-1)), \
        plan.recv_valid.reshape(-1)


def _a2a(what: str, x: jax.Array, axis) -> jax.Array:
    """The exchange's `all_to_all` under its own stage name
    (`exchange.a2a_ids` / `a2a_rows` / `a2a_grads`), so a device profile
    tells the three wires apart."""
    with _trace.scope("exchange", "a2a_" + what):
        return jax.lax.all_to_all(x, axis, 0, 0)


def _bucket_capacity(n: int, num_shards: int, capacity_factor: float) -> int:
    if capacity_factor <= 0:  # exact mode
        return n
    return max(1, min(n, int(-(-capacity_factor * n // num_shards))))


def _compact(x: jax.Array, offsets: jax.Array, W: int, fill=0) -> jax.Array:
    """(S, cap, ...) received buckets -> (W, ...): bucket s copied whole at
    the running offset of the valid ids before it (`ops/dedup.compact_blocks`).
    A bucket's valid slots are a PREFIX (`unique_and_route` fills a bucket
    from slot 0), so each later block overwrites exactly the empty tail of
    the one before: S contiguous block copies, no per-position scatter, and
    the source-major order of the valid slots is kept."""
    with _trace.scope("exchange", "compact"):
        return compact_blocks(x, offsets, W, fill)


def _expand(y: jax.Array, view: OwnerView, cap: int) -> jax.Array:
    """Inverse of `_compact` for what the owner serves: (W, ...) rows in
    compact order -> (S, cap, ...) in the wire's bucket layout, zeros past
    each bucket's r_s valid slots (what the full-size serve leaves there)."""
    with _trace.scope("exchange", "compact"):
        return expand_blocks(y, view.offsets, view.counts, cap)


def _to_buckets(payload: jax.Array, plan: "ExchangePlan") -> jax.Array:
    """Per-unique-slot payload rows (n, ...) -> the plan's S outgoing buckets
    (S, cap, ...): bucket s is owner s's range of the owner-major unique
    buffer, zeros past its count (module doc "WHAT THE CLIENT SENDS")."""
    with _trace.scope("exchange", "bucket"):
        return expand_blocks(payload, plan.buckets.start, plan.buckets.count,
                             plan.cap)


def _from_buckets(x: jax.Array, plan: "ExchangePlan") -> jax.Array:
    """What came back in the plan's bucket layout (S, cap, ...) ->
    per-unique-slot rows (n, ...): bucket s copied at its owner's range.
    Slots no bucket holds — hot and invalid ids, an overflowed tail, padding —
    read zeros, whatever an owner put in a bucket's empty slots."""
    with _trace.scope("exchange", "bucket"):
        return compact_blocks(x, plan.buckets.start, plan.uniq.order.shape[0],
                              counts=plan.buckets.count)


def _owner_view(recv_ids: jax.Array, recv_valid: jax.Array,
                n: int) -> Optional[OwnerView]:
    """The plan's `OwnerView` at working size W = n, the device's own number
    of positions; None (trace time) where the S * cap received slots are no
    more than that."""
    S, cap = recv_valid.shape
    if S * cap <= n:
        return None
    with _trace.scope("exchange", "compact"):
        counts = jnp.sum(recv_valid, axis=1, dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        offsets = jnp.minimum(ends - counts, n)
        if recv_ids.ndim == 3:
            from ..ops.id64 import PAIR_EMPTY as empty
        else:
            empty = -1
        ids = _compact(recv_ids, offsets, n, fill=empty)
        return OwnerView(ids, bucket_validity(ids), counts, offsets, ends[-1])


def _id_valid(spec: EmbeddingSpec, ids: jax.Array) -> jax.Array:
    """In-vocab mask. Hash tables accept any non-negative id; array tables reject
    ids outside [0, input_dim) so padded shard rows are never read or trained."""
    if ids.ndim == 2:  # split-pair 63-bit layout (hash tables only)
        from ..ops.id64 import pair_valid
        return pair_valid(ids)
    if spec.use_hash_table:
        return ids >= 0
    return (ids >= 0) & (ids < spec.input_dim)


def _is_pair_batch(spec: EmbeddingSpec, ids: jax.Array) -> bool:
    """Pair dispatch gated on use_hash_table: a uint32 two-field batch on an
    array table is NOT a pair (`ops/id64.is_pair` docstring)."""
    from ..ops.id64 import is_pair
    return spec.use_hash_table and is_pair(ids)


def adapt_batch_ids(spec: EmbeddingSpec, state: EmbeddingTableState,
                    ids: jax.Array) -> jax.Array:
    """Route ids in the TABLE's key layout. Under x64-off every hash table keys
    in the split-pair layout (`tables/hash_table.fresh_keys`), so a single-lane
    int batch must widen BEFORE dedup/routing or the server-side probe indexes
    pair keys with flat ids (the single-device paths adapt inside
    `hash_lookup*`; the sharded protocol adapts here, at its entry, so plan
    and probe agree — `adapt_ids` is shape-agnostic, the batch dims ride)."""
    if not spec.use_hash_table or state.keys is None:
        return ids
    from ..tables.hash_table import adapt_ids
    return adapt_ids(state.keys, ids)


def flatten_ids(spec: EmbeddingSpec, ids: jax.Array) -> jax.Array:
    """(... [, 2]) -> (n [, 2]): one row per id POSITION whatever the lane
    count (split-pair ids keep their trailing lane dim)."""
    return ids.reshape(-1, 2) if _is_pair_batch(spec, ids) else ids.reshape(-1)


def ids_positions(spec: EmbeddingSpec, ids: jax.Array) -> int:
    return ids.size // 2 if _is_pair_batch(spec, ids) else ids.size


def _out_shape(spec: EmbeddingSpec, ids: jax.Array):
    """Row-output shape for an id batch: pairs drop their lane dim."""
    return ids.shape[:-1] if _is_pair_batch(spec, ids) else ids.shape


def _hot_probe(hot: HotRows, flat: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-POSITION hot-set membership probe -> hot row in [0, H] (H = miss).
    One `hash_find` against the mini probe table; invalid positions probe the
    EMPTY sentinel and always miss. `flat` must be in the TABLE's key layout
    (`adapt_batch_ids`) so pair/single-lane matches `hot.keys`; valid array-
    table ids are < 2^31 by construction, so the dtype cast is lossless."""
    from ..tables.hash_table import hash_find
    C = hot.keys.shape[0]
    H = hot.weights.shape[0]
    if hot.keys.ndim == 2:
        from ..ops.id64 import PAIR_EMPTY
        probe = jnp.where(valid[:, None], flat, PAIR_EMPTY)
    else:
        probe = jnp.where(valid, flat, -1).astype(hot.keys.dtype)
    pslot = hash_find(hot.keys, probe, num_probes=HOT_NUM_PROBES)
    return jnp.where(pslot < C, hot.rank[jnp.clip(pslot, 0, C - 1)],
                     jnp.int32(H)).astype(jnp.int32)


def _mig_find(mig: MigRows, flat: jax.Array, valid: jax.Array):
    """Per-POSITION directory probe -> (found, rank, owner). One `hash_find`
    against the replicated migration directory; invalid positions probe the
    EMPTY sentinel and always miss. `flat` must be in the TABLE's key layout
    (same contract as `_hot_probe`)."""
    from ..tables.hash_table import hash_find
    C = mig.keys.shape[0]
    M = mig.ids.shape[0]
    if mig.keys.ndim == 2:
        from ..ops.id64 import PAIR_EMPTY
        probe = jnp.where(valid[:, None], flat, PAIR_EMPTY)
    else:
        probe = jnp.where(valid, flat, -1).astype(mig.keys.dtype)
    pslot = hash_find(mig.keys, probe, num_probes=HOT_NUM_PROBES)
    idx = jnp.clip(pslot, 0, C - 1)
    rank = jnp.where(pslot < C, mig.rank[idx], jnp.int32(M)).astype(jnp.int32)
    found = rank < M
    owner = jnp.where(found, mig.owners[jnp.clip(rank, 0, M - 1)],
                      jnp.int32(-1)).astype(jnp.int32)
    return found, rank, owner


def _route_owner(mig: MigRows, flat: jax.Array, valid: jax.Array,
                 S: int):
    """Per-position owner under the assignment indirection: the directory's
    assigned owner where it hits, the `id % S` hash home everywhere else.
    -> (owner (n,) int32 in [0, S], moved (n,) bool)."""
    if flat.ndim == 2:
        from ..ops.id64 import pair_mod
        home = pair_mod(flat, S).astype(jnp.int32)
    else:
        home = (flat % S).astype(jnp.int32)
    found, _rank, own = _mig_find(mig, flat, valid)
    moved = found & (own != home)
    owner = jnp.where(valid & found, own, jnp.where(valid, home, S))
    return owner, moved


def make_plan(spec: EmbeddingSpec, ids: jax.Array, *, axis: str = DATA_AXIS,
              capacity_factor: float = 0.0,
              hot: Optional[HotRows] = None,
              mig: Optional[MigRows] = None) -> ExchangePlan:
    """Dedup local ids, bucket by owner, exchange the id buckets (one all_to_all).

    Dedup and routing come out of ONE fused sort (`ops/dedup.unique_and_route`).
    `S == 1` is specialized at trace time: every id is local, so the buckets
    and the id all_to_all vanish — the plan serves the unique ids
    directly (the protocol's compute overhead at S=1 is the floor every
    multi-chip projection sits on; see PERF.md mesh1).

    `hot`: the table's replicated hot-row cache — hot positions are carved out
    of the exchange (module doc "HOT-ROW REPLICATION") and the plan carries
    their per-unique-slot cache rows in `hot_slot`. `mig`: the table's
    migration directory — cold positions route to their ASSIGNED owner
    instead of the `id % S` home (module doc "COLD-TAIL RE-SHARDING")."""
    with _trace.scope("exchange", "route"):
        S = jax.lax.axis_size(axis)
        flat = flatten_ids(spec, ids)
        n = flat.shape[0]
        if S == 1:
            if hot is not None:
                raise ValueError(
                    "hot-row replication needs S >= 2: on a 1-device mesh the "
                    "shard and the cache are the same memory, and two copies of "
                    "a row can only diverge (MeshTrainer disables hot_rows at "
                    "mesh size 1)")
            if mig is not None:
                raise ValueError(
                    "cold-tail re-sharding needs S >= 2: on a 1-device mesh "
                    "there is nowhere to migrate a row to (MeshTrainer disables "
                    "mig_rows at mesh size 1)")
            uniq = unique_with_counts(flat)
            valid = (uniq.counts > 0) & _id_valid(spec, uniq.unique_ids)
            recv_ids = uniq.unique_ids[None]
            recv_valid = valid[None]
            buckets = RoutedBuckets(
                bucket_ids=recv_ids, start=jnp.zeros((1,), jnp.uint32),
                count=jnp.sum(valid, dtype=jnp.int32)[None],
                positions=jnp.full((1,), n, jnp.int32),
                overflow=jnp.zeros((), jnp.int32))
            return ExchangePlan(uniq, buckets, recv_ids, recv_valid, n)
        uniq, buckets, cap, hot_slot, moved = _client_route(spec, flat, S,
                                                            capacity_factor, hot,
                                                            mig)
        # [BOUNDARY: was one RPC per owning server; now ONE ICI all_to_all —
        # empty bucket slots carry the EMPTY sentinel, so the receive side
        # derives validity from the ids and no bool mask rides the wire]
        recv_ids = _a2a("ids", buckets.bucket_ids, axis)
        recv_valid = bucket_validity(recv_ids)
    return ExchangePlan(uniq, buckets, recv_ids, recv_valid, cap, hot_slot,
                        0 if hot is None else hot.weights.shape[0], moved,
                        owner=_owner_view(recv_ids, recv_valid, n))


def _client_route(spec: EmbeddingSpec, flat: jax.Array, S: int,
                  capacity_factor: float, hot: Optional[HotRows] = None,
                  mig: Optional[MigRows] = None):
    """Per-table client-side dedup + owner routing: the plan minus its id
    exchange (shared by `make_plan` and the grouped fused exchange).
    -> (uniq, buckets, cap, hot_slot-or-None, mig_moved-or-None)."""
    with _trace.scope("exchange", "route"):
        n = flat.shape[0]
        valid = _id_valid(spec, flat)
        cap = _bucket_capacity(n, S, capacity_factor)
        if hot is None and mig is None:
            uniq, buckets = unique_and_route(flat, valid, S, cap)
            return uniq, buckets, cap, None, None
        # owner-assignment indirection (None keeps the plain `id % S` routing so
        # the mig-off program stays byte-identical to the pre-feature trace)
        owner = moved = None
        if mig is not None:
            owner, moved = _route_owner(mig, flat, valid, S)
        if hot is None:
            uniq, buckets = unique_and_route(flat, valid, S, cap, owner=owner)
            return uniq, buckets, cap, None, \
                carry_to_unique(uniq, moved.astype(jnp.int32), 0)
        H = hot.weights.shape[0]
        hr = _hot_probe(hot, flat, valid)
        # hot positions leave the exchange entirely: they route like invalid ids
        # (pseudo-owner S — no bucket slot, no wire bytes, no owner-shard load)
        # but keep their unique slots/counts for the local gather + reduced push
        uniq, buckets = unique_and_route(flat, valid & (hr >= H), S, cap,
                                         owner=owner)
        hot_slot = carry_to_unique(uniq, hr, H)
        mig_moved = None if moved is None else \
            carry_to_unique(uniq, (moved & (hr >= H)).astype(jnp.int32), 0)
        return uniq, buckets, cap, hot_slot, mig_moved


def grouped_make_plans(specs, ids_list, *, axis: str = DATA_AXIS,
                       capacity_factor: float = 0.0, hots=None, migs=None):
    """Routing plans for a DIM-GROUP of tables with ONE fused id all_to_all.

    Per-table dedup/bucketing is identical to `make_plan`; only the wire is
    shared — each table's (S, cap_t) bucket array rides as a fixed capacity
    segment of one concatenated array (`ops/dedup.concat_owner_buckets`), so
    the receive side recovers per-table buckets by slicing. `ids_list` must
    already be in each table's key layout (`adapt_batch_ids`). `hots`: one
    Optional[HotRows] per table (hot ids never enter the buckets). `migs`:
    one Optional[MigRows] per table (the owner-assignment indirection rides
    each table's own route)."""
    with _trace.scope("exchange", "route"):
        S = jax.lax.axis_size(axis)
        if hots is None:
            hots = [None] * len(specs)
        if migs is None:
            migs = [None] * len(specs)
        if S == 1:
            return [make_plan(spec, ids, axis=axis,
                              capacity_factor=capacity_factor, hot=hot, mig=mig)
                    for spec, ids, hot, mig in zip(specs, ids_list, hots, migs)]
        from ..ops.dedup import concat_owner_buckets, split_owner_buckets
        parts = []
        for spec, ids, hot, mig in zip(specs, ids_list, hots, migs):
            flat = flatten_ids(spec, ids)
            parts.append(_client_route(spec, flat, S, capacity_factor, hot, mig))
        wire_ids = concat_owner_buckets([b.bucket_ids for _, b, _, _, _ in parts])
        recv = _a2a("ids", wire_ids, axis)
        templates = [(cap, b.bucket_ids.ndim == 3, b.bucket_ids.dtype)
                     for _, b, cap, _, _ in parts]
        segs = split_owner_buckets(recv, templates)
        valids = [bucket_validity(seg) for seg in segs]
    return [ExchangePlan(uniq, buckets, seg, valid, cap, hs,
                         0 if hot is None else hot.weights.shape[0], mv,
                         owner=_owner_view(seg, valid, uniq.order.shape[0]))
            for (uniq, buckets, cap, hs, mv), seg, valid, hot
            in zip(parts, segs, valids, hots)]


def _flat_axis_index(axis) -> jax.Array:
    """This device's flattened position along `axis` (tuple axes compose
    row-major, matching the flattened collective order)."""
    if isinstance(axis, (tuple, list)):
        idx = jnp.zeros((), jnp.int32)
        for a in axis:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axis)


def exchange_load_stats(plan: ExchangePlan, *, axis: str = DATA_AXIS
                        ) -> Dict[str, jax.Array]:
    """Per-shard load accounting from one pull plan — the workload-skew
    counters Parallax (arXiv:1808.02621) argues partitioning must be tuned
    by, computed INSIDE the already-jitted step (pure array math on the
    plan; no host sync, no extra collective — the caller's stats psum
    carries them out).

    Each (S,) vector is this device's local contribution; after the stats
    psum (`reduce_metrics`) they read as:

    - ``shard_rows[d]``   — unique rows shard *d* serves this step (the
      wire/gather load; this source's routed-unique count per destination).
    - ``shard_positions[d]`` — duplicate-WEIGHTED id positions owned by
      shard *d* (the access skew `exchange.shard_imbalance` derives from —
      dedup hides it from shard_rows, real traffic concentrates it).
    - ``bucket_fill[s]``  — fraction of source shard *s*'s fullest outgoing
      a2a bucket (one-hot at this shard, so the psum assembles the
      per-source vector). The hash-routing bucket-occupancy/overflow
      predictor: raise `capacity_factor` while it nears 1.0.
    - ``owner_fill[d]`` / ``owner_full_steps[d]`` — only where the owner
      compacts what it receives (`plan.owner`): the valid ids shard *d*
      received over its working size W, and 1 where they did not fit and the
      shard took the full-size path this step (one-hot like `bucket_fill`).
      Folded to `exchange.owner_fill{table=}` (the fullest shard) and the
      `exchange.owner_full_steps{table=}` counter.

    `metrics.record_step_stats` folds these into labeled gauges
    (`exchange.shard_rows{table=,shard=}`) and the derived
    `exchange.shard_imbalance{table=}` histogram."""
    with _trace.scope("exchange", "stats"):
        S = jax.lax.axis_size(axis)
        # both per-destination vectors come with the route (`RoutedBuckets`:
        # counted over the sorted owners, S integers each)
        routed = plan.buckets.count
        positions = plan.buckets.positions
        occ = routed.max().astype(jnp.float32) / float(max(plan.cap, 1))
        me = _flat_axis_index(axis)
        fill = jnp.zeros((S,), jnp.float32).at[me].set(occ)
        out = {"shard_rows": routed, "shard_positions": positions,
               "bucket_fill": fill}
        view = plan.owner
        if view is not None:
            W = view.valid.shape[0]
            out["owner_fill"] = jnp.zeros((S,), jnp.float32).at[me].set(
                view.total.astype(jnp.float32) / float(W))
            out["owner_full_steps"] = jnp.zeros((S,), jnp.int32).at[me].set(
                (~view.fits).astype(jnp.int32))
        return out


def _serve_rows(spec: EmbeddingSpec, state: EmbeddingTableState,
                plan: ExchangePlan, *, train: bool, axis: str,
                fmt: str = "fp32", return_stash: bool = False,
                packed=None, share: bool = True):
    """Server side of a pull: gather this shard's rows for the received ids
    -> (state, (S, cap, width) rows in `fmt`, stash, owner plan).
    With a migration directory, received MIGRATED ids (the indirection routed
    them here because this shard is their assigned owner) read from the annex
    instead of the main table — and are masked out of the main-table probe,
    so a hash table never lazily re-inserts a row that lives in the annex.

    `fmt` is the wire format of the RETURNED buffer. "fp32" returns the raw
    (S, cap, dim) rows — the pre-round-13 contract, trace-identical. A
    narrow format encodes HERE, at the owner edge, so the pull all_to_all
    moves int8/bf16 with the scales in-band (`ops/wire.pack_inband`) — and
    when the table carries error-feedback residuals (`state.ef`), each
    served row ships q(w + ef) and the shard keeps ef' = (w + ef) - deq(q):
    server-side compression EF (dist-EF-SGD), sharded like the slots so the
    residual follows its row through checkpoints. Annex (migrated) rows
    quantize WITHOUT a residual — their owner is the assigned shard, not
    the hash home the ef array is laid out for.

    `return_stash=True` (the pipelined prefetch) fills the third value: the
    PRE-serve residual gathered per recv slot ((S, cap, dim) f32; None when
    no EF ran) — `grouped_conflict_patch` replays it against the post-apply
    weights to reproduce exactly what a serial serve would have shipped.

    `packed`: the column layout of a table whose shard is in the scan's
    packed form (by it a shard held four rows a lane line is known,
    `ops/sparse.in_lines`). `share`: the caller's apply of the SAME step
    follows with nothing written to the shard between
    (`grouped_lookup_train`): the serve of a packed array table then plans
    the step (module doc "THE OWNER PLANS ONCE A STEP") and the fourth value
    is the `PackedPlan` for `_owner_apply`; None otherwise (the pipelined
    prefetch), and for a hash table, which probes per slot either way.
    `exchange.owner_plans{path=}` counts which, once a table a trace.

    Where the plan holds an `OwnerView` the work (`_serve_flat`) runs over its
    W compacted slots and the rows (and the stash) go back to the bucket
    layout by S masked block copies (`_expand`); in a step whose received ids
    do not fit, the same function runs over all S * cap slots, per slot and
    with no plan (module doc "WHAT THE OWNER WORKS OVER")."""
    share = share and packed is not None and not spec.use_hash_table
    _metrics.observe("exchange.owner_plans", 1, "sum",
                     labels={"path": "shared" if share else "per_slot"})
    with _trace.scope("exchange", "owner_serve"):
        S = jax.lax.axis_size(axis)
        view = plan.owner

        def serve(ids, valid, share):
            return _serve_flat(spec, state, ids, valid, S, train=train,
                               fmt=fmt, return_stash=return_stash,
                               packed=packed, share=share)

        if view is None:
            writes, rows, stash, owner_plan = serve(*_flat_recv(plan), share)
        else:
            def compact():
                writes, rows, stash, owner_plan = serve(view.ids, view.valid,
                                                        share)

                def back(y):
                    return _expand(y, view, plan.cap).reshape(
                        (-1,) + y.shape[1:])
                return writes, back(rows), \
                    None if stash is None else back(stash), owner_plan

            def full_size():
                # the parent's program; both conditionals branch on the same
                # `view.fits`, so the apply never reads this step's plan
                return serve(*_flat_recv(plan), False)[:3] + (
                    _no_plan(state.weights, view,
                             _width(spec, state.weights, packed))
                    if share else None,)
            writes, rows, stash, owner_plan = jax.lax.cond(
                view.fits, compact, _full_size_scope(full_size))
        if spec.use_hash_table and train:
            # overflow is replicated table-level state: psum the per-shard
            # increment (out here: the branch taken above is per shard)
            delta = jax.lax.psum(writes["overflow"] - state.overflow, axis)
            writes["overflow"] = state.overflow + delta
        state = state.replace(**writes)
        rows = rows.reshape(S, plan.cap, -1)
        if stash is not None:
            stash = stash.reshape(S, plan.cap, spec.output_dim)
        return state, rows, stash, owner_plan


def _width(spec: EmbeddingSpec, weights: jax.Array, packed) -> Optional[int]:
    """Columns of a row of the shard `weights` in the scan's packed form
    under the layout `packed`; None where the shard is not packed. A packed
    shard read with no layout raises: its form is known by the layout alone
    (`ops/sparse.in_lines`), and read as split it would be wrong rows."""
    if packed is not None:
        return packed_width(spec.output_dim, packed)
    if weights.shape[1] != spec.output_dim:
        raise ValueError(
            f"table {spec.name!r}: a shard of {weights.shape[1]} columns for "
            f"dim {spec.output_dim} is packed and is read with its layout "
            "(packed= / packed_list=)")
    return None


def _no_plan(packed: jax.Array, view: OwnerView, width: int) -> PackedPlan:
    """Zeros in the shapes of the plan that the compact serve makes of
    `view`: what the full-size branch of the serve's conditional returns in
    its place (a conditional's branches return one shape) and the apply's
    full-size branch never reads."""
    like = jax.ShapeDtypeStruct
    shapes = jax.eval_shape(functools.partial(plan_packed_rows, width=width),
                            like(packed.shape, packed.dtype),
                            like(view.ids.shape, view.ids.dtype))
    return jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, x.dtype),
                                  shapes)


def _full_size_scope(fn):
    """`fn` under the stage name `exchange.full_size`: on a device profile the
    ops of a step that did not fit the working size sit under it, the compact
    path's ops do not."""
    def scoped():
        with _trace.scope("exchange", "full_size"):
            return fn()
    return scoped


def _serve_flat(spec: EmbeddingSpec, state: EmbeddingTableState,
                flat_recv: jax.Array, flat_valid: jax.Array, S: int, *,
                train: bool, fmt: str, return_stash: bool, packed,
                share: bool):
    """`_serve_rows` over one flat run of received slots, whatever its length
    (the compacted view or the whole receive buffer); no collective. ->
    (the state fields the serve wrote: `keys`/`overflow` on a hash insert,
    `ef` under error feedback; (m, width) rows in `fmt`; the (m, dim)
    pre-serve residuals or None; with `share`, an array table's shard in the
    packed layout, the step's `PackedPlan` over these slots, else None)."""
    pair = flat_recv.ndim == 2  # split-pair ids
    need_ef = train and fmt != "fp32" and state.ef is not None
    ef_idx = owner_plan = None
    writes = {}
    mig = state.mig
    m_found = None
    if mig is not None:
        m_found, m_rank, _ = _mig_find(mig, flat_recv, flat_valid)
        main_valid = flat_valid & ~m_found
    else:
        main_valid = flat_valid
    if spec.use_hash_table:
        if pair:
            from ..ops.id64 import PAIR_EMPTY
            probe = jnp.where(main_valid[:, None], flat_recv, PAIR_EMPTY)
        else:
            probe = jnp.where(main_valid, flat_recv, -1)
        if train:
            from ..tables.hash_table import hash_lookup_train
            inserted, rows = hash_lookup_train(state, probe,
                                               out_dim=spec.output_dim,
                                               layout=packed)
            writes.update(keys=inserted.keys, overflow=inserted.overflow)
            if need_ef:
                # post-insert probe: the residual lives at the row's slot
                # (invalid/annex positions probe EMPTY -> miss -> OOB index)
                from ..tables.hash_table import hash_find
                capacity = inserted.keys.shape[0]
                slot = hash_find(inserted.keys, probe)
                ef_idx = jnp.where(slot < capacity, slot, capacity)
        else:
            from ..tables.hash_table import hash_lookup
            rows = hash_lookup(state, probe)
    else:
        local_rows = jnp.where(main_valid, flat_recv // S, -1)
        if share:
            # the apply's dedup and routing of these slots and ONE gather of
            # the unique packed rows; every slot reads its weight columns
            # from that small array, an invalid one a row past the valid
            # prefix: 0. The slots to leave out (empty, or an annex row's, as
            # the apply zeroes their counts) are the rows at -1: no mask to
            # sum, the plan's counts are the dedup's own
            with _trace.scope("sparse", "pull"):
                owner_plan = plan_packed_rows(
                    state.weights, local_rows,
                    width=_width(spec, state.weights, packed))
                rows = lookup_rows(owner_plan.rows[:, :spec.output_dim],
                                   owner_plan.uniq.inverse)
        else:
            # in the packed weights+slots layout inside train_many's scan
            # (`ops/sparse.packed_layout`), served with no apply of the same
            # step to share with (the pipelined prefetch): the full packed
            # row once a slot, the weight columns sliced out
            rows = _weight_rows(spec, state.weights, local_rows, packed)
        if need_ef:
            ef_idx = jnp.where(main_valid, flat_recv // S,
                               state.ef.shape[0]).astype(jnp.int32)
    if m_found is not None:
        M = mig.weights.shape[0]
        arows = lookup_rows(mig.weights, jnp.where(m_found, m_rank, M))
        rows = jnp.where(m_found[:, None], arows.astype(rows.dtype), rows)
    if fmt == "fp32":
        return writes, rows, None, owner_plan
    # owner-edge encode: the pull a2a operand is already int8/bf16
    from ..ops import wire as wire_mod
    x = rows.astype(jnp.float32)
    if not need_ef:
        return writes, wire_mod.pack_inband(x, fmt), None, owner_plan
    # invalid/annex slots index OOB: the gather fills 0, the scatter
    # drops. Duplicate recv slots (one id requested by several sources)
    # gather the same w+ef and write the same residual — deterministic.
    ef_prev = state.ef.at[ef_idx].get(mode="fill", fill_value=0) \
        .astype(jnp.float32)
    x = x + ef_prev
    enc = wire_mod.pack_inband(x, fmt)
    ef_new = x - wire_mod.unpack_inband(enc, spec.output_dim, fmt)
    writes["ef"] = state.ef.at[ef_idx].set(ef_new.astype(state.ef.dtype),
                                           mode="drop")
    return writes, enc, ef_prev if return_stash else None, owner_plan


def _merge_hot_rows(plan: ExchangePlan, uniq_rows: jax.Array,
                    hot: Optional[HotRows]) -> jax.Array:
    """Overlay the LOCAL hot-cache gather onto the exchange's unique rows
    (cold left zeros at hot slots — their pseudo-owner S has no bucket)."""
    if hot is None or plan.hot_slot is None:
        return uniq_rows
    H = hot.weights.shape[0]
    hrows = hot.weights.at[plan.hot_slot].get(mode="fill", fill_value=0)
    return jnp.where((plan.hot_slot < H)[:, None],
                     hrows.astype(uniq_rows.dtype), uniq_rows)


def _hot_pull_stats(spec: EmbeddingSpec, plan: ExchangePlan, flat: jax.Array,
                    fmt: str) -> Dict[str, jax.Array]:
    """Per-step hot-cache accounting for the stats dict (psum'd like the rest):
    `hot_hits` (positions served locally — `metrics.record_step_stats` derives
    `hot.hit_ratio{table=}` against `pull_indices`), `hot_unique` (rows that
    skipped the wire), and `hot_bytes_saved` — unique rows x the static
    per-row wire cost (id lanes + pulled row + pushed grad+counts) the 3-a2a
    round trip would have charged for them."""
    with _trace.scope("exchange", "stats"):
        from ..ops import wire as wire_mod
        H = plan.hot_rows
        hm = (plan.hot_slot < H) & (plan.uniq.counts > 0)
        hot_unique = jnp.sum(hm).astype(jnp.int32)
        hot_hits = jnp.sum(jnp.where(hm, plan.uniq.counts, 0)).astype(jnp.int32)
        w = jnp.dtype(wire_mod.wire_dtype(fmt)).itemsize
        pair = flat.ndim == 2
        per_row = (wire_mod.id_wire_itemsize(pair, jnp.dtype(flat.dtype).itemsize)
                   + wire_mod.rows_wire_width(spec.output_dim, fmt) * w
                   + wire_mod.grads_wire_width(spec.output_dim, fmt) * w)
        return {"hot_unique": hot_unique, "hot_hits": hot_hits,
                "hot_bytes_saved": hot_unique.astype(jnp.float32)
                * float(per_row)}


def _mig_pull_stats(plan: ExchangePlan) -> Dict[str, jax.Array]:
    """Per-step re-sharding accounting (psum'd like the rest): `mig_unique`
    (rows the directory routed off their hash home this step) and `mig_hits`
    (duplicate-weighted positions those rows absorbed) —
    `metrics.record_step_stats` derives `placement.moved_ratio{table=}`."""
    with _trace.scope("exchange", "stats"):
        mm = (plan.mig_moved > 0) & (plan.uniq.counts > 0)
        return {"mig_unique": jnp.sum(mm).astype(jnp.int32),
                "mig_hits": jnp.sum(jnp.where(mm, plan.uniq.counts, 0))
                .astype(jnp.int32)}


# oelint: hot-path device_get=0
def _hot_apply(spec: EmbeddingSpec, optimizer, hot: HotRows,
               plan: ExchangePlan, g: jax.Array, axis,
               fmt: str = "fp32") -> HotRows:
    """Backward for the hot set: scatter the per-unique grad sums into the
    compact (H, dim) hot aggregate (SparCML's dense-ified hot payload — the
    shape collectives handle cheaply), ONE psum across the data axis, then
    the fused optimizer runs on every replica with the replicated slots. The
    update is identical everywhere (same reduced inputs, same math), so
    replicas never diverge; rows with count 0 stay bit-identical
    (`SparseOptimizer.apply`).

    Parity note: counts are int32 — exact under any reduction order. For the
    f32 grads, XLA's all-reduce on the CPU backend (the parity suite's 8
    virtual devices) folds replica partials in source order — exactly the
    order the cold owner's sorted-segment reduction applies over its
    source-major (S, cap) receive buffer — so fp32-wire training is
    bit-exact hot-on vs hot-off there (tests/test_hot.py pins it). A backend
    whose all-reduce associates differently keeps equality up to
    reassociation of the S per-replica partials (each partial is itself the
    bit-exact client pre-sum).

    `fmt` narrows the dense grad reduction (`MeshTrainer(hot_wire=...)`):
    bf16 runs the same one-psum plan on a bf16 aggregate; int8 runs the
    two-stage quantized reduce (EQuARX's in-collective scheme) — encode the
    padded (Hp, W) aggregate, all_to_all so shard r holds every replica's
    rows [r*Hp/S, (r+1)*Hp/S), decode + fp32-sum, re-encode the partial
    sums, all_gather(tiled) the (Hp/S, W) results back to everyone. Every
    replica decodes the SAME gathered bits, so the replicated slots still
    never diverge. Counts stay an exact int32 psum in every format."""
    with _trace.scope("exchange", "owner_apply"):
        H = hot.weights.shape[0]
        hm = plan.hot_slot < H
        tgt = jnp.where(hm, plan.hot_slot, H)
        hg = jnp.zeros((H, spec.output_dim), jnp.float32).at[tgt].set(
            g.astype(jnp.float32), mode="drop", unique_indices=True)
        hc = jnp.zeros((H,), jnp.int32).at[tgt].set(
            jnp.where(hm, plan.uniq.counts, 0).astype(jnp.int32),
            mode="drop", unique_indices=True)
        if fmt == "fp32":
            tg = jax.lax.psum(hg, axis)
        elif fmt == "bf16":
            tg = jax.lax.psum(hg.astype(jnp.bfloat16), axis).astype(jnp.float32)
        else:
            from ..ops import wire as wire_mod
            S = jax.lax.axis_size(axis)
            Hp = -(-H // S) * S
            hp = (jnp.zeros((Hp, spec.output_dim), jnp.float32).at[:H].set(hg)
                  if Hp != H else hg)
            enc = wire_mod.pack_inband(hp, "int8")              # (Hp, W)
            W = enc.shape[1]
            parts = _a2a("grads", enc.reshape(S, Hp // S, W), axis)
            dec = wire_mod.unpack_inband(
                parts.reshape(-1, W), spec.output_dim,
                "int8").reshape(S, Hp // S, spec.output_dim)
            partial = jnp.sum(dec, axis=0)                      # this shard's rows
            enc2 = wire_mod.pack_inband(partial, "int8")        # (Hp/S, W)
            full = jax.lax.all_gather(enc2, axis, tiled=True)   # (Hp, W)
            tg = wire_mod.unpack_inband(full, spec.output_dim, "int8")[:H]
        tc = jax.lax.psum(hc, axis)
        new_w, new_s = optimizer.apply(hot.weights.astype(jnp.float32),
                                       hot.slots, tg, tc)
        return hot.replace(
            weights=new_w.astype(hot.weights.dtype),
            slots={k: new_s[k].astype(hot.slots[k].dtype) for k in hot.slots})


def _reassemble(plan: ExchangePlan, rows: jax.Array, out_shape,
                dim: int, axis: str,
                hot: Optional[HotRows] = None) -> jax.Array:
    """Client side of a pull whose `rows` are raw fp32-wire rows: rows back
    over the a2a, read into unique order (`_from_buckets`), expand
    duplicates, overlay the local hot-cache gather. At S=1 the served rows
    ARE the unique rows (make_plan's identity plan) — no a2a, no buckets."""
    with _trace.scope("exchange", "reassemble"):
        if jax.lax.axis_size(axis) == 1:
            uniq_rows = rows[0]
        else:
            back = _a2a("rows", rows, axis)
            uniq_rows = _from_buckets(back, plan)
        uniq_rows = _merge_hot_rows(plan, uniq_rows, hot)
        out = jnp.take(uniq_rows, plan.uniq.inverse, axis=0)
        return out.reshape(out_shape + (dim,))


# `# oelint: hot-path device_get=0` marks pure jit-side protocol code for the
# host-sync lint pass (`make lint`): ANY device->host sync added inside —
# jax.device_get, block_until_ready, np.asarray of a device value, float()
# of a tracer — fails CI. The exchange functions below all carry it.
# oelint: hot-path device_get=0
def sharded_lookup(
    spec: EmbeddingSpec,
    state: EmbeddingTableState,
    ids: jax.Array,
    *,
    axis: str = DATA_AXIS,
    capacity_factor: float = 0.0,
) -> jax.Array:
    """Read-only pull (serving/eval; reference `read_only_pull` handler — never
    inserts, absent hash ids return zeros). Hot rows read from the replicated
    cache, migrated rows from their assigned owner's annex — the home copies
    are stale while either placement is active."""
    ids = adapt_batch_ids(spec, state, ids)
    plan = make_plan(spec, ids, axis=axis, capacity_factor=capacity_factor,
                     hot=state.hot, mig=state.mig)
    _, rows, _, _ = _serve_rows(spec, state, plan, train=False, axis=axis)
    return _reassemble(plan, rows, _out_shape(spec, ids), spec.output_dim,
                       axis, hot=state.hot)


def _apply_load_stats(load: Dict[str, jax.Array], axis) -> Dict[str, jax.Array]:
    """This shard's apply load (`ops/sparse.py` "WHAT THE APPLY WORKS OVER":
    `apply_fill`, `apply_full_steps`) one-hot at this shard, like
    `owner_fill`: the stats psum assembles the per-shard vectors."""
    with _trace.scope("exchange", "stats"):
        S = jax.lax.axis_size(axis)
        me = _flat_axis_index(axis)
        return {k: jnp.zeros((S,), v.dtype).at[me].set(v)
                for k, v in load.items()}


def _owner_apply(spec: EmbeddingSpec, state: EmbeddingTableState, optimizer,
                 plan: ExchangePlan, recv: jax.Array, decode, S: int,
                 packed=None):
    """Server-side tail of a push over what this shard RECEIVED: `recv` is
    the (S, cap, width) grad payload as it left the all_to_all, `decode` maps
    (m, width) payload rows to their (grads, exact counts). Where the plan
    holds an `OwnerView` the payload is compacted like the ids were (the same
    S block copies at the same offsets, so slot i of the view's ids meets its
    own gradient) and decode + `_apply_unique` run over W slots; in a step
    whose received ids do not fit they run over all S * cap. Either way the
    valid slots keep their source-major order, so the cross-source reduction
    adds in one order and the result is the same bit for bit. Where the
    serve of this step planned (`plan.owner_plan`), the apply over the same
    slots takes the plan: the compact branch, or the only one where nothing
    is compacted; the full-size branch dedups and gathers for itself, as the
    serve's did. -> (state, the main table's apply load, `_apply_unique`)."""
    view = plan.owner

    def apply(ids, payload, owner_plan=None):
        rg, rc = decode(payload)
        new, load = _apply_unique(spec, state, optimizer, ids, rg, rc, S,
                                  packed=packed, plan=owner_plan)
        return new.weights, new.slots, \
            None if new.mig is None else (new.mig.weights, new.mig.slots), \
            load

    def all_slots():
        return _flat_recv(plan)[0], recv.reshape(-1, recv.shape[-1])

    if view is None:
        weights, slots, annex, load = apply(*all_slots(), plan.owner_plan)
    else:
        weights, slots, annex, load = jax.lax.cond(
            view.fits,
            lambda: apply(view.ids, _compact(recv, view.offsets,
                                             view.valid.shape[0]),
                          plan.owner_plan),
            _full_size_scope(lambda: apply(*all_slots())))
    state = state.replace(weights=weights, slots=slots)
    if annex is not None:
        state = state.replace(mig=state.mig.replace(weights=annex[0],
                                                    slots=annex[1]))
    return state, load


def _apply_unique(spec: EmbeddingSpec, state: EmbeddingTableState, optimizer,
                  rids: jax.Array, rg: jax.Array, rc: jax.Array, S: int,
                  packed=None, plan: Optional[PackedPlan] = None):
    """Server-side tail of a push: cross-source re-dedup (the MPSC reducer,
    `MpscGradientReducer.h`) + ONE fused optimizer apply per unique row.
    `rids`/`rg`/`rc` are the received flat ids, grads and exact duplicate
    counts (count 0 = empty/invalid slot). Received MIGRATED ids apply into
    the annex (this shard is their assigned owner) through the identical
    sparse-apply machinery — the received buffer keeps its source-major
    order, so the per-row reduction is bit-identical to the home shard's.
    `plan`: what the serve of this step made of the same `rids` (module doc
    "THE OWNER PLANS ONCE A STEP"): the packed apply then sums `rg` and `rc`
    over the plan's segments and updates the plan's rows.
    -> (state, the main table's apply load: `ops/sparse.py` "WHAT THE APPLY
    WORKS OVER"; the annex's apply works the same way and is not counted)."""
    with _trace.scope("exchange", "owner_apply"):
        mig = state.mig
        if mig is not None:
            m_found, m_rank, _ = _mig_find(mig, rids, rc > 0)
            M = mig.weights.shape[0]
            mweights, mslots = sparse_apply_dense_table(
                optimizer, mig.weights, mig.slots,
                jnp.where(m_found, m_rank, M), rg,
                pre_counts=jnp.where(m_found, rc, 0))
            state = state.replace(mig=mig.replace(weights=mweights,
                                                  slots=mslots))
            # migrated ids are ANNEX rows: drop them from the main-table apply
            # (count 0 leaves a row bit-identical — SparseOptimizer.apply) so an
            # array table never scatters into the alien row `id // S` points at
            rc = jnp.where(m_found, 0, rc)
        pair = rids.ndim == 2
        if spec.use_hash_table:
            from ..tables.hash_table import hash_find
            if pair:
                from ..ops.id64 import PAIR_EMPTY
                probe = jnp.where((rc > 0)[:, None], rids, PAIR_EMPTY)
            else:
                probe = jnp.where(rc > 0, rids, -1).astype(state.keys.dtype)
            slot = hash_find(state.keys, probe)
            capacity = state.keys.shape[0]
            pre_counts = jnp.where((slot < capacity) & (rc > 0), rc, 0)
            rows, counts = jnp.clip(slot, 0, capacity), pre_counts
        else:
            rows = jnp.where(rc > 0, rids // S,
                             _shard_rows(spec, state.weights, packed))
            counts = rc
        if packed is not None:
            from ..ops.sparse import sparse_apply_packed_table
            new_packed, load = sparse_apply_packed_table(
                optimizer, state.weights, packed, spec.output_dim, rows, rg,
                pre_counts=counts, plan=plan)
            return state.replace(weights=new_packed), load
        weights, slots, load = sparse_apply_dense_table(
            optimizer, state.weights, state.slots, rows, rg, pre_counts=counts,
            with_load=True)
        return state.replace(weights=weights, slots=slots), load


# ---------------------------------------------------------------------------
# The training exchange, one GROUP of tables at a time: tables sharing an
# embedding dim fuse their three all_to_alls (ids / rows / grads+counts) into
# one each, and the row and grad payloads optionally travel quantized
# (`ops/wire.py`). Dedup/routing, serving and the optimizer apply run per
# table — grouping shares the wire, never the math: at fp32 wire a table
# trains bit-identically whichever tables share its group
# (`tests/test_wire.py` pins it against one group per table). Formats are per
# table and groups are keyed on (dim, fmt) — `split_wire_groups` subdivides
# the model's dim-groups so every group the protocol below sees is
# format-uniform (its encoded widths stay uniform and the concat still fuses
# one a2a).
# ---------------------------------------------------------------------------


def split_wire_groups(groups, fmt_for):
    """Split dim-groups by per-table wire format: tables sharing (dim, fmt)
    stay fused on one a2a pair; a mixed-format dim yields one subgroup per
    format, in first-appearance order with declaration order kept inside.
    A format-uniform group returns unchanged — the identity for every
    single-format config, which is what keeps their HLO byte-identical to
    the round-13 grouping."""
    out = []
    for g in groups:
        by_fmt = {}
        for n in g:
            by_fmt.setdefault(fmt_for(n), []).append(n)
        out.extend(by_fmt.values())
    return out


def _rows_round_trip(rows_list, dim: int, fmt: str, axis):
    """ONE all_to_all for a dim-group's served rows. fp32 keeps the round-6
    flow (mixed table dtypes promote at the concat, then widen); narrow
    formats ship the buffers `_serve_rows` already encoded straight through
    the collective. -> (what came back, STILL ENCODED and in the bucket
    layout (S, sum of caps, width); `decode`: (m, width) rows -> (m, dim)
    float32). Decoding is row by row, so the client reads its buckets back
    into unique order first (`_from_buckets`) and decodes n rows, not
    S x cap; each table then casts to its own dtype (exact for bf16-kept
    tables)."""
    from ..ops import wire as wire_mod
    stacked = jnp.concatenate(rows_list, axis=1)
    if fmt == "fp32":
        S = stacked.shape[0]
        enc = wire_mod.encode_rows(stacked.reshape(-1, dim), fmt)
        stacked = enc.reshape(S, -1, enc.shape[-1])
    back = _a2a("rows", stacked, axis)
    return back, lambda flat: wire_mod.decode_rows(flat, dim, fmt)


# oelint: hot-path device_get=0
def grouped_lookup_train(
    specs, states, ids_list, *,
    axis: str = DATA_AXIS,
    capacity_factor: float = 0.0,
    wire: Optional[str] = None,
    load_stats: bool = True,
    packed_list=None,
):
    """Fused training pull for one dim-group. Returns (new_states, outs,
    stats_list, plans) — parallel lists in the input order; feed `plans` to
    `grouped_apply_gradients` for the same batch. `load_stats=False` drops
    the per-shard skew vectors (`exchange_load_stats`) from each table's
    stats dict. `packed_list`: as `grouped_apply_gradients`'s, the column
    layout of each table whose shard is in the scan's packed form; such an
    array table's owner plans its step here and the plan rides `plans` to
    the apply (module doc "THE OWNER PLANS ONCE A STEP")."""
    from ..ops import wire as wire_mod
    S = jax.lax.axis_size(axis)
    dim = specs[0].output_dim
    for spec in specs:
        if spec.output_dim != dim:
            raise ValueError(
                f"grouped exchange needs one embedding dim per group: "
                f"{spec.name!r} has dim {spec.output_dim}, group has {dim}")
    ids_list = [adapt_batch_ids(spec, state, ids)
                for spec, state, ids in zip(specs, states, ids_list)]
    hots = [state.hot for state in states]
    plans = grouped_make_plans(specs, ids_list, axis=axis,
                               capacity_factor=capacity_factor, hots=hots,
                               migs=[state.mig for state in states])
    fmt = wire_mod.wire_format(wire) if S > 1 else "fp32"
    if packed_list is None:
        packed_list = [None] * len(specs)
    new_states, rows_list, planned = [], [], []
    for spec, state, plan, packed in zip(specs, states, plans, packed_list):
        # narrow formats encode PER TABLE at the owner edge (`_serve_rows`)
        # so each table's error-feedback residuals see their own rows; the
        # encoded widths are uniform across the dim-group, so the concat
        # below still fuses ONE a2a
        state, rows, _, owner_plan = _serve_rows(
            spec, state, plan, train=True, axis=axis, fmt=fmt, packed=packed)
        new_states.append(state)
        rows_list.append(rows)
        planned.append(plan._replace(owner_plan=owner_plan))
    plans = planned
    if S == 1:
        outs = [_reassemble(plan, rows, _out_shape(spec, ids),
                            spec.output_dim, axis)
                for spec, ids, plan, rows
                in zip(specs, ids_list, plans, rows_list)]
    else:
        back, decode = _rows_round_trip(rows_list, dim, fmt, axis)
        outs, off = [], 0
        for spec, ids, plan, hot in zip(specs, ids_list, plans, hots):
            seg = back[:, off:off + plan.cap]
            off += plan.cap
            with _trace.scope("exchange", "reassemble"):
                uniq_rows = decode(_from_buckets(seg, plan))
                uniq_rows = _merge_hot_rows(plan, uniq_rows, hot)
                out = jnp.take(uniq_rows, plan.uniq.inverse, axis=0)
                outs.append(out.astype(spec.dtype).reshape(
                    _out_shape(spec, ids) + (spec.output_dim,)))
    stats_list = []
    for spec, ids, plan in zip(specs, ids_list, plans):
        st = {
            "pull_indices": jnp.asarray(ids_positions(spec, ids), jnp.int32),
            "pull_unique": plan.uniq.num_unique,
            "pull_overflow": plan.buckets.overflow,
        }
        if plan.hot_slot is not None:
            st.update(_hot_pull_stats(spec, plan, flatten_ids(spec, ids),
                                      fmt))
        if plan.mig_moved is not None:
            st.update(_mig_pull_stats(plan))
        if load_stats:
            st.update(exchange_load_stats(plan, axis=axis))
        stats_list.append(st)
    return new_states, outs, stats_list, plans


# oelint: hot-path device_get=0
def grouped_apply_gradients(
    specs, states, optimizers, ids_list, grads_list, *,
    axis: str = DATA_AXIS,
    capacity_factor: float = 0.0,
    plans=None,
    packed_list=None,
    wire: Optional[str] = None,
    hot_wire: Optional[str] = None,
):
    """Fused push + update for one dim-group: ONE all_to_all carries every
    table's grads+counts (counts bit-exact in wire lanes, grads optionally
    quantized — int8 with stochastic rounding and in-band scales, dequantized
    here at the receiving edge, so the fused optimizer apply and table
    storage keep their full-precision dtypes). `hot_wire` selects the
    hot-row reduction's format separately (defaults to `wire`).
    Returns (new_states, stats_list)."""
    from ..ops import wire as wire_mod
    S = jax.lax.axis_size(axis)
    dim = specs[0].output_dim
    fmt = wire_mod.wire_format(wire) if S > 1 else "fp32"
    hot_fmt = (wire_mod.wire_format(hot_wire) if hot_wire is not None
               else fmt)
    if plans is None:
        ids_list = [adapt_batch_ids(spec, state, ids)
                    for spec, state, ids in zip(specs, states, ids_list)]
        plans = grouped_make_plans(specs, ids_list, axis=axis,
                                   capacity_factor=capacity_factor,
                                   hots=[state.hot for state in states],
                                   migs=[state.mig for state in states])
    if packed_list is None:
        packed_list = [None] * len(specs)
    # client side: per-table duplicate pre-sum into the unique slots
    gs, counts_list = [], []
    for spec, plan, grads in zip(specs, plans, grads_list):
        with _trace.scope("exchange", "route"):
            g = plan.uniq.segment_reduce(grads.reshape(-1, dim))
            valid = (plan.uniq.counts > 0) & _id_valid(spec,
                                                       plan.uniq.unique_ids)
            gs.append(g)
            counts_list.append(jnp.where(valid, plan.uniq.counts, 0)
                               .astype(jnp.int32))
    # hot sets: reduced data-parallel, never on the fused wire (_hot_apply)
    hot_list = [
        (None if plan.hot_slot is None or state.hot is None
         else _hot_apply(spec, opt, state.hot, plan, g, axis, fmt=hot_fmt))
        for spec, state, opt, plan, g
        in zip(specs, states, optimizers, plans, gs)]
    states = [state if hot is None else state.replace(hot=hot)
              for state, hot in zip(states, hot_list)]
    new_states, stats_list = [], []
    if S == 1:
        for spec, state, opt, plan, g, rc, packed in zip(
                specs, states, optimizers, plans, gs, counts_list,
                packed_list):
            new, load = _apply_unique(
                spec, state, opt, plan.uniq.unique_ids, g, rc, S,
                packed=packed, plan=plan.owner_plan)
            new_states.append(new)
            stats_list.append({"push_overflow": plan.buckets.overflow,
                               **_apply_load_stats(load, axis)})
        return new_states, stats_list
    payloads = [_to_buckets(
        wire_mod.encode_grads(g, rc, fmt, stochastic=(fmt == "int8")), plan)
                for plan, g, rc in zip(plans, gs, counts_list)]
    recv = _a2a("grads", jnp.concatenate(payloads, axis=1), axis)
    off = 0
    for spec, state, opt, plan, g, packed in zip(
            specs, states, optimizers, plans, gs, packed_list):
        seg = recv[:, off:off + plan.cap]
        off += plan.cap

        def decode(flat, dtype=g.dtype):
            rg32, rc = wire_mod.decode_grads(flat, dim, fmt)
            return rg32.astype(dtype), rc
        new, load = _owner_apply(spec, state, opt, plan, seg, decode, S,
                                 packed=packed)
        new_states.append(new)
        stats_list.append({"push_overflow": plan.buckets.overflow,
                           **_apply_load_stats(load, axis)})
    return new_states, stats_list


# ---------------------------------------------------------------------------
# Split-phase exchange for the software-pipelined train loop
# (`MeshTrainer(pipeline_steps=True)`): `grouped_prefetch` issues batch t+1's
# id plane + speculative weight plane with no data dependency on batch t's
# gradients (XLA overlaps its a2as with batch t's dense compute),
# `grouped_conflict_patch` re-gathers only the rows batch t's push actually
# updated, and `grouped_finalize_pull` runs the client tail (hot overlay +
# duplicate expansion) at consume time. fp32 wire stays bit-exact to the
# serial `grouped_lookup_train` flow; narrow wire re-encodes patched rows
# with the same deterministic codec the serve uses AND — when the table
# carries error feedback — replays the pre-serve residual stash
# (`ExchangePlan.ef_stash`) against the post-apply weights, so the int8 wire
# is bit-exact to the serial schedule too: patched rows decode to exactly
# what a serial serve would have shipped, and the post-patch residuals match
# the serial EF state bit for bit.
# ---------------------------------------------------------------------------


def plan_carry(plan: ExchangePlan) -> dict:
    """ExchangePlan -> a dict of ARRAYS safe to ride a `lax.scan` carry (the
    static ints `cap`/`hot_rows` would be traced into the carry and break the
    plan's shape-level uses; they travel out of band — `plan_from_carry`
    re-attaches them from the prologue's trace-time plan)."""
    return {"uniq": plan.uniq, "buckets": plan.buckets,
            "recv_ids": plan.recv_ids, "recv_valid": plan.recv_valid,
            "hot_slot": plan.hot_slot, "mig_moved": plan.mig_moved,
            "ef_stash": plan.ef_stash, "owner": plan.owner}


def plan_from_carry(carry: dict, cap: int, hot_rows: int) -> ExchangePlan:
    """Inverse of `plan_carry`: rebuild the plan around the scan body's
    carried arrays with the trace-time static ints re-attached."""
    return ExchangePlan(carry["uniq"], carry["buckets"], carry["recv_ids"],
                        carry["recv_valid"], cap, carry["hot_slot"],
                        hot_rows, carry["mig_moved"], carry["ef_stash"],
                        carry["owner"])


def conflict_patch_cap(cap: int, conflict_factor: float) -> int:
    """Static per-(src,dst) capacity of the conflict-patch buckets:
    `conflict_factor <= 0` re-gathers every possible conflict (pcap = cap,
    exact — the default, mirroring capacity_factor's exact mode); otherwise
    ceil(factor * cap) clipped to [1, cap], overflowed rows keeping their
    one-step-stale speculative value (counted in `conflict_overflow`)."""
    if conflict_factor <= 0:
        return cap
    return max(1, min(cap, int(-(-conflict_factor * cap // 1))))


# oelint: jit-entry
# oelint: hot-path device_get=0
def grouped_prefetch(
    specs, states, ids_list, *,
    axis: str = DATA_AXIS,
    capacity_factor: float = 0.0,
    wire: Optional[str] = None,
    load_stats: bool = True,
    packed_list=None,
):
    """Id plane + speculative weight plane of a fused training pull for one
    dim-group, WITHOUT the client tail (`grouped_finalize_pull` runs that at
    consume time, one step later).

    Issued for batch t+1 this depends only on batch t+1's ids and the
    CURRENT table state — no data dependency on batch t's gradients — so XLA
    is free to overlap both of its all_to_alls with batch t's dense
    forward/backward. Hash inserts happen here, in the same order the serial
    loop would insert (apply never touches keys and the open-addressing find
    is stable under later inserts), so the speculatively gathered rows
    differ from a serial pull's ONLY at rows batch t's push updates — the
    exact set `grouped_conflict_patch` re-gathers. Hot/mig probes ride the
    prefetched sort unchanged (their directories only change between
    windows).

    Returns (new_states, plans, uniq_rows_list, stats_list):
    `uniq_rows_list` holds each table's decoded per-UNIQUE-slot rows
    (n, dim) float32 — speculative until patched, hot slots zero until the
    finalize overlay. `packed_list`: as `grouped_lookup_train`'s, the
    column layout of each table whose shard is in the scan's packed form."""
    from ..ops import wire as wire_mod
    S = jax.lax.axis_size(axis)
    if packed_list is None:
        packed_list = [None] * len(specs)
    if S == 1:
        raise ValueError(
            "grouped_prefetch needs S >= 2: the pipelined loop has nothing "
            "to overlap on a 1-device mesh (MeshTrainer falls back to the "
            "serial train_many there)")
    dim = specs[0].output_dim
    ids_list = [adapt_batch_ids(spec, state, ids)
                for spec, state, ids in zip(specs, states, ids_list)]
    hots = [state.hot for state in states]
    plans = grouped_make_plans(specs, ids_list, axis=axis,
                               capacity_factor=capacity_factor, hots=hots,
                               migs=[state.mig for state in states])
    fmt = wire_mod.wire_format(wire)
    new_states, rows_list, stashed_plans = [], [], []
    for spec, state, plan, packed in zip(specs, states, plans, packed_list):
        # served a step BEFORE the apply that precedes its use writes the
        # shard: a plan made here would be stale, so per slot and no plan
        state, rows, stash, _ = _serve_rows(spec, state, plan, train=True,
                                            axis=axis, fmt=fmt,
                                            return_stash=True, packed=packed,
                                            share=False)
        new_states.append(state)
        rows_list.append(rows)
        # the pre-serve EF residuals ride the plan to the conflict patch
        # (local serving-shard state, zero extra wire)
        stashed_plans.append(plan._replace(ef_stash=stash)
                             if stash is not None else plan)
    plans = stashed_plans
    # same wire flow as grouped_lookup_train: ONE a2a for the group's rows
    back, decode = _rows_round_trip(rows_list, dim, fmt, axis)
    uniq_rows_list, off = [], 0
    for plan in plans:
        seg = back[:, off:off + plan.cap]
        off += plan.cap
        uniq_rows_list.append(decode(_from_buckets(seg, plan)))
    stats_list = []
    for spec, ids, plan in zip(specs, ids_list, plans):
        st = {
            "pull_indices": jnp.asarray(ids_positions(spec, ids), jnp.int32),
            "pull_unique": plan.uniq.num_unique,
            "pull_overflow": plan.buckets.overflow,
        }
        if plan.hot_slot is not None:
            st.update(_hot_pull_stats(spec, plan, flatten_ids(spec, ids),
                                      fmt))
        if plan.mig_moved is not None:
            st.update(_mig_pull_stats(plan))
        if load_stats:
            st.update(exchange_load_stats(plan, axis=axis))
        stats_list.append(st)
    return new_states, plans, uniq_rows_list, stats_list


# oelint: jit-entry
# oelint: hot-path device_get=0
def grouped_finalize_pull(specs, states, ids_list, plans, uniq_rows_list):
    """Client tail of a prefetched pull: hot-cache overlay + duplicate
    expansion, run at CONSUME time so the overlay reads the hot cache as of
    the previous batch's apply (hot rows never ride the buckets — the
    speculative unique rows hold zeros there, and the fresh overlay is what
    keeps hot rows exact under pipelining). Pure local math, no collective.
    Returns per-table batch-shaped rows in each table's dtype."""
    with _trace.scope("exchange", "reassemble"):
        outs = []
        for spec, state, ids, plan, uniq_rows in zip(specs, states, ids_list,
                                                     plans, uniq_rows_list):
            ids = adapt_batch_ids(spec, state, ids)
            ur = _merge_hot_rows(plan, uniq_rows, state.hot)
            out = jnp.take(ur, plan.uniq.inverse, axis=0)
            outs.append(out.astype(spec.dtype).reshape(
                _out_shape(spec, ids) + (spec.output_dim,)))
        return outs


def _shard_rows(spec: EmbeddingSpec, weights: jax.Array, packed) -> int:
    """Rows of a shard's main table, packed under the layout `packed` (in
    either form, `ops/sparse.packed_rows`) or not (None)."""
    width = _width(spec, weights, packed)
    return weights.shape[0] if width is None else packed_rows(weights, width)


def _weight_rows(spec: EmbeddingSpec, weights: jax.Array, idx: jax.Array,
                 packed) -> jax.Array:
    """`lookup_rows` of a shard's main table once a slot -> (m, dim): of a
    table the scan holds packed (`packed`: its layout; in either form) the
    whole packed row is read and the weight columns sliced out."""
    width = _width(spec, weights, packed)
    if width is None:
        return lookup_rows(weights, idx)
    with _trace.scope("sparse", "pull"):
        rows = gather_packed_rows(weights, width, idx)
    return rows[:, :spec.output_dim]


def _gather_rows_readonly(spec: EmbeddingSpec, state: EmbeddingTableState,
                          flat_recv: jax.Array, flat_valid: jax.Array,
                          S: int, *, want_ef_idx: bool = False, packed=None):
    """Row gather for ids this shard serves, strictly read-only: no hash
    insert (the prefetch already inserted every patched id), no
    error-feedback side effects. Mig-annex-aware exactly like `_serve_rows`;
    packed train_many layouts (`packed`) slice the weight columns out. -> (n, dim) in
    the table's storage dtype, plus (with `want_ef_idx`) each row's index
    into `state.ef` — the SAME index `_serve_rows` computes (OOB for
    invalid/annex rows), so the conflict patch's replay writes exactly the
    slots the speculative serve wrote."""
    with _trace.scope("exchange", "owner_serve"):
        mig = state.mig
        ef_idx = None
        if mig is not None:
            m_found, m_rank, _ = _mig_find(mig, flat_recv, flat_valid)
            main_valid = flat_valid & ~m_found
        else:
            m_found = None
            main_valid = flat_valid
        if spec.use_hash_table:
            from ..tables.hash_table import hash_find
            if flat_recv.ndim == 2:
                from ..ops.id64 import PAIR_EMPTY
                probe = jnp.where(main_valid[:, None], flat_recv, PAIR_EMPTY)
            else:
                probe = jnp.where(main_valid, flat_recv, -1)
            capacity = state.keys.shape[0]
            slot = hash_find(state.keys, probe)
            idx = jnp.where((slot < capacity) & main_valid, slot, capacity)
            rows = _weight_rows(spec, state.weights, idx, packed)
            if want_ef_idx:
                ef_idx = idx
        else:
            idx = jnp.where(main_valid, flat_recv // S, -1)
            rows = _weight_rows(spec, state.weights, idx, packed)
            if want_ef_idx:
                N = state.ef.shape[0] if state.ef is not None \
                    else _shard_rows(spec, state.weights, packed)
                ef_idx = jnp.where(main_valid, flat_recv // S,
                                   N).astype(jnp.int32)
        if m_found is not None:
            M = mig.weights.shape[0]
            arows = lookup_rows(mig.weights, jnp.where(m_found, m_rank, M))
            if arows.shape[1] != spec.output_dim:
                arows = arows[:, :spec.output_dim]
            rows = jnp.where(m_found[:, None], arows.astype(rows.dtype), rows)
        if want_ef_idx:
            return rows, ef_idx
        return rows


# oelint: jit-entry
# oelint: hot-path device_get=0
def grouped_conflict_patch(
    specs, states, prev_plans, plans, uniq_rows_list, *,
    axis: str = DATA_AXIS,
    conflict_factor: float = 0.0,
    wire: Optional[str] = None,
    packed_list=None,
):
    """Repair a dim-group's speculatively prefetched rows after the previous
    batch's push. Every row that push touched on this shard is exactly a
    VALID recv slot of the previous plan, so the conflict set is the
    intersection of the previous plan's recv ids with the new plan's (one
    fused sort per table, `ops/dedup.member_mask`). The serving shards
    re-gather only those rows from the POST-apply tables, compact them to
    `conflict_patch_cap` slots per source, and ONE all_to_all per group
    ships row + origin bucket slot back (slot+1 riding the exact count
    lanes, 0 = empty — the push codec reused verbatim); the client scatters
    them over its speculative unique rows. fp32 wire makes patched rows
    bit-identical to an unpipelined pull; with error feedback (int8 wire)
    the serving shard replays the plan's pre-serve residual stash against
    the post-apply weights — re-encoding x' = w_post + ef_pre and rewriting
    ef' = x' - deq(q(x')) at the same slots the speculative serve wrote —
    so patched rows AND residuals match the serial schedule bit for bit.

    Returns (patched_uniq_rows_list, stats_list, new_states) with per-table
    `conflict_rows` (this source's compacted patch rows — psum to the step
    total) and `conflict_overflow` (members dropped by the pcap budget;
    those rows keep their one-step-stale value); `new_states` carries the
    replayed EF residuals (the input states unchanged otherwise).
    `packed_list`: as `grouped_prefetch`'s."""
    from ..ops import wire as wire_mod
    from ..ops.dedup import compact_member_slots, member_mask
    S = jax.lax.axis_size(axis)
    dim = specs[0].output_dim
    fmt = wire_mod.wire_format(wire)
    if packed_list is None:
        packed_list = [None] * len(specs)
    payloads, metas, new_states = [], [], []
    for spec, state, pplan, plan, packed in zip(specs, states, prev_plans,
                                                plans, packed_list):
        cap = plan.cap
        pcap = conflict_patch_cap(cap, conflict_factor)
        pair = plan.recv_ids.ndim == 3
        ref = (pplan.recv_ids.reshape(-1, 2) if pair
               else pplan.recv_ids.reshape(-1))
        qry = (plan.recv_ids.reshape(-1, 2) if pair
               else plan.recv_ids.reshape(-1))
        member = member_mask(ref, pplan.recv_valid.reshape(-1), qry,
                             plan.recv_valid.reshape(-1)).reshape(S, cap)
        slots, oflow = compact_member_slots(member, pcap)
        cl = jnp.clip(slots, 0, cap - 1)
        taken = jnp.take_along_axis(plan.recv_ids,
                                    cl[..., None] if pair else cl, axis=1)
        flat_ids = taken.reshape(-1, 2) if pair else taken.reshape(-1)
        live = (slots >= 0).reshape(-1)
        want_ef = (fmt != "fp32" and state.ef is not None
                   and plan.ef_stash is not None)
        if want_ef:
            rows, ef_idx = _gather_rows_readonly(
                spec, state, flat_ids, live, S, want_ef_idx=True,
                packed=packed)
            # x' = post-apply weights + the residual the speculative serve
            # consumed (stash zeros for annex rows — no EF there, like the
            # serve); non-live compaction padding masks to zero and its
            # OOB ef_idx drops the scatter
            stash = jnp.take_along_axis(
                plan.ef_stash, cl[..., None], axis=1).reshape(-1, dim)
            x = rows.astype(jnp.float32) \
                + jnp.where(live[:, None], stash, 0.0)
            enc_rows = wire_mod.pack_inband(x, fmt)
            ef_new = x - wire_mod.unpack_inband(enc_rows, dim, fmt)
            state = state.replace(ef=state.ef.at[ef_idx].set(
                ef_new.astype(state.ef.dtype), mode="drop"))
            payload = jnp.concatenate(
                [enc_rows, wire_mod.counts_to_lanes(
                    (slots + 1).reshape(-1).astype(jnp.int32), fmt)],
                axis=1)
        else:
            rows = _gather_rows_readonly(spec, state, flat_ids, live, S,
                                         packed=packed)
            payload = wire_mod.encode_grads(
                rows.astype(jnp.float32),
                (slots + 1).reshape(-1).astype(jnp.int32), fmt)
        new_states.append(state)
        payloads.append(payload.reshape(S, pcap, -1))
        metas.append((pcap, member, oflow))
    recv = _a2a("rows", jnp.concatenate(payloads, axis=1), axis)
    width = recv.shape[-1]
    patched, stats_list, off = [], [], 0
    for spec, plan, uniq_rows, (pcap, member, oflow) in zip(
            specs, plans, uniq_rows_list, metas):
        seg = recv[:, off:off + pcap].reshape(-1, width)
        off += pcap
        prow, pc = wire_mod.decode_grads(seg, dim, fmt)
        cap = plan.cap
        live = pc > 0
        o = jnp.repeat(jnp.arange(S, dtype=jnp.int32), pcap)
        flat_pos = jnp.where(live, o * cap + jnp.clip(pc - 1, 0, cap - 1),
                             S * cap)
        stage = jnp.zeros((S * cap, dim), jnp.float32).at[flat_pos].set(
            prow, mode="drop").reshape(S, cap, dim)
        smask = jnp.zeros((S * cap,), bool).at[flat_pos].set(
            live, mode="drop").reshape(S, cap)
        patch_u = _from_buckets(stage, plan)
        mask_u = _from_buckets(smask, plan)
        patched.append(jnp.where(mask_u[:, None],
                                 patch_u.astype(uniq_rows.dtype), uniq_rows))
        stats_list.append({
            "conflict_rows": jnp.sum(member).astype(jnp.int32) - oflow,
            "conflict_overflow": oflow})
    return patched, stats_list, new_states


def build_hot_identity(spec: EmbeddingSpec, hot_rows: int, ids64=None, *,
                       key_template=None) -> dict:
    """Host-side identity of one table's hot set: the arrays the device probe
    (`_hot_probe`) and gather (`hot_gather`) consume — `keys` (C = 2H probe
    slots in the table's key layout, inserted with the device probe's budget
    so every placed id is reachable), `rank` (probe slot -> compact hot row,
    H = empty) and `ids` (hot ids by rank, padding EMPTY).

    `ids64`: candidate ids hottest-first (int64 array-like; None/empty -> an
    all-EMPTY identity). Invalid ids drop (negative; out-of-vocab for array
    tables); duplicates keep their first (hottest) rank. `key_template`: the
    table's device key array, pinning pair vs single-lane layout for hash
    tables."""
    import numpy as np

    from ..ops.id64 import np_split_ids
    from ..tables.hash_table import np_fresh_keys, np_hash_insert
    H = int(hot_rows)
    C = max(2 * H, 8)
    if spec.use_hash_table:
        keys = np_fresh_keys(C, like=(np.asarray(key_template)
                                      if key_template is not None else None))
    else:
        # array tables key the probe by int32 (vocab < 2^31 by the hash
        # threshold); the device probe casts valid batch ids down losslessly
        keys = np.full((C,), -1, np.int32)
    pair = keys.ndim == 2
    rank = np.full((C,), H, np.int32)
    if pair:
        ids_arr = np.full((H, 2), np.uint32(0xFFFFFFFF), np.uint32)
    else:
        ids_arr = np.full((H,), -1, keys.dtype)
    cand = np.asarray([] if ids64 is None else ids64,
                      np.int64).reshape(-1)
    cand = cand[cand >= 0]
    if not spec.use_hash_table:
        cand = cand[cand < spec.input_dim]
    _, first = np.unique(cand, return_index=True)  # dedupe, keep hottest rank
    cand = cand[np.sort(first)][:H]
    if cand.size:
        ins = cand if (pair or keys.dtype.itemsize >= 8) \
            else cand.astype(np.int32)  # host mixer must match device _mix
        pos = np_hash_insert(keys, ins, 1, num_probes=HOT_NUM_PROBES)
        placed = pos >= 0
        kept = cand[placed]
        rank[pos[placed]] = np.arange(kept.size, dtype=np.int32)
        if pair:
            ids_arr[:kept.size] = np_split_ids(kept)
        else:
            ids_arr[:kept.size] = kept.astype(keys.dtype)
    return {"keys": keys, "rank": rank, "ids": ids_arr}


def _hot_owner_route(spec: EmbeddingSpec, state: EmbeddingTableState,
                     ids: jax.Array, axis, insert: bool):
    """Owner-shard routing of the (replicated) hot id list inside shard_map:
    -> (new_state, src_row, owner) where `src_row` indexes THIS shard's
    weights/slots (out of bounds for ids it does not own — gathers fill 0,
    scatters drop) and `owner` is each id's owning shard index. Hash tables
    optionally insert absent ids (promotion must leave a row for writeback to
    land on; the overflow counter advances like `_serve_rows`)."""
    S = jax.lax.axis_size(axis)
    if spec.use_hash_table:
        from ..ops.id64 import pair_mod, pair_valid
        from ..tables.hash_table import (hash_find, hash_find_or_insert,
                                         shard_probe)
        mine, probe = shard_probe(state.keys, ids, axis)
        if insert:
            old_overflow = state.overflow
            new_keys, slot, oflow = hash_find_or_insert(state.keys, probe)
            delta = jax.lax.psum(oflow, axis)
            state = state.replace(keys=new_keys,
                                  overflow=old_overflow + delta)
        else:
            slot = hash_find(state.keys, probe)
        capacity = state.keys.shape[0]
        src = jnp.where(mine & (slot < capacity), slot, capacity)
        if ids.ndim == 2:
            owner = jnp.where(pair_valid(ids),
                              pair_mod(ids, S).astype(jnp.int32), 0)
        else:
            owner = jnp.where(ids >= 0, (ids % S).astype(jnp.int32), 0)
        return state, src, owner
    idx = _flat_axis_index(axis)
    valid = (ids >= 0) & (ids < spec.input_dim)
    mine = valid & ((ids % S).astype(jnp.int32) == idx)
    src = jnp.where(mine, (ids // S).astype(jnp.int32),
                    state.weights.shape[0])
    owner = jnp.where(valid, (ids % S).astype(jnp.int32), 0)
    return state, src, owner


# oelint: hot-path device_get=0
def hot_writeback(spec: EmbeddingSpec, state: EmbeddingTableState, *,
                  axis=DATA_AXIS) -> EmbeddingTableState:
    """Scatter the replicated hot rows (weights AND optimizer slots) back into
    their owner shards — NO collective: every device holds every hot row, each
    shard overwrites only the rows it owns. After this the owner copies equal
    the cache bit for bit, so checkpoint/export/delta readers see exactly what
    a hot-off run would have written (`MeshTrainer.hot_sync` drives it at
    snapshot time; `refresh_hot_rows` before demoting). The cache itself stays
    untouched and live."""
    hot = state.hot
    if hot is None:
        return state
    state, src, _owner = _hot_owner_route(spec, state, hot.ids, axis,
                                          insert=spec.use_hash_table)
    weights = state.weights.at[src].set(
        hot.weights.astype(state.weights.dtype), mode="drop")
    slots = {k: state.slots[k].at[src].set(
        hot.slots[k].astype(state.slots[k].dtype), mode="drop")
        for k in state.slots}
    return state.replace(weights=weights, slots=slots)


# oelint: hot-path device_get=0
def hot_gather(spec: EmbeddingSpec, state: EmbeddingTableState,
               identity: dict, *, axis=DATA_AXIS) -> EmbeddingTableState:
    """Fill the replicated cache for `identity`'s hot set from the owner
    shards: each shard contributes the rows it owns (zeros elsewhere), ONE
    all_gather ships the compact (H, dim + slot widths) contributions, and an
    exact per-id SELECT by owner shard replicates them — no floating-point
    reduction, promotion copies bits. Hash tables insert absent hot ids (a
    serving-side heavy hitter the trainer never pulled still gets a row —
    initializer values, exactly what its first cold pull would have lazily
    created). Returns the table state with `hot` swapped in (keys/overflow
    may advance on hash inserts); padding ranks hold zero rows and are
    masked everywhere by rank/id validity."""
    ids = identity["ids"]
    state, src, owner = _hot_owner_route(spec, state, ids, axis, insert=True)
    w_c = lookup_rows(state.weights, src).astype(jnp.float32)
    slot_names = sorted(state.slots)
    cols = [w_c] + [lookup_rows(state.slots[k], src).astype(jnp.float32)
                    for k in slot_names]
    widths = [c.shape[1] for c in cols]
    contrib = jnp.concatenate(cols, axis=1)
    parts = jax.lax.all_gather(contrib, axis)          # (S, H, W)
    S = parts.shape[0]
    sel = parts[jnp.clip(owner, 0, S - 1),
                jnp.arange(ids.shape[0])]              # (H, W): owner's copy
    off = widths[0]
    slots = {}
    for k, w in zip(slot_names, widths[1:]):
        slots[k] = sel[:, off:off + w].astype(state.slots[k].dtype)
        off += w
    hot = HotRows(keys=identity["keys"], rank=identity["rank"], ids=ids,
                  weights=sel[:, :widths[0]].astype(state.weights.dtype),
                  slots=slots)
    return state.replace(hot=hot)


# ---------------------------------------------------------------------------
# Cold-tail re-sharding lifecycle: host-side directory construction + device-
# side annex fill/writeback (inside shard_map; driven off the hot path by
# MeshTrainer.migrate_rows / hot_sync between steps — static shapes, so
# swapping directories never re-jits).
# ---------------------------------------------------------------------------


def build_mig_identity(spec: EmbeddingSpec, mig_rows: int, ids64=None,
                       owners=None, *, num_shards: int,
                       key_template=None) -> dict:
    """Host-side identity of one table's migration set: the replicated
    directory arrays `_mig_find` consumes — `keys` (C = 2M probe slots in the
    table's key layout), `rank` (probe slot -> migration rank, M = empty),
    `ids` (migrated ids by rank, padding EMPTY) and `owners` (assigned owner
    shard by rank, padding -1).

    `ids64`/`owners`: parallel arrays of candidate moves (int64 ids,
    heaviest first; None/empty -> an all-EMPTY directory that routes nothing
    off home). Invalid ids drop (negative; out-of-vocab for array tables),
    as do moves whose owner falls outside [0, num_shards); duplicates keep
    their first (heaviest) rank. Same probe-budget discipline as
    `build_hot_identity`: an id the device probe cannot reach is never
    placed."""
    import numpy as np

    from ..ops.id64 import np_split_ids
    from ..tables.hash_table import np_fresh_keys, np_hash_insert
    M = int(mig_rows)
    C = max(2 * M, 8)
    if spec.use_hash_table:
        keys = np_fresh_keys(C, like=(np.asarray(key_template)
                                      if key_template is not None else None))
    else:
        keys = np.full((C,), -1, np.int32)
    pair = keys.ndim == 2
    rank = np.full((C,), M, np.int32)
    own_arr = np.full((M,), -1, np.int32)
    if pair:
        ids_arr = np.full((M, 2), np.uint32(0xFFFFFFFF), np.uint32)
    else:
        ids_arr = np.full((M,), -1, keys.dtype)
    cand = np.asarray([] if ids64 is None else ids64, np.int64).reshape(-1)
    cown = np.asarray([] if owners is None else owners,
                      np.int64).reshape(-1)[:cand.size]
    keep = (cand >= 0) & (cown >= 0) & (cown < num_shards)
    if not spec.use_hash_table:
        keep &= cand < spec.input_dim
    cand, cown = cand[keep], cown[keep]
    _, first = np.unique(cand, return_index=True)  # dedupe, keep heaviest
    sel = np.sort(first)[:M]
    cand, cown = cand[sel], cown[sel]
    if cand.size:
        ins = cand if (pair or keys.dtype.itemsize >= 8) \
            else cand.astype(np.int32)  # host mixer must match device _mix
        pos = np_hash_insert(keys, ins, 1, num_probes=HOT_NUM_PROBES)
        placed = pos >= 0
        kept, kown = cand[placed], cown[placed]
        rank[pos[placed]] = np.arange(kept.size, dtype=np.int32)
        own_arr[:kept.size] = kown.astype(np.int32)
        if pair:
            ids_arr[:kept.size] = np_split_ids(kept)
        else:
            ids_arr[:kept.size] = kept.astype(keys.dtype)
    return {"keys": keys, "rank": rank, "ids": ids_arr, "owners": own_arr}


def _mig_live_select(mig: MigRows, axis):
    """All_gather every shard's annex and select each rank's LIVE copy (the
    assigned owner's) -> (live (M, W) f32, slot column layout). The one
    collective of the writeback path; pure bit movement, no float math."""
    slot_names = sorted(mig.slots)
    cols = [mig.weights.astype(jnp.float32)] + \
        [mig.slots[k].astype(jnp.float32) for k in slot_names]
    widths = [c.shape[1] for c in cols]
    parts = jax.lax.all_gather(jnp.concatenate(cols, axis=1), axis)
    S = parts.shape[0]
    M = mig.ids.shape[0]
    live = parts[jnp.clip(mig.owners, 0, S - 1), jnp.arange(M)]
    return live, slot_names, widths


# oelint: hot-path device_get=0
def mig_writeback(spec: EmbeddingSpec, state: EmbeddingTableState, *,
                  axis=DATA_AXIS) -> EmbeddingTableState:
    """Restore the HOME-shard copies of every migrated row (weights AND
    optimizer slots): ONE all_gather ships each shard's (M, W) annex, every
    shard selects the assigned owner's live copy per rank, and each home
    shard overwrites only the rows it natively owns (hash homes insert absent
    ids so a row promoted straight into the annex still lands). After this
    the main tables equal an unmigrated run bit for bit, so checkpoint/
    export/delta readers see exactly what they would have without the
    directory (`MeshTrainer.hot_sync` drives it at snapshot time;
    `migrate_rows` before installing a new directory). The directory and
    annex stay live."""
    mig = state.mig
    if mig is None:
        return state
    live, slot_names, widths = _mig_live_select(mig, axis)
    state, src, _home = _hot_owner_route(spec, state, mig.ids, axis,
                                         insert=spec.use_hash_table)
    weights = state.weights.at[src].set(
        live[:, :widths[0]].astype(state.weights.dtype), mode="drop")
    off = widths[0]
    slots = dict(state.slots)
    for k, w in zip(slot_names, widths[1:]):
        slots[k] = state.slots[k].at[src].set(
            live[:, off:off + w].astype(state.slots[k].dtype), mode="drop")
        off += w
    return state.replace(weights=weights, slots=slots)


# oelint: hot-path device_get=0
def mig_gather(spec: EmbeddingSpec, state: EmbeddingTableState,
               identity: dict, *, axis=DATA_AXIS) -> EmbeddingTableState:
    """Install `identity`'s migration directory and fill the annex from the
    HOME shards: each shard contributes the rows it natively owns (zeros
    elsewhere), ONE all_gather ships the compact (M, W) contributions, and an
    exact per-id select by home shard lands them — no floating-point
    reduction, migration copies bits. Hash homes insert absent ids (same
    rationale as `hot_gather`: a measured-heavy id the trainer never pulled
    still gets a row, and `mig_writeback` always has a home slot to restore).
    Every shard's annex starts with identical content; copies diverge as each
    assigned owner trains its rows, and the owner-select in `mig_writeback`
    is what makes that safe. Callers must writeback the OLD directory first
    (`mig_writeback`) or its in-flight updates are lost."""
    ids = identity["ids"]
    state, src, home = _hot_owner_route(spec, state, ids, axis, insert=True)
    w_c = lookup_rows(state.weights, src).astype(jnp.float32)
    slot_names = sorted(state.slots)
    cols = [w_c] + [lookup_rows(state.slots[k], src).astype(jnp.float32)
                    for k in slot_names]
    widths = [c.shape[1] for c in cols]
    contrib = jnp.concatenate(cols, axis=1)
    parts = jax.lax.all_gather(contrib, axis)          # (S, M, W)
    S = parts.shape[0]
    sel = parts[jnp.clip(home, 0, S - 1),
                jnp.arange(ids.shape[0])]              # (M, W): home's copy
    off = widths[0]
    slots = {}
    for k, w in zip(slot_names, widths[1:]):
        slots[k] = sel[:, off:off + w].astype(state.slots[k].dtype)
        off += w
    mig = MigRows(keys=identity["keys"], rank=identity["rank"], ids=ids,
                  owners=identity["owners"],
                  weights=sel[:, :widths[0]].astype(state.weights.dtype),
                  slots=slots)
    return state.replace(mig=mig)


# ---------------------------------------------------------------------------
# Layout converters for checkpointing / export.
# Shard-major storage: global array row (shard * rows_per_shard + local) holds id
# (local * S + shard). Checkpoints are written in plain id order (reference: load
# remaps keys `index*shard_num + shard_id`, `EmbeddingShardFile.h:23-25`), so any
# future mesh size can reshard by pure relayout.
# ---------------------------------------------------------------------------


def deinterleave_rows(global_rows, num_shards: int, vocab: int):
    """(S*rps, dim) shard-major -> (vocab, dim) id-major. Works on np or jnp."""
    rps = global_rows.shape[0] // num_shards
    per_shard = global_rows.reshape(num_shards, rps, -1)
    id_major = per_shard.transpose(1, 0, 2).reshape(num_shards * rps, -1)
    return id_major[:vocab]


def interleave_rows(id_major: jax.Array, num_shards: int) -> jax.Array:
    """(vocab, dim) id-major -> (S*rps, dim) shard-major, zero-padded."""
    vocab, dim = id_major.shape
    rps = -(-vocab // num_shards)
    padded = jnp.zeros((rps * num_shards, dim), id_major.dtype).at[:vocab].set(id_major)
    return padded.reshape(rps, num_shards, dim).transpose(1, 0, 2).reshape(
        num_shards * rps, dim)
