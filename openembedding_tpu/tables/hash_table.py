"""Static-capacity open-addressing device hash table.

Counterpart of the reference's `EmbeddingHashTable` (`variable/EmbeddingTable.h:24-119`:
`EasyHashMap<key, T*>` + pooled value arenas) used when `input_dim == -1` (63-bit hashed
id space, `tensorflow/exb.py:388-419`, `Meta.h:44-46`).

The reference grows unboundedly in host RAM; XLA needs static shapes, so this table has
a **fixed slot capacity** with linear probing and an overflow counter (documented
divergence; size capacity ~2x expected unique ids). All ops are jit-safe and run as a
handful of fused gathers/scatters:

- `hash_find_or_insert`: one probe round per loop iteration for the whole id batch at
  once; empty-slot claims race through a scatter-then-reread, so the winner is whoever
  XLA's scatter kept — the loser keeps probing. This replaces the reference's per-key
  mutex-free `EasyHashMap::try_emplace` on the owning server thread.
- newly claimed slots already hold initializer values: rows are materialized at table
  creation (`embedding.init_table_state`), replacing the reference's lazy `_new_weights`
  init-on-first-pull (`EmbeddingOptimizerVariable.h:242-266`).

Ids must be non-negative (63-bit hash space); -1 is the EMPTY sentinel.

**63-bit ids WITHOUT jax_enable_x64 (the default config):** XLA under x64-off
cannot hold int64 arrays at all, so keys are stored as a **split pair of
uint32 lanes** — shape (capacity, 2), `[:, 0]` = bits 62..32 (valid < 2^31),
`[:, 1]` = bits 31..0 — and ids travel the id pipeline (dedup -> bucket ->
all_to_all -> probe) in the same `uint32 (..., 2)` layout (`ops/id64.py`).
Every kernel here dispatches on `keys.ndim`: 1 = int64 single-lane (x64 on),
2 = split-pair. EMPTY/padding in pair form is hi >= 2^31 (all-ones row).
The reference gets 2^63 keys for free from C++ `uint64_t`
(`variable/Meta.h:44-46`); the pair layout is the TPU-native equivalent.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.id64 import (HI_INVALID, PAIR_EMPTY, is_pair, np_join_ids,
                        np_split_ids, pair_valid)
from ..utils import trace as _trace

EMPTY = -1
DEFAULT_NUM_PROBES = 64


def _mix(ids: jax.Array) -> jax.Array:
    """Avalanche mixer so clustered ids spread over slots (fibonacci hashing)."""
    if ids.dtype.itemsize >= 8:
        u = ids.astype(jnp.uint64)
        u = (u ^ (u >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
        u = u ^ (u >> 33)
        return u
    u = ids.astype(jnp.uint32)
    u = (u ^ (u >> 16)) * jnp.uint32(0x45D9F3B)
    u = u ^ (u >> 16)
    return u


def np_mix(ids):
    """Numpy mirror of `_mix` — MUST stay in sync: checkpoint load re-inserts keys
    host-side using the same probe sequence so the device `hash_find` locates them."""
    import numpy as np
    if ids.dtype.itemsize >= 8:
        u = ids.astype(np.uint64)
        u = (u ^ (u >> np.uint64(33))) * np.uint64(0xFF51AFD7ED558CCD)
        return u ^ (u >> np.uint64(33))
    u = ids.astype(np.uint32)
    u = (u ^ (u >> np.uint32(16))) * np.uint32(0x45D9F3B)
    return u ^ (u >> np.uint32(16))


def _mix_pair(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Avalanche both uint32 lanes of a split 63-bit id into one uint32."""
    u = lo.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    u = u ^ (hi.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    u = (u ^ (u >> 16)) * jnp.uint32(0x45D9F3B)
    return u ^ (u >> 16)


def np_mix_pair(hi, lo):
    """Numpy mirror of `_mix_pair` — same sync contract as `np_mix`."""
    import numpy as np
    u = hi.astype(np.uint32), lo.astype(np.uint32)
    v = u[1] * np.uint32(0x9E3779B1)
    v = v ^ (u[0] * np.uint32(0x85EBCA77))
    v = (v ^ (v >> np.uint32(16))) * np.uint32(0x45D9F3B)
    return v ^ (v >> np.uint32(16))


def fresh_keys(rows: int) -> jax.Array:
    """An all-EMPTY key array in the layout the current config supports:
    int64 single-lane under x64, the uint32 split pair otherwise — the
    dispatch point that makes `input_dim=-1` mean 2^63 in BOTH configs."""
    if jax.config.jax_enable_x64:
        return jnp.full((rows,), EMPTY, jnp.int64)
    return jnp.full((rows, 2), PAIR_EMPTY, jnp.uint32)


def np_fresh_keys(rows: int, like=None):
    """Host twin of `fresh_keys`; `like` (an existing keys array) pins the
    layout explicitly (checkpoint loaders build for a given template)."""
    import numpy as np
    pair = (like.ndim == 2) if like is not None \
        else not jax.config.jax_enable_x64
    if pair:
        return np.full((rows, 2), PAIR_EMPTY, np.uint32)
    return np.full((rows,), EMPTY, np.int64)


def adapt_ids(keys: jax.Array, ids: jax.Array) -> jax.Array:
    """Convert flat ids to the key array's layout (pair <-> single), keeping
    negatives/EMPTY invalid in either layout."""
    from ..ops.id64 import split_ids
    if keys.ndim == 2:
        return ids if is_pair(ids) else split_ids(ids)
    if is_pair(ids):
        raise ValueError(
            "split-pair ids need a pair-layout table (jax_enable_x64 is on; "
            "pass plain int64 ids instead)")
    return ids.astype(keys.dtype)


def shard_probe(keys: jax.Array, ids: jax.Array, axis) -> tuple:
    """-> (mine, probe) for a row-sharded hash table inside shard_map: `mine`
    masks the ids this shard owns (`id % S == shard_index`, the
    `parallel/sharded.py` routing rule) and `probe` is the id batch with
    non-owned/invalid entries replaced by the EMPTY sentinel so the local
    probe never matches them. THE one copy of the ownership/sentinel rule —
    admission, eviction, and the persist row reader all route through it."""
    import jax
    import jax.numpy as jnp

    from ..ops.id64 import PAIR_EMPTY, is_pair, pair_mod, pair_valid

    S = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    if is_pair(ids):
        mine = pair_valid(ids) & (pair_mod(ids, S).astype(jnp.int32) == idx)
        return mine, jnp.where(mine[:, None], ids, PAIR_EMPTY)
    mine = (ids >= 0) & ((ids % S).astype(jnp.int32) == idx)
    return mine, jnp.where(mine, ids, -1).astype(keys.dtype)


def np_hash_insert(keys, ids, num_shards: int,
                   num_probes: int = DEFAULT_NUM_PROBES):
    """Vectorized host-side insertion of checkpointed keys into a (possibly
    different) shard layout, same probe sequence as the device kernel: owner
    shard = id % S, base = np_mix(id) % capacity_per_shard, linear probing
    inside the owner's slot range. `keys` ((S*cps,) np array, EMPTY = -1) is
    mutated; `ids` must be unique and non-negative. Returns the global slot per
    id (-1 = dropped: no empty slot within `num_probes`).

    Replaces a per-id Python loop (a 10^8-row restore would take hours,
    reference load streams batched inserts, `EmbeddingLoadOperator.cpp:58-111`).
    One round per probe distance, all pending ids at once; among ids contending
    for the same empty slot the lowest-index wins (the sequential insertion
    order), losers advance — their probed slot is occupied from then on, so the
    resulting placement is a valid open-addressing state: every slot on an id's
    probe path before its final position is non-empty, which is exactly the
    invariant `hash_find` needs.

    `num_probes` deliberately defaults to the device kernel's probe budget:
    placing a row deeper than `hash_find` ever probes would make it silently
    unreachable — better to drop it and count it in overflow.
    """
    import numpy as np

    pair = keys.ndim == 2  # split-pair layout (see module docstring)
    rows_total = keys.shape[0]
    cps = rows_total // num_shards
    owner = (np.asarray(ids, np.int64) % num_shards) * cps
    if pair:
        ids_pair = np_split_ids(np.asarray(ids, np.int64))
        base = (np_mix_pair(ids_pair[:, 0], ids_pair[:, 1])
                % np.uint32(cps)).astype(np.int64)
    else:
        mixed = np_mix(ids)
        base = (mixed % np.uint64(cps) if ids.dtype.itemsize >= 8
                else mixed % np.uint32(cps)).astype(np.int64)
    pos_out = np.full(len(ids), -1, np.int64)
    max_d = min(num_probes, cps)
    active = np.arange(len(ids))
    dist = np.zeros(len(ids), np.int64)
    while active.size:
        p = owner[active] + (base[active] + dist[active]) % cps
        empty = keys[p, 0] >= HI_INVALID if pair else keys[p] == EMPTY
        cand, cp = active[empty], p[empty]
        order = np.argsort(cp, kind="stable")
        cp_s, cand_s = cp[order], cand[order]
        first = np.ones(cp_s.size, bool)
        if cp_s.size:
            first[1:] = cp_s[1:] != cp_s[:-1]
        win, wp = cand_s[first], cp_s[first]
        if pair:
            keys[wp] = ids_pair[win]
        else:
            keys[wp] = ids[win]
        pos_out[win] = wp
        placed = np.zeros(len(ids), bool)
        placed[win] = True
        rem = active[~placed[active]]
        dist[rem] += 1
        active = rem[dist[rem] < max_d]
    return pos_out


def _pair_find_or_insert(keys: jax.Array, ids: jax.Array,
                         num_probes: int) -> Tuple[jax.Array, jax.Array,
                                                   jax.Array]:
    """Split-pair twin of the single-lane probe loop below. One extra care:
    two contenders racing a scatter into one row could in principle tear the
    two lanes; the read-back verifies BOTH lanes, so a torn row simply matches
    neither contender (both keep probing) and the garbage slot is probed past
    forever — a leaked slot, never a wrong answer."""
    capacity = keys.shape[0]
    valid = pair_valid(ids)
    base = (_mix_pair(ids[:, 0], ids[:, 1])
            % jnp.uint32(capacity)).astype(jnp.int32)
    slot0 = jnp.full((ids.shape[0],), capacity, jnp.int32)
    placed0 = ~valid

    def probe(d, carry):
        keys, slot, placed = carry
        pos = (base + d) % capacity
        cur = keys[pos]
        match = (cur[:, 0] == ids[:, 0]) & (cur[:, 1] == ids[:, 1])
        found = (~placed) & match
        slot = jnp.where(found, pos, slot)
        placed = placed | found
        want = (~placed) & (cur[:, 0] >= HI_INVALID)
        target = jnp.where(want, pos, capacity)
        keys = keys.at[target].set(ids, mode="drop")
        re = keys[pos]
        got = want & (re[:, 0] == ids[:, 0]) & (re[:, 1] == ids[:, 1])
        slot = jnp.where(got, pos, slot)
        placed = placed | got
        return keys, slot, placed

    keys, slot, placed = jax.lax.fori_loop(
        0, num_probes, probe, (keys, slot0, placed0))
    overflow = jnp.sum(~placed).astype(jnp.int32)
    return keys, slot, overflow


def _pair_find(keys: jax.Array, ids: jax.Array, num_probes: int) -> jax.Array:
    capacity = keys.shape[0]
    base = (_mix_pair(ids[:, 0], ids[:, 1])
            % jnp.uint32(capacity)).astype(jnp.int32)
    slot0 = jnp.full((ids.shape[0],), capacity, jnp.int32)
    done0 = ~pair_valid(ids)

    def probe(d, carry):
        slot, done = carry
        pos = (base + d) % capacity
        cur = keys[pos]
        found = (~done) & (cur[:, 0] == ids[:, 0]) & (cur[:, 1] == ids[:, 1])
        slot = jnp.where(found, pos, slot)
        # an all-EMPTY row terminates the search; garbage (torn) rows do not
        done = done | found | ((~done) & (cur[:, 0] == jnp.uint32(0xFFFFFFFF))
                               & (cur[:, 1] == jnp.uint32(0xFFFFFFFF)))
        return slot, done

    slot, _ = jax.lax.fori_loop(0, num_probes, probe, (slot0, done0))
    return slot


def hash_find_or_insert(keys: jax.Array, ids: jax.Array,
                        num_probes: int = DEFAULT_NUM_PROBES
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Find each id's slot, inserting missing ids into empty slots.

    keys: (capacity,) int table OR (capacity, 2) uint32 split-pair table;
    ids in the matching layout ((n,) / (n, 2)), unique, non-negative (dedup
    first — duplicate ids in one call may claim two slots). Returns
    (new_keys, slot (n,) int32 with `capacity` marking overflow,
    overflow_count).
    """
    if keys.ndim == 2:
        return _pair_find_or_insert(keys, ids, num_probes)
    capacity = keys.shape[0]
    valid = ids >= 0  # negative ids (padding like -1) must never match EMPTY slots
    base = (_mix(ids) % jnp.asarray(capacity).astype(_mix(ids).dtype)).astype(jnp.int32)
    slot0 = jnp.full(ids.shape, capacity, jnp.int32)
    placed0 = ~valid  # invalid ids are "done" from the start, slot == capacity

    def probe(d, carry):
        keys, slot, placed = carry
        pos = (base + d) % capacity
        cur = keys[pos]
        found = (~placed) & (cur == ids)
        slot = jnp.where(found, pos, slot)
        placed = placed | found
        want = (~placed) & (cur == EMPTY)
        target = jnp.where(want, pos, capacity)
        keys = keys.at[target].set(ids, mode="drop")
        got = want & (keys[pos] == ids)
        slot = jnp.where(got, pos, slot)
        placed = placed | got
        return keys, slot, placed

    keys, slot, placed = jax.lax.fori_loop(
        0, num_probes, probe, (keys, slot0, placed0))
    overflow = jnp.sum(~placed).astype(jnp.int32)
    return keys, slot, overflow


def hash_find(keys: jax.Array, ids: jax.Array,
              num_probes: int = DEFAULT_NUM_PROBES) -> jax.Array:
    """Read-only probe: slot index per id, `capacity` if absent (reference read-only
    serving pull `get_weights`, `EmbeddingPullOperator.cpp:149-205`)."""
    if keys.ndim == 2:
        return _pair_find(keys, ids, num_probes)
    capacity = keys.shape[0]
    base = (_mix(ids) % jnp.asarray(capacity).astype(_mix(ids).dtype)).astype(jnp.int32)
    slot0 = jnp.full(ids.shape, capacity, jnp.int32)
    done0 = ids < 0  # negative ids never match (EMPTY sentinel is -1)

    def probe(d, carry):
        slot, done = carry
        pos = (base + d) % capacity
        cur = keys[pos]
        found = (~done) & (cur == ids)
        slot = jnp.where(found, pos, slot)
        # an EMPTY slot on the probe path terminates the search (id absent)
        done = done | found | ((~done) & (cur == EMPTY))
        return slot, done

    slot, _ = jax.lax.fori_loop(0, num_probes, probe, (slot0, done0))
    return slot


def hash_lookup(state, ids: jax.Array) -> jax.Array:
    """Read-only pull: absent ids return zero rows."""
    with _trace.scope("sparse", "pull"):
        ids = adapt_ids(state.keys, ids)
        slot = hash_find(state.keys, ids)
        capacity, dim = state.weights.shape
        hit = slot < capacity
        rows = jnp.take(state.weights, jnp.clip(slot, 0, capacity - 1), axis=0)
        return jnp.where(hit[:, None], rows, jnp.zeros_like(rows))


def hash_lookup_train(state, ids: jax.Array, out_dim: int = None,
                      layout=None):
    """Training pull: inserts unseen ids (their slots already carry initializer values)
    and returns (new_state, rows). Mirrors the reference's lazy-init pull
    (`EmbeddingOptimizerVariable.h:242-266`).

    `out_dim`: when the state holds the PACKED weights+slots layout
    (`ops/sparse.packed_layout`, inside `Trainer.train_many`'s scan), slice
    the weight columns out of the gathered packed rows — the gather is
    latency-bound, the slot bytes ride free. `layout`: that packed form's
    column layout, owed with it: by it a table held four rows a lane line is
    known (`ops/sparse.in_lines`)."""
    with _trace.scope("sparse", "pull"):
        from ..ops.dedup import unique_with_counts

        ids = adapt_ids(state.keys, ids)
        uniq = unique_with_counts(ids)
        # only insert real (count>0) unique ids; padding probes for EMPTY and is dropped
        if state.keys.ndim == 2:
            probe_ids = jnp.where((uniq.counts > 0)[:, None], uniq.unique_ids,
                                  PAIR_EMPTY)
        else:
            probe_ids = jnp.where(uniq.counts > 0, uniq.unique_ids, EMPTY)
        new_keys, uslot, overflow = hash_find_or_insert(state.keys, probe_ids)
        slot = uslot[uniq.inverse]
        capacity = state.keys.shape[0]
        hit = slot < capacity
        at = jnp.clip(slot, 0, capacity - 1)
        if layout is None:
            if out_dim is not None and state.weights.shape[1] != out_dim:
                raise ValueError(
                    "hash_lookup_train: the state's weights have "
                    f"{state.weights.shape[1]} columns for out_dim {out_dim}: "
                    "a packed table is read with its layout= (its form is "
                    "known by it alone)")
            rows = jnp.take(state.weights, at, axis=0)
        else:
            from ..ops.sparse import gather_packed_rows, packed_width
            rows = gather_packed_rows(
                state.weights, packed_width(out_dim, layout), at)
        if out_dim is not None and rows.shape[1] != out_dim:
            rows = rows[:, :out_dim]
        rows = jnp.where(hit[:, None], rows, jnp.zeros_like(rows))
        new_overflow = (state.overflow + overflow if state.overflow is not None
                        else overflow)
        return state.replace(keys=new_keys, overflow=new_overflow), rows


def _grad_slots_and_counts(state, ids: jax.Array):
    """ids -> (clipped slot indices, pre_counts) for the push+update: absent
    ids (overflowed at pull time) drop their gradients via count 0, like the
    reference dropping pushes for ids a dead shard lost."""
    with _trace.scope("sparse", "apply"):
        ids = adapt_ids(state.keys, ids)
        slot = hash_find(state.keys, ids)
        capacity = state.keys.shape[0]
        pre_counts = jnp.where(slot < capacity, 1, 0).astype(jnp.int32)
        return jnp.clip(slot, 0, capacity), pre_counts


def hash_apply_gradients(state, optimizer, ids: jax.Array, grads: jax.Array,
                         *, with_load: bool = False):
    """Push+update: translate ids -> slots (no insert; forward pull inserted them),
    then run the shared fused sparse apply over slot indices. `with_load` ->
    (state, the step's apply load, `ops/sparse.py`)."""
    from ..ops.sparse import sparse_apply_dense_table

    slot, pre_counts = _grad_slots_and_counts(state, ids)
    weights, slots, load = sparse_apply_dense_table(
        optimizer, state.weights, state.slots, slot, grads,
        pre_counts=pre_counts, with_load=True)
    state = state.replace(weights=weights, slots=slots)
    return (state, load) if with_load else state


def hash_apply_gradients_packed(state, optimizer, ids: jax.Array,
                                grads: jax.Array, layout, dim: int):
    """`hash_apply_gradients` over the packed weights+slots layout: same probe
    and drop semantics, one gather/scatter pair (`sparse_apply_packed_table`).
    Only the scan calls it: -> (state, the step's apply load)."""
    from ..ops.sparse import sparse_apply_packed_table

    slot, pre_counts = _grad_slots_and_counts(state, ids)
    packed, load = sparse_apply_packed_table(
        optimizer, state.weights, layout, dim, slot, grads,
        pre_counts=pre_counts)
    return state.replace(weights=packed), load
