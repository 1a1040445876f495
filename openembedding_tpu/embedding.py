"""Embedding table state + the user-facing `Embedding` layer spec.

Counterpart of the reference's user API surface (`tensorflow/exb.py`):
- `EmbeddingSpec` ~ the layer config (`Embedding.__init__`, `exb.py:388-419`):
  input_dim (-1 = 2^63 hashed), output_dim, dtype, initializer, per-variable optimizer,
  num_shards, sparse_as_dense.
- `EmbeddingTableState` ~ the server-side storage for one variable
  (`variable/EmbeddingTable.h` array table + optimizer slots from
  `EmbeddingOptimizerVariable.h`) — here a pytree of jax.Arrays so it shards,
  checkpoints and donates like any other train state.

Row-sharding layout (matches the reference so checkpoints stay resharding-friendly,
`EmbeddingPullOperator.cpp:74-84`): global id `i` lives on shard `i % S`, local row
`i // S`. A single-device table is the S=1 special case.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from flax import struct

from .initializers import Initializer, Uniform, make_initializer
from .meta import EmbeddingVariableMeta, HASH_VOCABULARY_THRESHOLD
from .optimizers import SparseOptimizer, make_optimizer
from .ops.sparse import lookup_rows, sparse_apply_dense_table


class HotRows(struct.PyTreeNode):
    """Replicated hot-row cache for one table (Parallax-style hybrid placement,
    `parallel/sharded.py`): a small trace-time-static set of H heavy-hitter rows
    held IDENTICALLY on every device, so their pulls gather locally (zero
    exchange bytes, zero owner-shard load) and their gradients reduce over the
    data axis like dense params. Chosen/refreshed off the hot path from the
    heavy-hitter sketches (`MeshTrainer.refresh_hot_rows`); persisted never —
    `hot_sync` writes the rows back into their owner shards at snapshot time so
    checkpoints/export/sync stay byte-identical to the hot-off world.

    Membership is a mini open-addressing probe table (`tables/hash_table.py`
    machinery, built host-side by `parallel/sharded.build_hot_identity`):
    `keys` holds the hot ids in the table's key layout at ~2x load headroom,
    `rank` maps a probe slot to its compact hot row in [0, H); empty slots
    carry rank H. `ids` lists the hot ids by rank (padding -1 / PAIR_EMPTY)
    for writeback/refresh bookkeeping."""

    keys: jax.Array               # (C,) or (C, 2) — probe table, table key layout
    rank: jax.Array               # (C,) int32 — probe slot -> hot row; H = empty
    ids: jax.Array                # (H,) or (H, 2) — hot ids by rank
    weights: jax.Array            # (H, dim) — table dtype
    slots: Dict[str, jax.Array]   # name -> (H, k) f32 (replicated optimizer state)


class MigRows(struct.PyTreeNode):
    """Cold-tail re-sharding state for one table (the other half of Parallax-
    style hybrid placement, `parallel/sharded.py` "COLD-TAIL RE-SHARDING"):
    a trace-time-static set of M measured-heavy COLD rows whose owner shard is
    overridden away from the `id % S` hash home, so a hot home shard sheds
    load it cannot shed through replication alone. Unlike `HotRows` the rows
    are NOT replicated — each keeps exactly one owner; only the id -> owner
    DIRECTORY is replicated so every client routes identically.

    The directory is a mini open-addressing probe table (same machinery as
    the hot probe, built host-side by `parallel/sharded.build_mig_identity`):
    `keys` holds the migrated ids at ~2x load headroom, `rank` maps a probe
    slot to the id's compact migration rank in [0, M) (M = empty), `ids` /
    `owners` list the migrated ids and their assigned owner shard by rank.
    `weights`/`slots` are each shard's ANNEX — M spare rows per shard; only
    the assigned owner's copy of a rank is live (the home-shard main-table
    row goes stale while migrated, exactly like a hot row's). `mig_writeback`
    restores the home copies at snapshot/refresh time so checkpoints, export
    and the sync delta feed stay byte-identical to an unmigrated run.
    Chosen/refreshed off the hot path by `MeshTrainer.migrate_rows` (driven
    by `placement.PlacementController`); persisted never."""

    keys: jax.Array               # (C,) or (C, 2) — directory probe, replicated
    rank: jax.Array               # (C,) int32 — probe slot -> rank; M = empty
    ids: jax.Array                # (M,) or (M, 2) — migrated ids by rank
    owners: jax.Array             # (M,) int32 — assigned owner shard; -1 = pad
    weights: jax.Array            # (M, dim) per shard — the annex (SHARDED)
    slots: Dict[str, jax.Array]   # name -> (M, k) per shard (SHARDED)


class EmbeddingTableState(struct.PyTreeNode):
    """One variable's shard-local storage: weights + optimizer slots.

    For `kind == "hash"` tables, `keys` maps slot -> global id (EMPTY sentinel = -1) and
    lookups go through the open-addressing probe (`tables/hash_table.py`).
    """

    weights: jax.Array                    # (rows, dim)
    slots: Dict[str, jax.Array]           # name -> (rows, k)
    keys: Optional[jax.Array] = None      # (rows,) int64, hash tables only
    # cumulative count of ids that failed to insert (hash tables only; the static-
    # capacity divergence from the reference's unbounded table must be observable)
    overflow: Optional[jax.Array] = None  # () int32
    # replicated hot-row cache (MeshTrainer(hot_rows=...); None = off). NOT
    # serialized: checkpoint/persist/export writers see owner-shard rows only,
    # after the trainer's hot_sync writeback.
    hot: Optional[HotRows] = None
    # cold-tail re-sharding directory + annex (MeshTrainer(mig_rows=...);
    # None = off). NOT serialized either: `hot_sync` writes migrated rows
    # back into their home shards before any snapshot/export/delta reader.
    mig: Optional[MigRows] = None
    # per-row error-feedback residuals for the quantized pull wire
    # (MeshTrainer(error_feedback=...); None = off). Sharded and laid out
    # exactly like `weights`, SERIALIZED like an optimizer slot (reserved
    # slot name "__ef__" in sharded checkpoints and persist deltas): the
    # residual is training state — dropping it at restore would re-bias the
    # int8 wire for every row mid-stream.
    ef: Optional[jax.Array] = None        # (rows, dim) f32


@dataclasses.dataclass(frozen=True)
class EmbeddingSpec:
    """Static description of one embedding variable (hashable; safe as a jit static).

    reference parity: `exb.py:388-443` (layer args) + `variable/Meta.h` (variable meta).
    """

    name: str
    input_dim: int                       # -1 -> hashed 63-bit id space (hash table)
    output_dim: int
    datatype: str = "float32"
    initializer: Initializer = dataclasses.field(default_factory=Uniform)
    optimizer: Optional[SparseOptimizer] = None   # None -> use model default
    num_shards: int = -1                 # -1 -> all mesh devices
    sparse_as_dense: bool = False        # small tables: dense mirrored param instead
    capacity: int = 0                    # hash tables: slots per build; 0 = auto
    # "hbm": the whole table lives in device memory. "host_cached": HBM holds a
    # fixed-capacity cache (`capacity` slots) and the full table lives in host RAM
    # (`tables/host_offload.py`) — tables larger than HBM, the reference's per-
    # variable PMem table selection (`EmbeddingInitOperator.cpp:146-168`).
    storage: str = "hbm"
    variable_id: int = -1
    # batch feature this variable reads its ids from; "" = the variable's own
    # name. Lets two variables share one id stream (e.g. a CTR model's
    # first-order dim-1 table beside the latent table — the reference's
    # DeepCTR linear feature columns likewise re-read the same input,
    # `test/benchmark/criteo_deepctr.py`).
    feature: str = ""
    # multivalent-feature pooling over the trailing id axis: "" (no pooling,
    # the layer emits per-slot rows), "sum", "mean" or "sqrtn". The framework's
    # answer to the reference's RaggedTensor `sparse_read` (`exb.py:308-327`,
    # whose downstream Keras graphs pool the ragged rows): variable-length id
    # lists pad to the static field width with -1 (`data.pad_ragged`) and the
    # pooling masks the pad slots out of both the value and the gradient, so
    # the result equals true varlen pooling (TF's safe_embedding_lookup_sparse
    # combiners) with static TPU-friendly shapes.
    combiner: str = ""

    def __post_init__(self):
        if self.input_dim == 0 or self.input_dim < -1:
            raise ValueError(f"invalid input_dim {self.input_dim}")
        if self.output_dim <= 0:
            raise ValueError(f"invalid output_dim {self.output_dim}")
        if self.storage not in ("hbm", "host_cached"):
            raise ValueError(f"invalid storage {self.storage!r} "
                             "(expected 'hbm' or 'host_cached')")
        if self.storage == "host_cached" and not self.use_hash_table:
            raise ValueError(
                f"embedding {self.name!r}: storage='host_cached' needs a "
                "hash-table variable (input_dim=-1 + capacity) — the device "
                "cache is keyed by id, not by dense row position")
        if self.combiner not in ("", "sum", "mean", "sqrtn"):
            raise ValueError(
                f"embedding {self.name!r}: unknown combiner "
                f"{self.combiner!r} (expected '', 'sum', 'mean' or 'sqrtn')")
        if self.storage == "host_cached" and self.sparse_as_dense:
            raise ValueError(
                f"embedding {self.name!r}: sparse_as_dense (dense-mirrored "
                "'Cache' mode) and storage='host_cached' are mutually "
                "exclusive — a dense mirror bypasses the two-tier table")

    @property
    def use_hash_table(self) -> bool:
        return self.input_dim == -1 or self.input_dim >= HASH_VOCABULARY_THRESHOLD

    @property
    def vocabulary_size(self) -> int:
        return HASH_VOCABULARY_THRESHOLD if self.use_hash_table else self.input_dim

    @property
    def feature_name(self) -> str:
        """The batch["sparse"] key this variable's ids come from."""
        return self.feature or self.name

    @property
    def meta(self) -> EmbeddingVariableMeta:
        return EmbeddingVariableMeta(
            datatype=self.datatype,
            embedding_dim=self.output_dim,
            vocabulary_size=-1 if self.use_hash_table else self.input_dim,
        )

    @property
    def dtype(self):
        return jnp.dtype(self.datatype) if self.datatype != "bfloat16" else jnp.bfloat16

    def rows_per_shard(self, num_shards: int) -> int:
        """ceil(vocab / S), the reference's `reserve_items`
        (`EmbeddingInitOperator.cpp:146-168`)."""
        if self.use_hash_table:
            if self.capacity <= 0:
                raise ValueError(
                    f"hash-table variable {self.name!r} needs an explicit capacity")
            return -(-self.capacity // num_shards)
        return -(-self.input_dim // num_shards)

    def device_bytes(self, optimizer: SparseOptimizer, num_shards: int, *,
                     need_ef: bool = False) -> Dict[str, int]:
        """Analytic PER-DEVICE byte model of this table's base state at
        shard count S, by subcomponent — the shapes `MeshTrainer.
        init_tables` materializes, priced without materializing them
        (utils/memwatch ledger; pinned exact against the live arrays by
        tests). Key lanes cost 8 bytes/row in BOTH layouts (one int64 or a
        uint32 pair); the replicated overflow scalar rides `keys`."""
        rows = self.rows_per_shard(num_shards)
        item = jnp.dtype(self.dtype).itemsize
        out = {
            "weights": rows * self.output_dim * item,
            "slots": rows * 4 * sum(
                optimizer.slot_shapes(self.output_dim).values()),
        }
        if self.use_hash_table:
            out["keys"] = rows * 8 + 4
        if need_ef:
            out["ef"] = rows * self.output_dim * 4
        return out

    def to_config(self) -> dict:
        return {
            "name": self.name,
            "input_dim": self.input_dim,
            "output_dim": self.output_dim,
            "datatype": self.datatype,
            "initializer": self.initializer.to_config(),
            "optimizer": self.optimizer.to_config() if self.optimizer else None,
            "num_shards": self.num_shards,
            "sparse_as_dense": self.sparse_as_dense,
            "capacity": self.capacity,
            "storage": self.storage,
            "variable_id": self.variable_id,
            "feature": self.feature,
            "combiner": self.combiner,
        }

    @classmethod
    def from_config(cls, d: dict) -> "EmbeddingSpec":
        d = dict(d)
        d["initializer"] = make_initializer(d["initializer"])
        d["optimizer"] = make_optimizer(d["optimizer"]) if d.get("optimizer") else None
        return cls(**d)


# ---------------------------------------------------------------------------
# Functional table ops (single shard / single device).  The sharded versions in
# `parallel/sharded.py` run these on each device's shard under shard_map.
# ---------------------------------------------------------------------------


def init_table_state(spec: EmbeddingSpec, optimizer: SparseOptimizer,
                     seed: int = 0, num_shards: int = 1,
                     shard_id: int = 0,
                     error_feedback: bool = False) -> EmbeddingTableState:
    """Materialize one shard's table (reference: lazy `_new_weights` init on first pull,
    `EmbeddingOptimizerVariable.h:242-266`; we init rows eagerly — deterministic per
    (seed, shard), documented divergence: RNG stream differs from lazy order).
    `error_feedback` adds the zero-initialized per-row residual array the
    quantized pull wire accumulates into (`parallel/sharded._serve_rows`)."""
    rows = spec.rows_per_shard(num_shards)
    # fold_in needs uint32 data; the unassigned sentinel (-1, specs built
    # outside an EmbeddingModel, e.g. a bare EmbeddingVariable) maps to a slot
    # no real variable_id reaches (2^15: 131071 * 2^15 still fits uint32)
    # instead of raising OverflowError. Streams of assigned ids are unchanged.
    vid = spec.variable_id if spec.variable_id >= 0 else (1 << 15)
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             vid * 131071 + shard_id)
    weights = spec.initializer(key, (rows, spec.output_dim), spec.dtype)
    slots = optimizer.init_slots(rows, spec.output_dim, spec.dtype)
    keys = None
    overflow = None
    if spec.use_hash_table:
        # x64 on: int64 single-lane keys; x64 off (the default): uint32
        # split-pair keys — 63-bit ids in EITHER config (ops/id64.py)
        from .tables.hash_table import fresh_keys
        keys = fresh_keys(rows)
        overflow = jnp.zeros((), jnp.int32)
    ef = (jnp.zeros((rows, spec.output_dim), jnp.float32)
          if error_feedback else None)
    return EmbeddingTableState(weights=weights, slots=slots, keys=keys,
                               overflow=overflow, ef=ef)


def _flat_ids(spec: EmbeddingSpec, ids: jax.Array):
    """-> (flat ids, row-output shape): split-pair ids ((..., 2) uint32,
    `ops/id64.py`) keep their lane dim flat and drop it from the output.
    Pair dispatch is gated on `use_hash_table`: a uint32 two-field batch on an
    array table must NOT be misread as one 63-bit id per row."""
    from .ops.id64 import is_pair
    if spec.use_hash_table and is_pair(ids):
        return ids.reshape(-1, 2), ids.shape[:-1]
    return ids.reshape(-1), ids.shape


def lookup(spec: EmbeddingSpec, state: EmbeddingTableState,
           ids: jax.Array) -> jax.Array:
    """Single-shard pull: ids (any shape) -> rows (ids.shape + (dim,)).
    reference: `Variable.sparse_read`/`pull_weights` (`exb.py:308-327`)."""
    flat, out_shape = _flat_ids(spec, ids)
    if spec.use_hash_table:
        from .tables.hash_table import hash_lookup
        rows = hash_lookup(state, flat)
    else:
        rows = lookup_rows(state.weights, flat)
    return rows.reshape(out_shape + (spec.output_dim,))


def lookup_train(spec: EmbeddingSpec, state: EmbeddingTableState,
                 ids: jax.Array):
    """Training pull: like `lookup` but hash tables insert unseen ids (lazy init).
    Returns (new_state, rows). Array tables never mutate on pull."""
    flat, out_shape = _flat_ids(spec, ids)
    if spec.use_hash_table:
        from .tables.hash_table import hash_lookup_train
        state, rows = hash_lookup_train(state, flat)
    else:
        rows = lookup_rows(state.weights, flat)
    return state, rows.reshape(out_shape + (spec.output_dim,))


def valid_mask(spec: EmbeddingSpec, ids: jax.Array) -> jax.Array:
    """True where an id slot holds a real id — single-lane ids >= 0, split
    pairs via `pair_valid` (`ops/id64.py`). Shape = `lookup`'s row-output
    shape (the pair lane dim is dropped), so it broadcasts against rows."""
    from .ops.id64 import is_pair, pair_valid
    ids = jnp.asarray(ids)
    if spec.use_hash_table and is_pair(ids):
        return pair_valid(ids)
    return ids >= 0


def np_valid_mask(spec: EmbeddingSpec, ids) -> "np.ndarray":
    """Host-side twin of `valid_mask` for serving paths that hold the ORIGINAL
    numpy ids. They must mask from the numpy array, not from `jnp.asarray(ids)`:
    with x64 off that conversion truncates 63-bit int64 ids to int32, flipping
    real ids whose bit 31 is set to negative — `valid_mask` would silently
    mark them padding and drop their (correctly fetched) rows from the pool."""
    import numpy as np
    ids = np.asarray(ids)
    from .ops.id64 import HI_INVALID, is_pair
    if spec.use_hash_table and is_pair(ids):
        return ids[..., 0] < HI_INVALID
    return ids >= 0


def combine(spec: EmbeddingSpec, ids, rows: jax.Array,
            mask=None) -> jax.Array:
    """Pool multivalent rows (..., F, dim) over the id axis F per
    `spec.combiner`; identity when no combiner is set. Pad slots (-1 /
    EMPTY-pair ids) contribute zero to the pooled value AND receive zero
    gradient through the mask multiply — independent of the separate
    negative-ids-never-train row guarantee. mean/sqrtn divide by the VALID
    count (clamped >= 1: an all-pad row pools to zeros instead of NaN), which
    is exactly TF's safe_embedding_lookup_sparse combiner semantics — the op
    the reference's ragged `sparse_read` consumers feed (`exb.py:308-327`).

    `mask` overrides the id-derived validity — serving paths pass
    `np_valid_mask` computed on the original host int64 ids, which a device
    conversion could truncate (see np_valid_mask)."""
    if not spec.combiner:
        return rows
    m = jnp.asarray(mask) if mask is not None else valid_mask(spec, ids)
    if m.ndim < 2:
        raise ValueError(
            f"embedding {spec.name!r}: combiner={spec.combiner!r} needs ids "
            f"of shape (batch, fields), got rank {m.ndim}")
    mf = m.astype(rows.dtype)[..., None]
    s = jnp.sum(rows * mf, axis=-2)
    if spec.combiner == "sum":
        return s
    cnt = jnp.maximum(jnp.sum(mf, axis=-2), jnp.asarray(1, rows.dtype))
    if spec.combiner == "mean":
        return s / cnt
    return s / jnp.sqrt(cnt)


def serve_rows(spec: EmbeddingSpec, ids, lookup_fn) -> jax.Array:
    """The ONE serving-side embed: `lookup_fn(ids)` + combiner pooling with
    the validity mask taken from the ORIGINAL host ids (np_valid_mask — a
    device conversion would truncate 63-bit int64 ids under x64-off). Both
    `StandaloneModel.predict` and `parallel.ShardedModel.predict` route
    through here so the mask invariant lives in one place."""
    rows = lookup_fn(ids)
    if spec.combiner:
        rows = combine(spec, None, rows, mask=np_valid_mask(spec, ids))
    return rows


def apply_gradients(spec: EmbeddingSpec, state: EmbeddingTableState,
                    optimizer: SparseOptimizer, ids: jax.Array,
                    grads: jax.Array, *, with_load: bool = False):
    """Single-shard push+update fused: duplicate grads summed, optimizer applied once
    per unique id (reference: push `EmbeddingPushOperator.cpp` + store
    `EmbeddingStoreOperator.cpp` collapsed into one step — SPMD needs no batch gate).
    -> the new table state; `with_load` -> (state, the step's apply load:
    `ops/sparse.py` "WHAT THE APPLY WORKS OVER")."""
    flat_ids, _ = _flat_ids(spec, ids)
    flat_grads = grads.reshape(-1, spec.output_dim)
    if spec.use_hash_table:
        from .tables.hash_table import hash_apply_gradients
        return hash_apply_gradients(state, optimizer, flat_ids, flat_grads,
                                    with_load=with_load)
    weights, slots, load = sparse_apply_dense_table(
        optimizer, state.weights, state.slots, flat_ids, flat_grads,
        with_load=True)
    state = state.replace(weights=weights, slots=slots)
    return (state, load) if with_load else state


class Embedding:
    """Drop-in layer handle, mirroring `exb.Embedding` (`exb.py:388-443`).

    Collects itself into the enclosing `EmbeddingModel`'s variable list; the actual
    compute is functional (lookup / apply_gradients) driven by the Trainer.
    """

    def __init__(self, input_dim: int, output_dim: int, *, name: str,
                 datatype: str = "float32",
                 embeddings_initializer: Optional[Initializer] = None,
                 optimizer: Optional[SparseOptimizer] = None,
                 num_shards: int = -1,
                 sparse_as_dense: bool = False,
                 capacity: int = 0,
                 storage: str = "hbm",
                 feature: str = "",
                 combiner: str = ""):
        self.spec = EmbeddingSpec(
            name=name,
            input_dim=input_dim,
            output_dim=output_dim,
            datatype=datatype,
            initializer=embeddings_initializer or Uniform(),
            optimizer=optimizer,
            num_shards=num_shards,
            sparse_as_dense=sparse_as_dense,
            capacity=capacity,
            storage=storage,
            feature=feature,
            combiner=combiner,
        )

    def __repr__(self):
        return f"Embedding({self.spec.name}: {self.spec.input_dim}x{self.spec.output_dim})"
