"""MeshTrainer: the multi-device Trainer — one SPMD program replacing the reference's
master + parameter servers + Horovod workers.

Reuses the single-device `Trainer`'s per-device step functions via hooks:
- dense grads: `psum` over the data axis (reference: Horovod allreduce op=Sum,
  `examples/criteo_deepctr_network.py:53-62`);
- table pull/push: the all_to_all protocol in `parallel/sharded.py`;
- loss: pmean for reporting; per-variable pull/overflow stats psum'd (reference
  accumulators `pull_indices`/`pull_unique`, `EmbeddingPullOperator.cpp:207-252`).

State placement (see `parallel/mesh.py`): tables row-sharded over 'data', dense
replicated, batch sharded on its leading dim. The whole train step runs under
`jax.shard_map` + `jit` with the input state donated (tables update in place in HBM).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..embedding import EmbeddingSpec, EmbeddingTableState, HotRows, MigRows
from ..model import (EmbeddingModel, TrainManyDispatch, TrainState, Trainer,
                     _fold_table_stats, _observe_table_stats, _table_stats,
                     _window_values, init_dense_slots)
from ..optimizers import SparseOptimizer
from ..utils import metrics as _metrics
from ..utils import trace as _trace
from .mesh import DATA_AXIS, make_mesh
from .sharded import (build_hot_identity, build_mig_identity, hot_gather,
                      hot_writeback, mig_gather, mig_writeback,
                      sharded_lookup)


_WINDOW_TABLE_STATS = _metrics.APPLY_STATS + _metrics.OWNER_STATS


def _fold_mesh_window(metrics) -> None:
    """`MeshTrainer._window_fold` (its doc); a function of the window alone,
    so a pending window holds no trainer."""
    vals = _window_values(
        metrics, _WINDOW_TABLE_STATS + ("conflict", "conflict_overflow"))
    for name, v in vals.pop("conflict", {}).items():
        _metrics.observe("exchange.conflict_rows", float(v), "gauge",
                         labels={"table": name})
    if "conflict_overflow" in vals:
        _metrics.observe("exchange.conflict_overflow",
                         float(vals.pop("conflict_overflow")), "gauge")
    _observe_table_stats(vals)


class MeshTrainer(Trainer):
    def __init__(self, model: EmbeddingModel,
                 optimizer: Optional[SparseOptimizer] = None, *,
                 mesh: Optional[Mesh] = None, seed: int = 0,
                 capacity_factor: float = 0.0,
                 on_overflow: str = "count",
                 wire: Optional[str] = None,
                 shard_stats: bool = True,
                 hot_rows: "int | Dict[str, int]" = 0,
                 mig_rows: "int | Dict[str, int]" = 0,
                 hot_wire: Optional[str] = None,
                 error_feedback: Optional[bool] = None,
                 dense_shard: bool = False,
                 dense_wire: Optional[str] = None,
                 dense_topk: Optional[int] = None,
                 dense_stats: bool = False,
                 offload_pipeline: bool = False,
                 offload_densify: int = 1,
                 offload_stage_depth: int = 1,
                 pipeline_steps: bool = False,
                 conflict_factor: float = 0.0,
                 sentinel: bool = False,
                 halt_on_nonfinite: bool = False,
                 measure_every: int = 0):
        super().__init__(model, optimizer, seed,
                         offload_pipeline=offload_pipeline,
                         offload_densify=offload_densify,
                         offload_stage_depth=offload_stage_depth,
                         sentinel=sentinel,
                         halt_on_nonfinite=halt_on_nonfinite,
                         measure_every=measure_every)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.axis = self.mesh.axis_names[0]
        self.num_shards = self.mesh.devices.size  # overrides Trainer.num_shards
        # per-(src,dst) bucket headroom for the a2a exchange; 0 = exact (capacity = n)
        self.capacity_factor = capacity_factor
        # wire payload format for the exchange a2as: None -> $OETPU_WIRE ->
        # bf16 (ops/wire.py; "fp32" opts out of quantization entirely). The
        # encode runs INSIDE the protocol (owner/client edge), so the
        # compiled a2a operands carry this format. A PER-TABLE dict is
        # accepted too ({"big_table": "int8", "*": "fp32"} — "*" the default
        # for unnamed tables): formats resolve once at trace time
        # (`wire_for`), and the exchange splits dim-groups on (dim, fmt) so
        # mixed-format tables ride separate a2a groups while same-format
        # tables stay fused (`_exchange_groups`).
        if isinstance(wire, dict):
            from ..ops import wire as wire_mod
            unknown = [k for k in wire
                       if k != "*" and k not in model.specs]
            if unknown:
                raise ValueError(
                    f"wire= names unknown tables {sorted(unknown)} "
                    f"(model tables: {sorted(model.specs)}; use '*' for "
                    "the default format)")
            for v in wire.values():
                wire_mod.wire_format(v)  # validate each format eagerly
        self.wire = wire
        # wire format of the hot-row backward's dense (H, dim) reduction:
        # None -> follow `wire` (fp32 keeps the round-10 one-psum plan; int8
        # runs the two-stage a2a + all_gather reduce, `sharded._hot_apply`)
        self.hot_wire = hot_wire
        # per-row error-feedback residuals for the lossy pull wire
        # (`EmbeddingTableState.ef`): None -> on exactly when the resolved
        # wire format is int8 on a real mesh (bf16 truncation is unbiased
        # enough for AUC parity; int8 is not — PERF.md round 13)
        self.error_feedback = error_feedback
        # static wire-cost model of the last traced step (set at trace time;
        # also published as exchange.* gauges — utils/metrics.py)
        self.last_wire_cost = None
        # per-shard load accounting inside the jitted step (workload-skew
        # telemetry: `sharded.exchange_load_stats` -> exchange.shard_rows /
        # shard_positions / bucket_fill vectors in the step stats, folded to
        # labeled gauges by `metrics.record_step_stats`). Pure array math on
        # the routing plan; turn off to shave the last percent from a tuned
        # production step
        self.shard_stats = shard_stats
        # bounded buckets can DROP ids (divergence from the reference's
        # unbounded buffers, `EmbeddingPullOperator.cpp:86-112`); the policy
        # when `check_overflow` sees drops: "count" (watch the counters),
        # "grow" (raise capacity_factor, recompile), "raise" (fail loud)
        if on_overflow not in ("count", "grow", "raise"):
            raise ValueError(f"on_overflow={on_overflow!r}: expected "
                             "'count', 'grow', or 'raise'")
        self.on_overflow = on_overflow
        # replicated hot-row cache size per table (int for all PS tables, or
        # {name: H}; 0 = off — the default path must stay free). Hot sets are
        # trace-time STATIC: H rows replicated on every device serve the
        # measured heavy hitters locally (`parallel/sharded.py` "HOT-ROW
        # REPLICATION"); promote/demote between steps with
        # `refresh_hot_rows()` (fed by the round-9 sketches), write back into
        # owner shards with `hot_sync()` (save/persist do it automatically).
        # Silently inert on 1-device meshes (the shard IS local there).
        self.hot_rows = hot_rows
        # cold-tail migration annex capacity per table (int or {name: M};
        # 0 = off). M spare rows per shard plus a replicated id -> owner
        # directory let `migrate_rows` re-home up to M measured-heavy COLD
        # rows per table off their `id % S` hash shard (`parallel/sharded.py`
        # "COLD-TAIL RE-SHARDING") — contents swap between steps, shapes
        # never, so a migration never re-jits. Silently inert on 1-device
        # meshes, like hot_rows. Driven autonomously by
        # `placement.PlacementController`.
        self.mig_rows = mig_rows
        # ZeRO-style dense-state sharding (parallel/zero.py, arXiv:2004.13336):
        # keep dense params replicated but give each replica a 1/S shard of
        # the flattened dense optimizer state — the dense-grad psum becomes
        # reduce_scatter -> chunk update -> all_gather (same wire bytes; a
        # ring all-reduce IS those two collectives), so dense optimizer
        # memory and update FLOPs stop scaling with replica count. fp32
        # training is bit-exact vs replicated and checkpoints/exports/deltas
        # byte-identical (tests/test_zero.py pins both). Inert on 1-device
        # meshes and off by default — ZeRO-off compiles byte-identical HLO
        # (oelint hlo-budget delta 0).
        self.dense_shard = bool(dense_shard)
        # quantized dense ZeRO collectives (round 17): encode the flat dense
        # grad chunk with the round-13 in-band codec before the reduce — the
        # fp32 reduce_scatter becomes an a2a of encoded partials + a
        # per-replica fp32 sum (mirroring the round-13 two-stage hot int8
        # reduce) — and the params all_gather ships the u16 bf16 carrier,
        # with fp32 master weights (and, for int8, a per-replica
        # error-feedback residual) kept as extra `__zero__` flat slots
        # (parallel/zero.py DENSE_MASTER_KEY / DENSE_EF_KEY). Requires
        # dense_shard; inert at mesh size 1 like everything else here.
        # dense_wire="sparse_topk" is the stream-sparse variant (round 23,
        # SparCML arXiv:1802.08021): each replica ships only the k largest-
        # magnitude elements per destination chunk (int8 values + in-band
        # scales + bitcast index lanes, `ops.wire.pack_topk`), the receiver
        # scatter-sums the decoded partials in fp32, and the untransmitted
        # mass accumulates in the same `__dense_ef__` residual int8 uses.
        if dense_wire in ("fp32", "none"):
            dense_wire = None
        if dense_wire is not None:
            if dense_wire not in ("bf16", "int8", "sparse_topk"):
                raise ValueError(
                    f"dense_wire={dense_wire!r}: expected 'int8', 'bf16', "
                    "'sparse_topk', or None/'fp32' (the lossless round-14 "
                    "path)")
            if not self.dense_shard:
                raise ValueError(
                    "dense_wire quantizes the ZeRO dense collectives — "
                    "construct MeshTrainer(dense_shard=True, dense_wire=...)")
        self.dense_wire = dense_wire
        # elements shipped per destination chunk under sparse_topk; None ->
        # auto-size at plan time (`dense_topk_for`: ~1/16 of the chunk,
        # rounded up to whole INBAND_BLOCK codec blocks). A trace-time
        # constant — changing it is a deliberate re-jit
        # (`set_dense_wire`, counted in dense.wire_rejits).
        if dense_topk is not None:
            dense_topk = int(dense_topk)
            if dense_topk <= 0:
                raise ValueError(
                    f"dense_topk={dense_topk}: expected a positive element "
                    "count (or None to auto-size from the chunk)")
            if dense_wire != "sparse_topk":
                raise ValueError(
                    "dense_topk sizes the sparse_topk payload — construct "
                    "MeshTrainer(dense_wire='sparse_topk', dense_topk=...)")
        self.dense_topk = dense_topk
        # publish the dense.grad_density stat (nonzero fraction of the dense
        # grad vector, psum-averaged across replicas on the existing per-key
        # stats psum). Off by default so density-stat-off configs compile
        # byte-identical HLO; `PlacementController(manage_wire=True)` turns
        # it on at prime() to feed `PlacementPolicy.recommend_dense_wire`.
        self.dense_stats = bool(dense_stats)
        # software-pipelined train_many (round 18): prefetch batch t+1's
        # exchange (id plane + speculative row gather) under batch t's dense
        # compute, then re-gather only the rows batch t actually updated (the
        # CONFLICT PATCH, `sharded.grouped_conflict_patch`) so fp32 results
        # stay bit-exact to the serial scan. Static trace-time bool:
        # pipeline_steps=False routes train_many through the base scan
        # untouched — byte-identical HLO (hlo-budget delta 0). Inert on
        # 1-device meshes (nothing to overlap: the exchange is local).
        self.pipeline_steps = bool(pipeline_steps)
        # conflict-patch compaction cap as a fraction of the bucket capacity:
        # 0 (default) keeps the patch EXACT (pcap = cap, bit-exactness
        # guaranteed); 0 < f < 1 bounds patch wire bytes at f * cap rows per
        # (src, dst) pair — overflowed rows keep their one-step-stale
        # speculative value (counted in the window's "conflict_overflow")
        if not (0.0 <= float(conflict_factor) <= 1.0):
            raise ValueError(f"conflict_factor={conflict_factor!r}: expected "
                             "0.0 (exact) .. 1.0")
        self.conflict_factor = float(conflict_factor)
        self._zero_plan = None
        self._zero_fns: Dict[str, Any] = {}
        self._hot_fns: Dict[str, Any] = {}
        self._mig_fns: Dict[str, Any] = {}
        self._train_step_fn = None
        self._eval_step_fn = None

    # -- overflow governance -------------------------------------------------

    @staticmethod
    def overflow_count(metrics) -> int:
        """Exchange-bucket drops in one step's (or one scan window's) metrics."""
        import numpy as np
        total = int(np.asarray(metrics.get("overflow", 0)))
        for k, v in metrics.get("stats", {}).items():
            if k.endswith("_overflow"):
                total += int(np.asarray(v))
        return total

    def check_overflow(self, metrics, *, growth: float = 2.0) -> bool:
        """Drive the overflow policy with a step/window's metrics. Returns
        True when the exchange capacity GREW — the caller must rebuild its
        jitted step (`jit_train_step`/`jit_train_many` return fresh compiled
        fns after a growth; bucket shapes are trace-time constants, so this
        is the recompile-between-windows adaptive scheme).

        The reference's buffers are dynamically sized and can never drop
        (`EmbeddingPullOperator.cpp:86-112`); bounded buckets are the static-
        shape price, and this policy is the governance: f grows until the
        hottest shard fits (capped at f = S, where the bucket equals the
        exact-mode capacity and overflow is impossible)."""
        dropped = self.overflow_count(metrics)
        if dropped == 0:
            return False
        if self.on_overflow == "raise":
            raise RuntimeError(
                f"{dropped} ids overflowed the a2a exchange buckets this "
                f"window (capacity_factor={self.capacity_factor}); raise "
                "capacity_factor (sizing rule in parallel/sharded.py) or "
                "construct MeshTrainer(on_overflow='grow')")
        if self.on_overflow != "grow" or self.capacity_factor <= 0:
            return False  # exact mode cannot drop; "count" just watches
        new = min(self.capacity_factor * growth, float(self.num_shards))
        if new == self.capacity_factor:
            return False
        _metrics.observe("exchange.capacity_grown", 1)
        self.capacity_factor = new
        self._train_step_fn = None
        self._eval_step_fn = None
        self._train_many_fn = None
        return True

    # -- checkpointing -------------------------------------------------------

    def save(self, state, path: str, **kw):
        """Per-shard streaming dump (`parallel/checkpoint.py`): each process
        writes only its addressable shards, peak host memory O(chunk) — the
        reference's server-side per-shard dump, `EmbeddingDumpOperator.cpp:36-96`.
        `Trainer.load` / `MeshTrainer.load` restore it at any mesh size.
        Hot-replicated rows write back into their owner shards first and
        ZeRO dense slots unshard (`externalize`), so the dump equals a
        hot-off, ZeRO-off run's byte for byte."""
        state = self.externalize(state)
        from .checkpoint import save_sharded
        return self._stage_save(
            lambda p: save_sharded(
                state, self.model, p, num_shards=self.num_shards,
                offload_stores=self.offload_store_snapshots(state), **kw),
            path)

    # -- device-memory accounting (utils/memwatch ledger) --------------------

    def _hot_device_bytes(self, spec: EmbeddingSpec, H: int) -> int:
        """Analytic per-device bytes of one table's replicated hot cache at
        H rows: probe keys/rank (C = max(2H, 8) slots, `build_hot_identity`
        layout), id list, replicated weights + f32 optimizer slots."""
        if H <= 0:
            return 0
        C = max(2 * H, 8)
        kb = 8 if spec.use_hash_table else 4  # int64 or uint32-pair vs int32
        item = jnp.dtype(spec.dtype).itemsize
        opt = self.opt_for(spec)
        widths = sum(opt.slot_shapes(spec.output_dim).values())
        return (C * kb + C * 4 + H * kb
                + H * spec.output_dim * item + H * 4 * widths)

    def _mig_device_bytes(self, spec: EmbeddingSpec, M: int) -> int:
        """Analytic per-device bytes of one table's migration set at M rows:
        replicated directory (probe keys/rank, ids, owners) + this device's
        annex slice (M rows of the (M*S) sharded weights/slots)."""
        if M <= 0:
            return 0
        C = max(2 * M, 8)
        kb = 8 if spec.use_hash_table else 4
        item = jnp.dtype(spec.dtype).itemsize
        opt = self.opt_for(spec)
        widths = sum(opt.slot_shapes(spec.output_dim).values())
        return (C * kb + C * 4 + M * kb + M * 4
                + M * spec.output_dim * item + M * 4 * widths)

    def memory_model(self, state: Optional[TrainState] = None
                     ) -> Dict[str, Any]:
        """Per-device byte model of everything this trainer keeps resident.

        -> {"analytic": {"component/table": bytes}, "measured": {...},
            "host": {...}, "device_total_bytes": int}. The ANALYTIC view
        prices the shapes the trainer WOULD materialize (specs + plan only
        — usable before init, and before a resize commits); the MEASURED
        view walks the live `state` arrays (largest addressable shard per
        array — replicated arrays count full, sharded 1/S). The two agree
        exactly on every component (pinned by tests/test_flightdata.py);
        dense components need `state` (leaf shapes live there)."""
        from ..utils import memwatch as _memwatch
        analytic: Dict[str, int] = {}
        measured: Dict[str, int] = {}
        host: Dict[str, int] = {}
        for name, spec in self.model.ps_specs().items():
            if spec.storage == "host_cached":
                ot = self.offload.get(name)
                if ot is not None:
                    analytic[f"offload_cache/{name}"] = \
                        ot.device_cache_bytes()
                    measured[f"offload_cache/{name}"] = \
                        _memwatch.tree_device_bytes(ot.state)
                    host[f"host_store/{name}"] = ot.store.nbytes()
                continue
            opt = self.opt_for(spec)
            for sub, b in spec.device_bytes(
                    opt, self.num_shards,
                    need_ef=self.ef_for(name)).items():
                analytic[f"table_{sub}/{name}"] = b
            H = self.hot_rows_for(name)
            if H:
                analytic[f"hot/{name}"] = self._hot_device_bytes(spec, H)
            M = self.mig_rows_for(name)
            if M:
                analytic[f"mig/{name}"] = self._mig_device_bytes(spec, M)
            if state is not None:
                ts = state.tables.get(name)
                if ts is None:
                    continue
                measured[f"table_weights/{name}"] = \
                    _memwatch.array_device_bytes(ts.weights)
                measured[f"table_slots/{name}"] = \
                    _memwatch.tree_device_bytes(ts.slots)
                if ts.keys is not None:
                    measured[f"table_keys/{name}"] = (
                        _memwatch.array_device_bytes(ts.keys)
                        + (_memwatch.array_device_bytes(ts.overflow)
                           if ts.overflow is not None else 0))
                if ts.ef is not None:
                    measured[f"table_ef/{name}"] = \
                        _memwatch.array_device_bytes(ts.ef)
                if ts.hot is not None:
                    measured[f"hot/{name}"] = \
                        _memwatch.tree_device_bytes(ts.hot)
                if ts.mig is not None:
                    measured[f"mig/{name}"] = \
                        _memwatch.tree_device_bytes(ts.mig)
        if state is not None:
            self._dense_memory(state, analytic, measured)
        totals = measured or analytic
        return {"analytic": analytic, "measured": measured, "host": host,
                "device_total_bytes": sum(totals.values())}

    def _dense_memory(self, state: TrainState, analytic: Dict[str, int],
                      measured: Dict[str, int]) -> None:
        """Dense tower components (params replicated; slots flat-sharded
        under ZeRO, per-leaf replicated otherwise)."""
        from ..utils import memwatch as _memwatch
        from . import zero
        measured["dense_params"] = \
            _memwatch.tree_device_bytes(state.dense_params)
        analytic["dense_params"] = measured["dense_params"]
        slots = state.dense_slots
        if zero.is_sharded_slots(slots):
            flat = slots[zero.ZERO_KEY]
            plan = self._zero_plan_for(self._dense_trainable(state))
            has_ef = zero.DENSE_EF_KEY in flat
            has_master = zero.DENSE_MASTER_KEY in flat
            analytic.update(zero.plan_device_bytes(
                plan, ef=has_ef, master=has_master))
            measured["zero_slots"] = sum(
                _memwatch.array_device_bytes(v) for k, v in flat.items()
                if k not in (zero.DENSE_EF_KEY, zero.DENSE_MASTER_KEY))
            if has_ef:
                measured["zero_ef"] = \
                    _memwatch.array_device_bytes(flat[zero.DENSE_EF_KEY])
            if has_master:
                measured["zero_master"] = _memwatch.array_device_bytes(
                    flat[zero.DENSE_MASTER_KEY])
        elif slots is not None:
            measured["dense_slots"] = _memwatch.tree_device_bytes(slots)
            analytic["dense_slots"] = measured["dense_slots"]

    def publish_memory(self, state: Optional[TrainState] = None
                       ) -> Dict[str, Any]:
        """Push the model into the memwatch ledger (`memory.bytes{
        component=,table=}` gauges) and reconcile against live device stats
        where the backend reports them. Host-side only — never touches jit."""
        from ..utils import memwatch as _memwatch
        model = self.memory_model(state)
        view = dict(model["analytic"])
        view.update(model["measured"])  # measured wins where both exist
        for key, nbytes in view.items():
            comp, _, table = key.partition("/")
            labels = {"table": table} if table else None
            _memwatch.WATCH.set_component(comp, nbytes, labels=labels)
        for key, nbytes in model["host"].items():
            comp, _, table = key.partition("/")
            _memwatch.WATCH.set_component(
                comp, nbytes, labels={"table": table} if table else None,
                host=True)
        _memwatch.WATCH.publish()
        _memwatch.WATCH.sample_devices()
        return model

    # -- hot-row replication (skew-aware hybrid placement) -------------------

    def hot_rows_for(self, name: str) -> int:
        """Replicated hot-cache rows for one table (0 = off). Inert at mesh
        size 1 and for host-cached tables (their own cache tier governs)."""
        if self.num_shards <= 1:
            return 0
        spec = self.model.specs.get(name)
        if spec is None or spec.sparse_as_dense \
                or spec.storage == "host_cached":
            return 0
        if isinstance(self.hot_rows, dict):
            return int(self.hot_rows.get(name, 0))
        return int(self.hot_rows)

    @property
    def hot_enabled(self) -> bool:
        return any(self.hot_rows_for(n) for n in self.model.ps_specs())

    def _hot_specs(self) -> Dict[str, EmbeddingSpec]:
        return {n: s for n, s in self.model.ps_specs().items()
                if self.hot_rows_for(n)}

    # -- cold-tail re-sharding (owner-assignment indirection) ----------------

    def mig_rows_for(self, name: str) -> int:
        """Migration annex rows for one table (0 = off). Inert at mesh size 1
        and for host-cached tables, same gates as `hot_rows_for`."""
        if self.num_shards <= 1:
            return 0
        spec = self.model.specs.get(name)
        if spec is None or spec.sparse_as_dense \
                or spec.storage == "host_cached":
            return 0
        if isinstance(self.mig_rows, dict):
            return int(self.mig_rows.get(name, 0))
        return int(self.mig_rows)

    @property
    def mig_enabled(self) -> bool:
        return any(self.mig_rows_for(n) for n in self.model.ps_specs())

    def _mig_specs(self) -> Dict[str, EmbeddingSpec]:
        return {n: s for n, s in self.model.ps_specs().items()
                if self.mig_rows_for(n)}

    # -- per-table wire resolution -------------------------------------------

    def wire_for(self, name: str) -> str:
        """The resolved wire format for ONE table: with a per-table dict the
        table's entry wins, then the dict's "*" default, then the usual
        $OETPU_WIRE/bf16 chain; a plain string/None resolves globally.
        Resolution happens at trace time — format changes re-jit, content
        never does."""
        from ..ops import wire as wire_mod
        if isinstance(self.wire, dict):
            return wire_mod.wire_format(
                self.wire.get(name, self.wire.get("*")))
        return wire_mod.wire_format(self.wire)

    def wire_default(self) -> str:
        """The resolved format tables without a dict entry get (the global
        format when `wire` is not a dict) — what `hot_wire=None` follows."""
        from ..ops import wire as wire_mod
        if isinstance(self.wire, dict):
            return wire_mod.wire_format(self.wire.get("*"))
        return wire_mod.wire_format(self.wire)

    # -- error feedback (lossy-pull residuals) -------------------------------

    def ef_for(self, name: str) -> bool:
        """Whether this table carries the per-row error-feedback residual
        (`EmbeddingTableState.ef`). Inert at mesh size 1 (no wire) and for
        dense-mirrored / host-cached tables (they never ride the exchange);
        default = on iff the table's resolved wire format is int8."""
        if self.num_shards <= 1:
            return False
        spec = self.model.specs.get(name)
        if spec is None or spec.sparse_as_dense \
                or spec.storage == "host_cached":
            return False
        if self.error_feedback is not None:
            return bool(self.error_feedback)
        return self.wire_for(name) == "int8"

    # -- ZeRO dense-state sharding (parallel/zero.py) ------------------------

    @property
    def zero_enabled(self) -> bool:
        """Whether the dense update runs sharded. Inert at mesh size 1 (the
        chunk IS the whole vector there — nothing to save)."""
        return self.dense_shard and self.num_shards > 1

    def _dense_trainable(self, state: TrainState):
        """The trainable dense subtree (what dense_slots covers — modules
        with frozen state split it out, see Trainer.init)."""
        split = getattr(self.model.module, "split_params", None)
        return (split(state.dense_params)[0] if split is not None
                else state.dense_params)

    def _zero_plan_for(self, params):
        """The (cached) flat layout for the trainable subtree. Shapes are
        model statics, so one plan serves trace time and the host-side
        conversions alike."""
        if self._zero_plan is None:
            from ..ops import wire as wire_mod
            from . import zero
            # dense_wire needs whole in-band codec blocks per chunk; the
            # extra zero padding is inert (and absent for fp32 — the
            # round-14 layout stays bit-identical)
            align = wire_mod.INBAND_BLOCK if self.dense_wire else 1
            self._zero_plan = zero.build_plan(params, self.optimizer,
                                              self.num_shards, align=align)
        return self._zero_plan

    @property
    def dense_ef_enabled(self) -> bool:
        """Dense wire modes that carry the `__dense_ef__` residual: int8's
        quantization bias and sparse_topk's untransmitted mass both need
        error feedback; bf16 truncation is unbiased enough without."""
        return self.dense_wire in ("int8", "sparse_topk")

    def dense_topk_for(self, plan) -> int:
        """Resolved trace-time k for dense_wire='sparse_topk': the explicit
        `dense_topk` clamped to the chunk, else ~1/16 of the chunk rounded
        up to whole INBAND_BLOCK codec blocks (at the sparse price of ~5.125
        bytes per transmitted element that default is ~0.28x the int8 dense
        path's grad bytes — comfortably under the Densifying crossover)."""
        from ..ops import wire as wire_mod
        if plan.chunk <= 0:
            return 0
        k = self.dense_topk
        if k is None:
            k = -(-plan.chunk // 16)
            k = -(-k // wire_mod.INBAND_BLOCK) * wire_mod.INBAND_BLOCK
        return max(1, min(int(k), plan.chunk))

    def dense_to_sharded(self, state: TrainState) -> TrainState:
        """Baseline per-leaf dense_slots -> the flat sharded form (no-op when
        ZeRO is off or the state is already sharded). Pure concats — a
        round trip through `dense_to_replicated` is byte-identical."""
        if not self.zero_enabled:
            return state
        from . import zero
        if zero.is_sharded_slots(state.dense_slots):
            return state
        plan = self._zero_plan_for(self._dense_trainable(state))
        if plan.total == 0:
            return state
        zero.check_scalar_slots_equal(plan, state.dense_slots)
        if "shard" not in self._zero_fns:
            extra = []
            if self.dense_wire:
                # dense_wire rides two more flat slots: fp32 masters for this
                # replica's chunk (the all_gather ships a rounded bf16
                # carrier) and — int8/sparse_topk — the full-length
                # per-replica error-feedback residual. Both are derived/zero
                # state: `unshard_slots` iterates plan slots only, so
                # externalize() drops them and checkpoints stay
                # byte-identical to a dense_wire-off run.
                extra.append(zero.DENSE_MASTER_KEY)
                if self.dense_ef_enabled:
                    extra.append(zero.DENSE_EF_KEY)
            out_sh = {zero.ZERO_KEY: {
                k: NamedSharding(self.mesh,
                                 P(None, self.axis) if k in plan.vector_slots
                                 or k in extra else P())
                for k in (*plan.vector_slots, *plan.scalar_slots, *extra)}}

            def shard(slots, trainable):
                flat = dict(zero.shard_slots(plan, slots))
                if self.dense_wire:
                    flat[zero.DENSE_MASTER_KEY] = \
                        zero.flatten_tree(plan, trainable).reshape(1, -1)
                    if self.dense_ef_enabled:
                        flat[zero.DENSE_EF_KEY] = jnp.zeros(
                            (1, plan.num_shards * plan.padded), jnp.float32)
                return {zero.ZERO_KEY: flat}

            self._zero_fns["shard"] = jax.jit(shard, out_shardings=out_sh)
        return state.replace(
            dense_slots=self._zero_fns["shard"](
                state.dense_slots, self._dense_trainable(state)))

    def dense_to_replicated(self, state: TrainState) -> TrainState:
        """The flat sharded dense_slots -> the baseline per-leaf form (no-op
        when not sharded). This is the external layout: checkpoint / persist
        / export writers see exactly what a ZeRO-off run holds."""
        from . import zero
        if not zero.is_sharded_slots(state.dense_slots):
            return state
        plan = self._zero_plan_for(self._dense_trainable(state))
        if "unshard" not in self._zero_fns:
            self._zero_fns["unshard"] = jax.jit(
                lambda fs: zero.unshard_slots(plan, fs),
                out_shardings=NamedSharding(self.mesh, P()))
        new_slots = self._zero_fns["unshard"](
            state.dense_slots[zero.ZERO_KEY])
        if not self.dense_wire:
            return state.replace(dense_slots=new_slots)
        # dense_wire: the replicated forward params carry the bf16-carrier
        # all_gather's rounding — the external form must hold the fp32
        # masters instead (exactly what a dense_wire-off run would hold, and
        # what dense_to_sharded seeds the masters from on the way back in).
        # The int8/sparse_topk error-feedback residual is dropped here and
        # re-seeded to zeros on load: EF is a convergence aid, not model
        # state.
        if "master" not in self._zero_fns:
            self._zero_fns["master"] = jax.jit(
                lambda fm, tr: zero.unflatten_tree(plan, fm.reshape(-1), tr),
                out_shardings=NamedSharding(self.mesh, P()))
        new_trainable = self._zero_fns["master"](
            state.dense_slots[zero.ZERO_KEY][zero.DENSE_MASTER_KEY],
            self._dense_trainable(state))
        split = getattr(self.model.module, "split_params", None)
        if split is not None:
            new_params = self.model.module.merge_params(
                new_trainable, split(state.dense_params)[1])
        else:
            new_params = new_trainable
        return state.replace(dense_slots=new_slots, dense_params=new_params)

    def externalize(self, state: TrainState) -> TrainState:
        """See Trainer.externalize: placement writeback + dense unshard."""
        return self.dense_to_replicated(self.hot_sync(state))

    def set_dense_wire(self, state: TrainState, dense_wire,
                       dense_topk=None) -> TrainState:
        """Flip the dense-gradient wire on a LIVE trainer (the
        `PlacementController(manage_wire=True)` hook, usable directly too).
        No-op when the format and k already match. Otherwise: unshard to
        the external fp32 form (masters land in dense_params, wire-only
        slots drop), swap the knobs, drop the compiled artifacts — the
        flat layout's alignment and extra slots are format-dependent, so
        this is a counted re-jit, not a content swap — and re-shard under
        the new format. The int8/sparse_topk error-feedback residual
        re-seeds to zeros, same as a checkpoint round trip."""
        if dense_wire in (None, "fp32"):
            dense_wire = None
        elif dense_wire not in ("int8", "bf16", "sparse_topk"):
            raise ValueError(
                f"set_dense_wire: dense_wire={dense_wire!r}: expected "
                "'int8', 'bf16', 'sparse_topk', or None/'fp32'")
        if dense_topk is not None:
            if dense_wire != "sparse_topk":
                raise ValueError(
                    "set_dense_wire: dense_topk only applies to "
                    "dense_wire='sparse_topk'")
            dense_topk = int(dense_topk)
            if dense_topk <= 0:
                raise ValueError(f"set_dense_wire: dense_topk={dense_topk} "
                                 "must be positive")
        if dense_wire == self.dense_wire and dense_topk == self.dense_topk:
            return state
        state = self.dense_to_replicated(state)
        self.dense_wire = dense_wire
        self.dense_topk = dense_topk
        # layout + codec are trace-time statics: rebuild the plan and every
        # compiled program that baked them in
        self._zero_plan = None
        self._zero_fns = {}
        self._train_step_fn = None
        self._eval_step_fn = None
        self._train_many_fn = None
        _metrics.observe("dense.wire_rejits", 1)
        return self.dense_to_sharded(state)

    # -- sharding specs ------------------------------------------------------

    def _table_pspec(self, spec: EmbeddingSpec,
                     hot: Optional[bool] = None,
                     mig: Optional[bool] = None,
                     ef: Optional[bool] = None) -> EmbeddingTableState:
        """PartitionSpec pytree for one table's state. `hot`/`mig`/`ef`
        override whether the hot-cache / migration / error-feedback subtrees
        are included (default: iff the trainer enables them for this table —
        the managed states always carry them then)."""
        if hot is None:
            hot = bool(self.hot_rows_for(spec.name))
        if mig is None:
            mig = bool(self.mig_rows_for(spec.name))
        if ef is None:
            ef = self.ef_for(spec.name)
        hot_spec = None
        if hot:
            hot_spec = HotRows(
                keys=P(), rank=P(), ids=P(), weights=P(),
                slots={k: P() for k in
                       self.opt_for(spec).slot_shapes(spec.output_dim)})
        mig_spec = None
        if mig:
            # directory replicated (every source must route identically);
            # annex SHARDED — each shard's M spare rows are its own
            mig_spec = MigRows(
                keys=P(), rank=P(), ids=P(), owners=P(),
                weights=P(self.axis),
                slots={k: P(self.axis) for k in
                       self.opt_for(spec).slot_shapes(spec.output_dim)})
        # row-sharded specs are spelled WITHOUT the trailing None (`P(axis)`,
        # not `P(axis, None)`): jit outputs carry the trimmed spelling, and
        # PartitionSpec('data', None) != PartitionSpec('data') as a jit cache
        # key — the untrimmed spelling on the init-committed tables made the
        # SECOND train step recompile the whole program (caught by
        # utils/guards.assert_no_recompile; every placement site must agree)
        return EmbeddingTableState(
            weights=P(self.axis),
            slots={k: P(self.axis)
                   for k in self.opt_for(spec).slot_shapes(spec.output_dim)},
            keys=P(self.axis) if spec.use_hash_table else None,
            overflow=P() if spec.use_hash_table else None,
            hot=hot_spec,
            mig=mig_spec,
            ef=P(self.axis) if ef else None,  # residuals shard like weights
        )

    def _dense_slots_pspec(self, slots):
        """Replicated per-leaf baseline, or — the flat ZeRO form — vector
        slots sharded on their padded axis (each replica holds the (1, C)
        chunk it updates) with the shared scalar slots replicated."""
        from . import zero
        if zero.is_sharded_slots(slots):
            return {zero.ZERO_KEY: {
                k: P() if v.shape[1] == 1 else P(None, self.axis)
                for k, v in slots[zero.ZERO_KEY].items()}}
        return jax.tree_util.tree_map(lambda _: P(), slots)

    def _state_pspec_tree(self, state: TrainState):
        """Full-pytree spec: replicated everywhere except the tables (and
        the ZeRO dense_slots, when sharded)."""
        table_specs = {name: self._table_pspec(spec)
                       for name, spec in self.model.ps_specs().items()}
        return TrainState(
            step=P(),
            dense_params=jax.tree_util.tree_map(lambda _: P(), state.dense_params),
            dense_slots=self._dense_slots_pspec(state.dense_slots),
            tables=table_specs,
            model_version=P(),
        )

    def _batch_pspec(self, batch):
        return jax.tree_util.tree_map(lambda _: P(self.axis), batch)

    def _logits_pspec(self):
        return P(self.axis)

    # -- init ----------------------------------------------------------------

    def _init_state(self, sample_batch) -> TrainState:
        """Global TrainState: dense params replicated; tables created directly sharded
        (jit + out_shardings — a full table never materializes on one device)."""
        base = super()._init_state(sample_batch)
        rep = NamedSharding(self.mesh, P())
        return self.dense_to_sharded(TrainState(
            step=jax.device_put(base.step, rep),
            dense_params=jax.device_put(base.dense_params, rep),
            dense_slots=jax.device_put(base.dense_slots, rep),
            tables=base.tables,  # already sharded by init_tables below
            model_version=jax.device_put(base.model_version, rep),
        ))

    def init_tables(self):
        self._check_num_shards()
        mesh = self.mesh
        tables = {}
        for name, spec in self.model.ps_specs().items():
            if spec.storage == "host_cached":
                from ..tables.host_offload import HostOffloadTable
                ot = HostOffloadTable(spec, self.opt_for(spec), seed=self.seed,
                                      mesh=mesh, axis=self.axis,
                                      pipeline=self.offload_pipeline,
                                      densify_k=self.offload_densify,
                                      stage_depth=self.offload_stage_depth)
                self.offload[name] = ot
                tables[name] = ot.state
                continue
            opt = self.opt_for(spec)
            rows = spec.rows_per_shard(self.num_shards) * self.num_shards

            need_ef = self.ef_for(name)

            def mk(spec=spec, opt=opt, rows=rows, need_ef=need_ef):
                from ..tables.hash_table import fresh_keys
                key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                         spec.variable_id * 131071)
                weights = spec.initializer(key, (rows, spec.output_dim), spec.dtype)
                slots = opt.init_slots(rows, spec.output_dim)
                keys = fresh_keys(rows) if spec.use_hash_table else None
                overflow = (jnp.zeros((), jnp.int32)
                            if spec.use_hash_table else None)
                ef = (jnp.zeros((rows, spec.output_dim), jnp.float32)
                      if need_ef else None)
                return EmbeddingTableState(weights=weights, slots=slots, keys=keys,
                                           overflow=overflow, ef=ef)

            shardings = jax.tree_util.tree_map(
                lambda p: NamedSharding(mesh, p),
                self._table_pspec(spec, hot=False, mig=False),
                is_leaf=lambda x: isinstance(x, P))
            ts = jax.jit(mk, out_shardings=shardings)()
            H = self.hot_rows_for(name)
            if H:
                # start with an all-EMPTY replicated cache (no hot ids until
                # the first refresh_hot_rows promotes from the sketches)
                ident = build_hot_identity(spec, H, None, key_template=ts.keys)
                hot = HotRows(
                    keys=jnp.asarray(ident["keys"]),
                    rank=jnp.asarray(ident["rank"]),
                    ids=jnp.asarray(ident["ids"]),
                    weights=jnp.zeros((H, spec.output_dim), spec.dtype),
                    slots=opt.init_slots(H, spec.output_dim))
                ts = ts.replace(hot=jax.device_put(
                    hot, NamedSharding(mesh, P())))
            M = self.mig_rows_for(name)
            if M:
                # all-EMPTY directory (routes nothing off home) + zeroed
                # annex; migrate_rows installs real moves later
                ts = ts.replace(mig=self._empty_mig(spec, ts, M))
            tables[name] = ts
        return tables

    def _empty_mig(self, spec: EmbeddingSpec, ts: EmbeddingTableState,
                   M: int) -> MigRows:
        mesh = self.mesh
        ident = build_mig_identity(spec, M, num_shards=self.num_shards,
                                   key_template=ts.keys)
        rep = NamedSharding(mesh, P())
        shd = NamedSharding(mesh, P(self.axis))
        opt = self.opt_for(spec)
        return MigRows(
            keys=jax.device_put(jnp.asarray(ident["keys"]), rep),
            rank=jax.device_put(jnp.asarray(ident["rank"]), rep),
            ids=jax.device_put(jnp.asarray(ident["ids"]), rep),
            owners=jax.device_put(jnp.asarray(ident["owners"]), rep),
            weights=jax.device_put(
                jnp.zeros((M * self.num_shards, spec.output_dim),
                          spec.dtype), shd),
            slots={k: jax.device_put(v, shd) for k, v in
                   opt.init_slots(M * self.num_shards,
                                  spec.output_dim).items()})

    # -- hot-set lifecycle (writeback / promote / demote off the hot path) ---

    def _hot_jit(self, mode: str):
        """Jitted shard_map over the hot tables for one lifecycle mode:
        'sync' (writeback only), 'refresh' (writeback + install new identity +
        gather), 'fill' (gather into states that carry no cache yet).
        Shapes are static, so each mode compiles ONCE ever — promote/demote
        is array-content swaps, never a re-jit. Operates on tables with the
        migration subtree STRIPPED (hot ops never touch it; callers reattach
        it unchanged) so the compiled fns are placement-combination
        agnostic."""
        if mode in self._hot_fns:
            return self._hot_fns[mode]
        specs = self._hot_specs()
        tspec_in = {n: self._table_pspec(s, hot=(mode != "fill"), mig=False)
                    for n, s in specs.items()}
        tspec_out = {n: self._table_pspec(s, hot=True, mig=False)
                     for n, s in specs.items()}
        axis = self.axis

        if mode == "sync":
            def fn(tables):
                return {name: hot_writeback(spec, tables[name], axis=axis)
                        for name, spec in specs.items()}
            in_specs = (tspec_in,)
        else:
            def fn(tables, idents):
                out = {}
                for name, spec in specs.items():
                    ts = tables[name]
                    if mode == "refresh":
                        ts = hot_writeback(spec, ts, axis=axis)
                    out[name] = hot_gather(spec, ts, idents[name], axis=axis)
                return out
            in_specs = (tspec_in, {n: P() for n in specs})

        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=tspec_out, check_vma=False)
        self._hot_fns[mode] = jax.jit(sm)
        return self._hot_fns[mode]

    def _hot_sub(self, state: TrainState, *, need_hot: bool = True):
        sub = {n: state.tables[n] for n in self._hot_specs()}
        if need_hot:
            missing = [n for n, ts in sub.items() if ts.hot is None]
            if missing:
                raise ValueError(
                    f"tables {missing} carry no hot cache — states managed "
                    "by a hot-enabled MeshTrainer must come from its init()/"
                    "load()/refresh_hot_rows() (a restored state needs "
                    "MeshTrainer.load to re-attach the cache)")
        return sub

    @staticmethod
    def _run_stripped(fn, sub, field, *extra):
        """Run a lifecycle jit over `sub` with the OTHER placement subtree
        (`field`: 'hot' or 'mig') stripped, reattaching it unchanged after —
        hot ops never touch migration state and vice versa, so each compiled
        fn stays agnostic to the other feature's on/off."""
        kept = {n: getattr(ts, field) for n, ts in sub.items()}
        stripped = {n: ts.replace(**{field: None}) for n, ts in sub.items()}
        new = fn(stripped, *extra) if extra else fn(stripped)
        return {n: ts.replace(**{field: kept[n]}) for n, ts in new.items()}

    def hot_sync(self, state: TrainState) -> TrainState:
        """The placement writeback hook: restore every row the placement
        layer serves from somewhere other than its home shard — replicated
        HOT rows scatter back into their owner shards, MIGRATED rows copy
        back from their assigned owner's annex (one all_gather) — and return
        the updated state; cache, directory and annex stay live and
        authoritative. Call before handing raw table state to anything
        outside the trainer (export, custom readers) — `save` and the
        persisters (`persist.py`) call it automatically, which is what keeps
        checkpoints/exports/sync deltas byte-identical to a placement-off
        run."""
        if not self.hot_enabled and not self.mig_enabled:
            return state
        tables = dict(state.tables)
        if self.hot_enabled:
            tables.update(self._run_stripped(
                self._hot_jit("sync"), self._hot_sub(state), "mig"))
        if self.mig_enabled:
            sub = {n: tables[n] for n in self._mig_specs()
                   if tables[n].mig is not None}
            if sub:
                tables.update(self._run_stripped(
                    self._mig_jit("sync"), sub, "hot"))
        return state.replace(tables=tables)

    # -- cold-tail migration lifecycle ---------------------------------------

    def _mig_jit(self, mode: str, names=None):
        """Jitted shard_map over (a subset of) the migration tables for one
        lifecycle mode: 'sync' (home writeback only), 'migrate' (writeback +
        install new directory + fill annex), 'fill' (install into states
        carrying no directory yet — load/attach). Compiles once per
        (mode, table subset); directory swaps are content-only, never a
        re-jit. Operates with the hot subtree STRIPPED (see `_hot_jit`)."""
        specs = self._mig_specs()
        if names is not None:
            specs = {n: specs[n] for n in names}
        key = (mode, tuple(sorted(specs)))
        if key in self._mig_fns:
            return self._mig_fns[key]
        tspec_in = {n: self._table_pspec(s, hot=False, mig=(mode != "fill"))
                    for n, s in specs.items()}
        tspec_out = {n: self._table_pspec(s, hot=False, mig=True)
                     for n, s in specs.items()}
        axis = self.axis

        if mode == "sync":
            def fn(tables):
                return {name: mig_writeback(spec, tables[name], axis=axis)
                        for name, spec in specs.items()}
            in_specs = (tspec_in,)
        else:
            def fn(tables, idents):
                out = {}
                for name, spec in specs.items():
                    ts = tables[name]
                    if mode == "migrate":
                        ts = mig_writeback(spec, ts, axis=axis)
                    out[name] = mig_gather(spec, ts, idents[name], axis=axis)
                return out
            in_specs = (tspec_in, {n: P() for n in specs})

        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=tspec_out, check_vma=False)
        self._mig_fns[key] = jax.jit(sm)
        return self._mig_fns[key]

    @staticmethod
    def _np_id_list(arr) -> "Any":
        """Device id array ((M,) int or (M, 2) pair) -> valid int64 host ids."""
        import numpy as np

        from ..ops.id64 import HI_INVALID, np_join_ids
        a = np.asarray(arr)
        if a.ndim == 2:
            return np_join_ids(a[a[:, 0] < HI_INVALID])
        return a[a >= 0].astype(np.int64)

    def migrate_rows(self, state: TrainState, moves=None) -> TrainState:
        """Re-home up to `mig_rows` measured-heavy COLD rows per table
        between steps: write the OLD migrated rows back to their home shards,
        install the new directory, and fill the annex from the homes (bit
        copies both ways — a migration never perturbs training values).

        `moves`: {table: (ids, owners)} — parallel arrays, heaviest first
        (`placement.plan_migration` produces them from the sketches + the
        per-shard load vectors). Missing tables / None install an all-EMPTY
        directory (= de-migrate everything). Ids currently in a table's HOT
        set are dropped: hot and migrated sets stay disjoint — a replicated
        row has no single owner to migrate. Static shapes: a migration NEVER
        re-jits the step."""
        if not self.mig_enabled:
            return state
        import numpy as np
        moves = moves or {}
        idents, fill, migrate = {}, [], []
        for name, spec in self._mig_specs().items():
            M = self.mig_rows_for(name)
            ids, owners = moves.get(name) or (None, None)
            ts = state.tables[name]
            if ids is not None and ts.hot is not None:
                hot_now = set(self._np_id_list(ts.hot.ids).tolist())
                ids = np.asarray(ids, np.int64).reshape(-1)
                owners = np.asarray(owners, np.int64).reshape(-1)[:ids.size]
                keep = np.asarray([i not in hot_now for i in ids.tolist()],
                                  bool) if hot_now else np.ones(ids.shape,
                                                                bool)
                ids, owners = ids[keep], owners[keep]
            ident = build_mig_identity(spec, M, ids, owners,
                                       num_shards=self.num_shards,
                                       key_template=ts.keys)
            idents[name] = ident
            placed = int((np.asarray(ident["rank"]) < M).sum())
            _metrics.observe("placement.migrated_rows", float(placed),
                             "gauge", labels={"table": name})
            (migrate if ts.mig is not None else fill).append(name)
        _metrics.observe("placement.migrations", 1)
        tables = dict(state.tables)
        for mode, names in (("migrate", migrate), ("fill", fill)):
            if names:
                sub = {n: tables[n] for n in names}
                tables.update(self._run_stripped(
                    self._mig_jit(mode, names), sub, "hot",
                    {n: idents[n] for n in names}))
        return state.replace(tables=tables)

    def refresh_hot_rows(self, state: TrainState, hot_ids=None,
                         monitor=None) -> TrainState:
        """Promote/demote the hot sets between steps: write the OLD hot rows
        back to their owner shards, install the new per-table sets, and
        gather their rows into the replicated cache (bit-copies via owner
        select — no float math, so promotion never perturbs training).

        New sets come from `hot_ids` ({table: int64 ids, hottest first}) or
        the heavy-hitter sketches — `monitor`, the trainer's
        `enable_skew_monitor()` feed, or the global `utils.sketch.MONITOR`.
        Size `hot_rows` from the measured coverage curve
        (`tools/skew_report.py` / the /statusz hot-id table); refresh on a
        coarse cadence (e.g. every few hundred steps) — under
        `SpaceSaving(decay=...)` the sketch itself rotates with the
        workload. Static shapes: a refresh NEVER re-jits the step.

        Candidates currently in a table's MIGRATION directory are skipped
        (hot and migrated sets stay disjoint — de-migrate via `migrate_rows`
        first to promote one; `placement.PlacementController` orders the two
        that way). Tables whose state carries no cache yet (hot_rows enabled
        after init) are filled in place — same machinery as `load`'s
        re-attach."""
        if not self.hot_enabled:
            return state
        import numpy as np
        idents = {}
        for name, spec in self._hot_specs().items():
            H = self.hot_rows_for(name)
            if hot_ids is not None and name in hot_ids:
                cand = np.asarray(hot_ids[name], np.int64)
            else:
                mon = monitor if monitor is not None else self._skew
                if mon is None:
                    from ..utils import sketch
                    mon = sketch.MONITOR
                cand = np.asarray(
                    [h for h, _est, _err in mon.sketch(name).topk(H)],
                    np.int64)
            ts = state.tables[name]
            if ts.mig is not None and cand.size:
                migrated = set(self._np_id_list(ts.mig.ids).tolist())
                if migrated:
                    cand = np.asarray(
                        [i for i in cand.reshape(-1).tolist()
                         if i not in migrated], np.int64)
            ident = build_hot_identity(spec, H, cand,
                                       key_template=ts.keys)
            idents[name] = ident
            _metrics.observe("hot.set_size",
                             float(int((np.asarray(ident["rank"]) < H).sum())),
                             "gauge", labels={"table": name})
        _metrics.observe("hot.refreshes", 1)
        sub = self._hot_sub(state, need_hot=False)
        missing = [n for n, ts in sub.items() if ts.hot is None]
        if missing and len(missing) != len(sub):
            self._hot_sub(state)  # raises with the managed-state message
        mode = "fill" if missing else "refresh"
        if mode == "fill":
            # attaching caches to cache-less states is the one refresh that
            # ALLOCATES: preflight the delta against the device budget and
            # keep the state cache-free when it would not fit
            from ..utils import memwatch as _memwatch
            specs = self._hot_specs()
            delta = sum(self._hot_device_bytes(specs[n],
                                               self.hot_rows_for(n))
                        for n in missing if n in specs)
            if not _memwatch.WATCH.preflight(delta, reason="hot_fill"):
                return state
        new = self._run_stripped(self._hot_jit(mode), sub, "mig", idents)
        tables = dict(state.tables)
        tables.update(new)
        return state.replace(tables=tables)

    def load(self, state: TrainState, path: str):
        """See Trainer.load. With hot replication on, the loaders rebuild
        plain table states (the cache is never serialized), so this re-attaches
        the PRE-load hot identity (or an empty one) and re-GATHERS its rows
        from the loaded shards — the stale pre-load cache values are never
        written back. Migration directories re-attach the same way: the
        PRE-load id -> owner assignment is re-installed and the annex
        re-fills from the loaded home shards (which the checkpoint holds in
        their written-back, authoritative form). ZeRO dense slots load in
        their serialized baseline form and re-shard on the way out."""
        state = self.dense_to_replicated(state)
        loaded = super().load(state, path)
        if self.hot_enabled:
            idents = {}
            for name, spec in self._hot_specs().items():
                old = state.tables.get(name)
                old_hot = old.hot if old is not None else None
                if old_hot is not None:
                    idents[name] = {"keys": old_hot.keys,
                                    "rank": old_hot.rank,
                                    "ids": old_hot.ids}
                else:
                    idents[name] = build_hot_identity(
                        spec, self.hot_rows_for(name), None,
                        key_template=loaded.tables[name].keys)
            sub = {n: loaded.tables[n].replace(hot=None) for n in idents}
            new = self._run_stripped(self._hot_jit("fill"), sub, "mig",
                                     idents)
            tables = dict(loaded.tables)
            tables.update(new)
            loaded = loaded.replace(tables=tables)
        if self.mig_enabled:
            idents = {}
            for name, spec in self._mig_specs().items():
                old = state.tables.get(name)
                old_mig = old.mig if old is not None else None
                if old_mig is not None:
                    idents[name] = {"keys": old_mig.keys,
                                    "rank": old_mig.rank,
                                    "ids": old_mig.ids,
                                    "owners": old_mig.owners}
                else:
                    idents[name] = build_mig_identity(
                        spec, self.mig_rows_for(name),
                        num_shards=self.num_shards,
                        key_template=loaded.tables[name].keys)
            sub = {n: loaded.tables[n].replace(mig=None) for n in idents}
            new = self._run_stripped(
                self._mig_jit("fill", sorted(idents)), sub, "hot", idents)
            tables = dict(loaded.tables)
            tables.update(new)
            loaded = loaded.replace(tables=tables)
        return self.dense_to_sharded(loaded)

    # -- per-device hooks (run inside shard_map) -----------------------------

    def reduce_module_state(self, fr):
        # BatchNorm-style moving stats: each shard computed its update from
        # LOCAL batch statistics (per-replica BN, same as the reference's
        # Horovod DP); pmean makes the replicated frozen state one value.
        # Integer leaves (seed counters) advance identically on every shard.
        import jax.numpy as jnp

        def avg(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return jax.lax.pmean(x, self.axis)
            return x
        return jax.tree_util.tree_map(avg, fr)

    def reduce_dense_grads(self, grads):
        # reference parity: Horovod allreduce op=Sum (NOT average) — effective dense
        # lr scales with worker count exactly like the reference's examples
        if self.zero_enabled:
            # the sum folds into dense_update's psum_scatter: one
            # reduce-scatter replaces the all-reduce (same ring wire bytes),
            # and psum_scatter == psum-then-slice bit for bit
            return grads
        return jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, self.axis), grads)

    def dense_grad_stats(self, grads):
        """`dense/grad_density`: the nonzero fraction of this replica's
        PRE-reduction dense grad vector, emitted pre-divided by S so the
        per-key stats psum (`reduce_metrics`) yields the MEAN replica
        density — the measured input to
        `PlacementPolicy.recommend_dense_wire`. Off by default
        (`dense_stats=False` compiles byte-identical HLO; the placement
        controller flips it on at prime())."""
        if not self.dense_stats:
            return {}
        leaves = jax.tree_util.tree_leaves(grads)
        total = sum(int(leaf.size) for leaf in leaves)
        if total == 0:
            return {}
        nnz = sum(jnp.count_nonzero(leaf).astype(jnp.float32)
                  for leaf in leaves)
        return {"dense/grad_density":
                nnz / jnp.float32(total * self.num_shards)}

    # oelint: hot-path device_get=0
    def dense_update(self, params, slots, grads):
        """The ZeRO-sharded dense apply (runs inside shard_map; see
        parallel/zero.py for the layout and the bit-exactness argument):
        reduce_scatter the un-psum'd grads, update this replica's 1/S chunk,
        all_gather the new weights. With `dense_wire` both collectives
        quantize: the grads ride an a2a of in-band-encoded partials summed
        per replica in fp32 (the round-13 two-stage hot-reduce shape — a
        reduce_scatter that never ships fp32), the updated params all_gather
        on the u16 bf16 carrier, and the chunk's fp32 masters (plus, for
        int8/sparse_topk, the full-length error-feedback residual) persist
        as two more "__zero__" flat slots that externalize() drops.
        dense_wire='sparse_topk' ships only the k largest-|x| elements per
        destination chunk (values + in-band scales + bitcast index lanes,
        `ops.wire.pack_topk`); the receiver scatter-sums the decoded sparse
        partials in fp32 and the untransmitted mass feeds the residual."""
        if not self.zero_enabled:
            return super().dense_update(params, slots, grads)
        from . import zero
        plan = self._zero_plan_for(params)
        if plan.total == 0:
            return super().dense_update(params, slots, grads)
        flat_slots = slots[zero.ZERO_KEY]
        fmt = self.dense_wire
        k = self.dense_topk_for(plan) if fmt == "sparse_topk" else None
        dcost = zero.dense_wire_cost(plan, fmt, topk=k)
        if self.last_wire_cost is not None:
            # trace-time byte attribution for the dense collectives — the
            # hlo-budget pass pins model == compiled HLO on these
            cost = dict(self.last_wire_cost)
            cost["dense_wire_format"] = dcost["format"]
            cost["dense_a2a_bytes"] = dcost["a2a_bytes"]
            cost["dense_reduce_scatter_bytes"] = dcost["rs_bytes"]
            cost["dense_all_gather_bytes"] = dcost["ag_bytes"]
            cost["dense_bytes_per_step"] = dcost["bytes_per_step"]
            if k is not None:
                cost["dense_wire_k"] = int(k)
            self.last_wire_cost = cost
        _metrics.observe("dense.params_total", float(plan.total), "gauge")
        _metrics.observe("dense.zero_shards", float(plan.num_shards), "gauge")
        _metrics.observe("dense.shard_elems", float(plan.chunk), "gauge")
        _metrics.observe(
            "dense.opt_state_bytes_per_replica",
            float(len(plan.vector_slots) * plan.chunk * 4
                  + len(plan.scalar_slots) * 4), "gauge")
        # truthful per-collective bytes: fp32 moves padded f32 both ways
        # (ring-equivalent halves of the baseline all-reduce); quantized
        # formats zero the reduce_scatter — it compiles into the encoded a2a
        _metrics.observe("dense.reduce_scatter_bytes",
                         float(dcost["rs_bytes"]), "gauge")
        _metrics.observe("dense.a2a_bytes", float(dcost["a2a_bytes"]),
                         "gauge")
        _metrics.observe("dense.all_gather_bytes", float(dcost["ag_bytes"]),
                         "gauge")
        _metrics.observe("dense.wire_bytes_per_step",
                         float(dcost["bytes_per_step"]), "gauge")
        # wire_dtype as an itemsize gauge (same convention as
        # exchange.wire_dtype; sparse_topk's value lanes are int8 = 1) and
        # the bytes the chosen mode saves vs the lossless fp32 plan
        _metrics.observe(
            "dense.wire_dtype",
            {None: 4.0, "bf16": 2.0, "int8": 1.0, "sparse_topk": 1.0}[fmt],
            "gauge")
        fp32_cost = zero.dense_wire_cost(plan, None)
        _metrics.observe(
            "dense.wire_bytes_saved",
            float(fp32_cost["bytes_per_step"] - dcost["bytes_per_step"]),
            "gauge")
        if k is not None:
            _metrics.observe("dense.grad_topk", float(k), "gauge")
        S, chunk = plan.num_shards, plan.chunk
        new_ef = None
        if not fmt:
            with _trace.scope("dense", "reduce"):
                flat_g = zero.flatten_tree(plan, grads)
                g_local = jax.lax.psum_scatter(flat_g, self.axis,
                                               scatter_dimension=0,
                                               tiled=True)
        elif fmt == "sparse_topk":
            with _trace.scope("dense", "reduce"):
                flat_g = zero.flatten_tree(plan, grads) \
                    + flat_slots[zero.DENSE_EF_KEY].reshape(-1)
                x = flat_g.reshape(S, chunk)  # destination-major partials
                enc = zero.encode_flat_topk(flat_g, S, k)    # (S, Wk) s8
                # the residual keeps EVERYTHING the sparse payload failed to
                # ship: untransmitted elements whole, transmitted ones their
                # int8 rounding error
                new_ef = (x - zero.decode_flat_topk(enc, k, chunk)) \
                    .reshape(1, -1)
                recv = jax.lax.all_to_all(
                    enc.reshape(S, 1, enc.shape[1]), self.axis, 0, 0)
                # stream-sparse two-stage reduce: decode ALL S sources'
                # sparse partials of this chunk and scatter-sum in fp32
                g_local = zero.decode_flat_topk(
                    recv.reshape(S, -1), k, chunk).sum(axis=0)
        else:
            with _trace.scope("dense", "reduce"):
                flat_g = zero.flatten_tree(plan, grads)
                if fmt == "int8":
                    flat_g = flat_g \
                        + flat_slots[zero.DENSE_EF_KEY].reshape(-1)
                enc = zero.encode_flat(flat_g, fmt)       # (padded/B, W)
                if fmt == "int8":
                    new_ef = (flat_g - zero.decode_flat(enc, fmt)) \
                        .reshape(1, -1)
                W = enc.shape[1]
                recv = jax.lax.all_to_all(
                    enc.reshape(S, enc.shape[0] // S, W), self.axis, 0, 0)
                # two-stage reduce: every replica decodes ALL S sources'
                # partials of its own chunk and sums them in fp32 — one
                # lossy step per gradient, never a chain of S roundings
                g_local = zero.decode_flat(recv.reshape(-1, W), fmt) \
                    .reshape(S, chunk).sum(axis=0)
        with _trace.scope("dense", "update"):
            if fmt:
                # this replica's fp32 masters live in the flat slot — the
                # replicated `params` only hold the rounded bf16 carrier
                w_local = flat_slots[zero.DENSE_MASTER_KEY].reshape(-1)
                opt_slots = {k: v for k, v in flat_slots.items()
                             if k not in (zero.DENSE_MASTER_KEY,
                                          zero.DENSE_EF_KEY)}
            else:
                flat_w = zero.flatten_tree(plan, params)
                i = jax.lax.axis_index(self.axis)
                w_local = jax.lax.dynamic_slice(flat_w, (i * chunk,),
                                                (chunk,))
                opt_slots = flat_slots
            new_w_local, new_flat_slots = self.optimizer.apply(
                w_local.reshape(1, -1), opt_slots,
                g_local.reshape(1, -1), jnp.ones((1,), jnp.int32))
        with _trace.scope("dense", "gather"):
            w_flat = new_w_local.reshape(-1)
            if fmt:
                carrier = jax.lax.bitcast_convert_type(
                    w_flat.astype(jnp.bfloat16), jnp.uint16)
                gathered = jax.lax.all_gather(carrier, self.axis, tiled=True)
                flat_new = jax.lax.bitcast_convert_type(
                    gathered, jnp.bfloat16).astype(jnp.float32)
            else:
                flat_new = jax.lax.all_gather(w_flat, self.axis, tiled=True)
            new_params = zero.unflatten_tree(plan, flat_new, params)
        if fmt:
            new_flat_slots = dict(new_flat_slots)
            new_flat_slots[zero.DENSE_MASTER_KEY] = new_w_local.reshape(1, -1)
            if new_ef is not None:
                new_flat_slots[zero.DENSE_EF_KEY] = new_ef
        return new_params, {zero.ZERO_KEY: new_flat_slots}

    def _reduce_loss(self, loss):
        return jax.lax.pmean(loss, self.axis)

    def reduce_metrics(self, metrics):
        with _trace.scope("trainer", "metrics"):
            out = dict(metrics)
            out["loss"] = self._reduce_loss(metrics["loss"])
            out["stats"] = {k: jax.lax.psum(v, self.axis)
                            for k, v in metrics.get("stats", {}).items()}
            return out

    # -- fused multi-table exchange ------------------------------------------

    def _exchange_groups(self, ps_specs):
        """Dim-groups restricted to the tables actually pulled this step,
        then split by resolved per-table wire format: tables sharing
        (dim, fmt) stay fused on one a2a pair, mixed-format dims ride
        separate groups. A uniform-format config keeps the model's
        dim-groups as they are."""
        from .sharded import split_wire_groups
        groups = [[n for n in g if n in ps_specs]
                  for g in self.model.dim_groups()
                  if any(n in ps_specs for n in g)]
        return split_wire_groups(groups, self.wire_for)

    # oelint: hot-path device_get=0
    def tables_pull(self, tables, batch, ps_specs, packed):
        """Sharded pull: 1 id a2a + 1 (optionally quantized) row a2a per
        DIM-GROUP (`sharded.grouped_lookup_train`). The layouts of the tables
        the scan holds packed go down with it: the owner of such an array
        table plans its step at the serve and the plan rides `plans` to
        `tables_apply` (`sharded.py` "THE OWNER PLANS ONCE A STEP")."""
        self._observe_wire_cost(ps_specs, batch)
        from .sharded import grouped_lookup_train
        pulled_tables, pulled, stats, plans = {}, {}, {}, {}
        for names in self._exchange_groups(ps_specs):
            specs = [ps_specs[n] for n in names]
            ids_list = [jnp.asarray(batch["sparse"][s.feature_name])
                        for s in specs]
            new_states, outs, stats_list, plan_list = grouped_lookup_train(
                specs, [tables[n] for n in names], ids_list,
                axis=self.axis, capacity_factor=self.capacity_factor,
                wire=self.wire_for(names[0]),
                load_stats=self.shard_stats,
                packed_list=[packed.get(n) for n in names])
            for n, ts, out, st, pl in zip(names, new_states, outs,
                                          stats_list, plan_list):
                pulled_tables[n], pulled[n], plans[n] = ts, out, pl
                for k, v in st.items():
                    stats[f"{n}/{k}"] = v
        return pulled_tables, pulled, stats, plans

    # oelint: hot-path device_get=0
    def tables_apply(self, ps_specs, pulled_tables, batch, row_grads, packed,
                     plans):
        """Sharded push: 1 grads+counts a2a per DIM-GROUP
        (`sharded.grouped_apply_gradients`), reusing the pull's plans."""
        from .sharded import grouped_apply_gradients
        new_tables, stats = {}, {}
        for names in self._exchange_groups(ps_specs):
            specs = [ps_specs[n] for n in names]
            ids_list = [jnp.asarray(batch["sparse"][s.feature_name])
                        for s in specs]
            states, stats_list = grouped_apply_gradients(
                specs, [pulled_tables[n] for n in names],
                [self.opt_for(s) for s in specs], ids_list,
                [row_grads[n] for n in names], axis=self.axis,
                capacity_factor=self.capacity_factor,
                plans=[plans[n] for n in names],
                packed_list=[packed.get(n) for n in names],
                wire=self.wire_for(names[0]), hot_wire=self.hot_wire)
            for n, ts, st in zip(names, states, stats_list):
                new_tables[n] = ts
                for k, v in st.items():
                    stats[f"{n}/{k}"] = v
        return new_tables, stats

    # -- software-pipelined train_many (round 18) ----------------------------

    def _pipeline_on(self) -> bool:
        """Static trace-time gate: pipelining is inert on 1-device meshes
        (the exchange is local — there is nothing to overlap) and off by
        default, so the serial path compiles byte-identical HLO."""
        return self.pipeline_steps and self.num_shards > 1

    # oelint: hot-path device_get=0
    def _pipeline_prefetch(self, tables, batch, ps_specs, packed):
        """Issue a batch's exchange a FULL STEP ahead: id plane (dedup/sort/
        route + id a2a) and the speculative row gather
        (`sharded.grouped_prefetch`). Returns (new_tables, plans, rows,
        stats) keyed by table, stats prefixed like tables_pull's."""
        from .sharded import grouped_prefetch
        self._observe_wire_cost(ps_specs, batch, pipelined=True)
        new_tables = dict(tables)
        plans, rows, stats = {}, {}, {}
        with _trace.scope("trainer", "prefetch"):
            for names in self._exchange_groups(ps_specs):
                specs = [ps_specs[n] for n in names]
                ids_list = [jnp.asarray(batch["sparse"][s.feature_name])
                            for s in specs]
                states, plan_list, rows_list, stats_list = grouped_prefetch(
                    specs, [tables[n] for n in names], ids_list,
                    axis=self.axis, capacity_factor=self.capacity_factor,
                    wire=self.wire_for(names[0]),
                    load_stats=self.shard_stats,
                    packed_list=[packed.get(n) for n in names])
                for n, ts, pl, rw, st in zip(names, states, plan_list,
                                             rows_list, stats_list):
                    new_tables[n], plans[n], rows[n] = ts, pl, rw
                    for k, v in st.items():
                        stats[f"{n}/{k}"] = v
        return new_tables, plans, rows, stats

    # oelint: hot-path device_get=0
    def _pipeline_finalize(self, tables, batch, ps_specs, plans, rows):
        """Client tail of the carried prefetch — hot-cache overlay +
        duplicate expansion at CONSUME time (`sharded.grouped_finalize_pull`;
        pure local math, no collective)."""
        from .sharded import grouped_finalize_pull
        pulled = {}
        for names in self._exchange_groups(ps_specs):
            specs = [ps_specs[n] for n in names]
            ids_list = [jnp.asarray(batch["sparse"][s.feature_name])
                        for s in specs]
            outs = grouped_finalize_pull(
                specs, [tables[n] for n in names], ids_list,
                [plans[n] for n in names], [rows[n] for n in names])
            for n, out in zip(names, outs):
                pulled[n] = out
        return pulled

    # oelint: hot-path device_get=0
    def _pipeline_patch(self, ps_specs, tables, prev_plans, plans, rows,
                        packed):
        """Repair the next batch's speculative rows against what this batch's
        apply just wrote (`sharded.grouped_conflict_patch`). Returns
        (patched_rows, new_tables, {name: conflict_rows psum},
        conflict_overflow psum) — `new_tables` carries the replayed
        error-feedback residuals on narrow-wire tables (unchanged
        otherwise)."""
        from .sharded import grouped_conflict_patch
        patched, conflict = {}, {}
        new_tables = dict(tables)
        coflow = jnp.zeros((), jnp.int32)
        with _trace.scope("trainer", "conflict_patch"):
            for names in self._exchange_groups(ps_specs):
                specs = [ps_specs[n] for n in names]
                outs, stats_list, states = grouped_conflict_patch(
                    specs, [tables[n] for n in names],
                    [prev_plans[n] for n in names],
                    [plans[n] for n in names],
                    [rows[n] for n in names], axis=self.axis,
                    conflict_factor=self.conflict_factor,
                    wire=self.wire_for(names[0]),
                    packed_list=[packed.get(n) for n in names])
                for n, out, st, ts in zip(names, outs, stats_list, states):
                    patched[n] = out
                    new_tables[n] = ts
                    conflict[n] = jax.lax.psum(st["conflict_rows"],
                                               self.axis)
                    coflow = coflow + jax.lax.psum(st["conflict_overflow"],
                                                   self.axis)
        return patched, new_tables, conflict, coflow

    def train_many(self, state: TrainState, batches):
        """See `Trainer.train_many`. With pipeline_steps=True on a real mesh
        the window is SOFTWARE-PIPELINED (`_train_many_pipelined`); the
        returned metrics gain per-window "conflict" ({table: patched rows})
        and "conflict_overflow" counters — fold them into gauges with
        `record_window_stats`."""
        if not self._pipeline_on():
            return super().train_many(state, batches)
        return self._train_many_pipelined(state, batches)

    def _scan_stats(self, stats):
        """A step's apply load (`Trainer._scan_stats`) and, where the owner
        compacts what it receives, its `owner_fill` / `owner_full_steps`
        (`sharded.exchange_load_stats`); each per-shard vector folded over
        the shards: the fullest shard's fill, and 1 where any shard ran full
        size."""
        return _table_stats(stats, _WINDOW_TABLE_STATS)

    def _window_stats(self, kept):
        """The stacked `_scan_stats` folded over a window's steps: under
        "apply_fill" / "owner_fill" {table: the fullest step}, under
        "apply_full_steps" / "owner_full_steps" {table: steps that ran full
        size}; the owner's two are empty where no table's receive side is
        compacted."""
        return _fold_table_stats(kept, _WINDOW_TABLE_STATS)

    def _train_many_pipelined(self, state: TrainState, batches):
        """Prologue / steady-state / epilogue around `lax.scan`:

            prologue:  prefetch(b[0])
            body t:    prefetch(b[t+1])         # issued FIRST — overlaps
                       finalize(b[t])           # batch t's fwd/bwd/applies
                       fwd/bwd + applies (b[t]) # model._train_step_tail
                       conflict_patch(b[t+1])   # repair the speculation
            epilogue:  finalize(b[K-1]) + fwd/bwd + applies

        The prefetch has no data dependency on batch t's gradients (the
        jaxpr pin in tests/test_pipeline.py), so XLA may hoist its
        collectives under the dense compute; batch t's push a2a + scatter
        likewise overlap batch t+1's id plane. Hash inserts happen in serial
        order (prologue inserts b[0], body t inserts b[t+1]), apply never
        touches keys, and the patch re-gathers every row the apply could
        have touched — fp32 results are bit-exact vs the serial scan.
        Narrow wire replays error feedback at patch time: the prefetch
        stashes each served row's pre-serve residual on the plan, and the
        patch re-encodes the patched rows with the same codec and rewrites
        the residual slots, so pipelined int8 windows match serial int8
        bit-for-bit."""
        _metrics.observe("trainer.traces", 1, "sum",
                         labels={"fn": "train_many"})
        if self.offload and not getattr(self, "_offload_prepared", False):
            raise ValueError(
                "train_many on storage='host_cached' tables needs the union "
                "of the K batches' ids admitted first: use "
                "trainer.offload_train_many(state, batches) (or call "
                "offload_prepare(state, batches) before every window).")
        from ..ops.sparse import pack_table, unpack_table
        from .sharded import plan_carry, plan_from_carry
        model = self.model
        ps_specs = model.ps_specs()
        sad_specs = model.sad_specs()
        layouts = self._packed_layouts(state)
        if layouts:
            tables = dict(state.tables)
            for name, lay in layouts.items():
                ts = tables[name]
                tables[name] = ts.replace(
                    weights=pack_table(ts.weights, ts.slots, lay), slots={})
            state = state.replace(tables=tables)
        K = jax.tree_util.tree_leaves(batches)[0].shape[0]

        def batch_at(t):
            return jax.tree_util.tree_map(lambda x: x[t], batches)

        def transform(b):
            return (model.batch_transform(b)
                    if model.batch_transform is not None else b)

        def stats_overflow(stats):
            oflow = jnp.zeros((), jnp.int32)
            for k, v in stats.items():
                if k.endswith("_overflow"):
                    oflow = oflow + jnp.asarray(v).astype(jnp.int32)
            return oflow

        def step_tail(state, bt, pulled, stats, plans_t):
            split = getattr(model.module, "split_params", None)
            if split is not None:
                tr0, fr0 = split(state.dense_params)
            else:
                tr0, fr0 = state.dense_params, None
            return self._train_step_tail(
                state, bt, ps_specs, sad_specs, layouts, tr0, fr0,
                dict(state.tables), pulled, stats, plans_t)

        # prologue: batch 0's exchange runs un-overlapped (nothing to hide
        # it under yet); its pull stats contribute only overflow
        b0 = transform(batch_at(0))
        tables, plans0, rows0, pf_stats = self._pipeline_prefetch(
            state.tables, b0, ps_specs, layouts)
        state = state.replace(tables=tables)
        # ... and what its owners counted, on the same psum
        total_oflow, owner0 = jax.lax.psum(
            (stats_overflow(pf_stats),
             {k: v for k, v in pf_stats.items()
              if k.partition("/")[2] in _metrics.OWNER_STATS}), self.axis)
        kept = self._scan_stats(owner0)
        # static plan ints (cap, hot_rows) travel out of band — shapes are
        # uniform over the window, so the prologue's trace-time values hold
        statics = {n: (plans0[n].cap, plans0[n].hot_rows) for n in plans0}
        pre0 = {n: {"plan": plan_carry(plans0[n]), "rows": rows0[n]}
                for n in plans0}

        def body(carry, xs):
            state, pre = carry
            bt, bn = xs
            bt = transform(bt)
            bn = transform(bn)
            # (1) batch t+1's exchange FIRST: no data dependency on batch
            # t's grads, so its collectives are free to overlap the compute
            tables, plans_n, rows_n, pf_stats = self._pipeline_prefetch(
                state.tables, bn, ps_specs, layouts)
            state = state.replace(tables=tables)
            # (2) consume the carried prefetch as batch t's pull
            plans_t = {n: plan_from_carry(pre[n]["plan"], *statics[n])
                       for n in pre}
            pulled = self._pipeline_finalize(
                state.tables, bt, ps_specs, plans_t,
                {n: pre[n]["rows"] for n in pre})
            # (3) fwd/bwd + dense & sparse applies; batch t+1's pull stats
            # ride this step's metrics (the per-batch stats accounting)
            state, metrics = step_tail(state, bt, pulled, dict(pf_stats),
                                       plans_t)
            # (4) repair batch t+1's speculative rows post-apply; narrow
            # wire also rewrites the replayed error-feedback residuals
            patched, patch_tables, conflict, coflow = self._pipeline_patch(
                ps_specs, state.tables, plans_t, plans_n, rows_n, layouts)
            state = state.replace(tables=patch_tables)
            oflow = stats_overflow(metrics.get("stats", {}))
            pre_n = {n: {"plan": plan_carry(plans_n[n]), "rows": patched[n]}
                     for n in plans_n}
            return (state, pre_n), (
                metrics["loss"], oflow, conflict, coflow,
                self._scan_stats(metrics.get("stats", {})))

        if K > 1:
            head = jax.tree_util.tree_map(lambda x: x[:-1], batches)
            nxt = jax.tree_util.tree_map(lambda x: x[1:], batches)
            (state, pre), (losses, oflows, conflicts, coflows, kepts) = \
                jax.lax.scan(body, (state, pre0), (head, nxt))
            total_oflow = total_oflow + jnp.sum(oflows)
            conflict = {n: jnp.sum(conflicts[n]) for n in conflicts}
            coflow = jnp.sum(coflows)
        else:
            pre = pre0
            losses = None
            conflict = {n: jnp.zeros((), jnp.int32) for n in ps_specs}
            coflow = jnp.zeros((), jnp.int32)

        # epilogue: the last batch consumes its prefetch; nothing left to
        # prefetch or patch
        bl = transform(batch_at(K - 1))
        plans_l = {n: plan_from_carry(pre[n]["plan"], *statics[n])
                   for n in pre}
        pulled = self._pipeline_finalize(state.tables, bl, ps_specs, plans_l,
                                         {n: pre[n]["rows"] for n in pre})
        state, metrics = step_tail(state, bl, pulled, {}, plans_l)
        total_oflow = total_oflow + stats_overflow(metrics.get("stats", {}))
        # the window's K values of each kept stat: the owners' from the
        # prologue's prefetch and the body's, the applies' from the body's
        # steps and this one (the folds take no notice of the order)
        kept = {**kept, **self._scan_stats(metrics.get("stats", {}))}
        if K > 1:
            kept = jax.tree_util.tree_map(
                lambda edge, rest: jnp.concatenate([edge[None], rest]),
                kept, kepts)
        last = jnp.reshape(metrics["loss"], (1,))
        losses = last if losses is None else jnp.concatenate([losses, last])

        if layouts:
            tables = dict(state.tables)
            for name, lay in layouts.items():
                spec = self.model.specs[name]
                ts = tables[name]
                w, slots = unpack_table(ts.weights, lay, spec.output_dim,
                                        spec.dtype)
                tables[name] = ts.replace(weights=w, slots=slots)
            state = state.replace(tables=tables)
        return state, {"loss": losses, "overflow": total_oflow,
                       "conflict": conflict, "conflict_overflow": coflow,
                       **self._window_stats(kept)}

    def _window_fold(self):
        """What `record_window_stats` folds of a window (`Trainer`'s doc):
        each table's `sparse.apply_fill{table=}` /
        `sparse.apply_full_steps{table=}`; where the owner compacts what it
        receives, `exchange.owner_fill{table=}` (gauge: the window's fullest
        step on its fullest shard) and `exchange.owner_full_steps{table=}`
        (counter: steps that took the full-size path); pipelined windows
        publish `exchange.conflict_rows{table=}` plus the pcap-dropped
        `exchange.conflict_overflow`. ONE device_get per window (the
        window-level sibling of `metrics.record_step_stats`)."""
        return _fold_mesh_window

    def _observe_wire_cost(self, ps_specs, batch, *, pipelined=False):
        """Publish the static wire-cost model of the traced step (runs once
        per trace — all inputs are shapes, not values)."""
        from ..ops import wire as wire_mod
        from ..ops.id64 import is_pair
        from .sharded import _bucket_capacity
        tables = []
        for name, spec in ps_specs.items():
            # `batch` is the per-device shard here (tables_pull runs inside
            # shard_map), so ids.size IS the per-device position count
            ids = jnp.asarray(batch["sparse"][spec.feature_name])
            pair_batch = spec.use_hash_table and is_pair(ids)
            n = ids.size // 2 if pair_batch else ids.size
            cap = _bucket_capacity(max(n, 1), self.num_shards,
                                   self.capacity_factor)
            tables.append({
                "dim": spec.output_dim,
                "cap": cap,
                # hash ids ride the wire in the TABLE's key layout —
                # `adapt_batch_ids` widens single-lane batches to split-pair
                # at the protocol entry — so their wire slot is 8 B whatever
                # the batch dtype; array tables ship the batch dtype as-is
                "pair": spec.use_hash_table,
                "id_itemsize": jnp.dtype(ids.dtype).itemsize,
                # the table's RESOLVED format: exchange_cost groups on
                # (dim, fmt), mirroring _exchange_groups' split
                "fmt": self.wire_for(name)})
            # per-table pull sizes, LABELED by table: the per-table skew
            # (Parallax: sparse behavior is dominated by it) reads straight
            # off /metrics as oetpu_exchange_pull_positions{table=...}
            _metrics.observe("exchange.pull_positions", float(n), "gauge",
                             labels={"table": name})
            # row dim per table: lets offline consumers (tools/skew_report.py
            # --recommend) price hot/migrated rows from one /metrics scrape
            _metrics.observe("exchange.row_dim", float(spec.output_dim),
                             "gauge", labels={"table": name})
            M = self.mig_rows_for(name)
            if M:
                _metrics.observe("placement.mig_rows", float(M), "gauge",
                                 labels={"table": name})
        # the resolved wire format goes through the compiled a2as (in-band
        # scales); the model prices the a2a RESULT buffers, the same thing
        # oelint's hlo-budget counts. Per-table "fmt" keys make the model
        # group on (dim, fmt) exactly like _exchange_groups does
        fmt = self.wire_default()
        cost = wire_mod.exchange_cost(tables, self.num_shards, fmt)
        self.last_wire_cost = cost
        _metrics.observe_exchange_cost(cost)
        for name in ps_specs:
            # the table's RESOLVED row-payload itemsize — under mixed wire
            # each table reports its own format, not one global value
            _metrics.observe(
                "exchange.wire_dtype",
                float(jnp.dtype(wire_mod.wire_dtype(
                    self.wire_for(name))).itemsize),
                "gauge", labels={"table": name})
        if pipelined:
            # pipelined windows (round 18): the prefetched id+row a2as and
            # the push a2a ride under the dense compute — OFF the critical
            # path ("overlapped_bytes", which StepWatch's drift baseline
            # excludes) — and the conflict patch is the only NEW wire the
            # pipeline adds, priced by the same static model and pinned by
            # the fused_fp32_pipelined hlo-budget config
            from .sharded import conflict_patch_cap
            ptables = [dict(t, pcap=conflict_patch_cap(
                t["cap"], self.conflict_factor)) for t in tables]
            pcost = wire_mod.conflict_patch_cost(ptables, self.num_shards,
                                                 fmt)
            cost = dict(cost)
            cost["overlapped_bytes"] = int(cost["bytes_per_step"])
            cost["conflict_patch_bytes"] = int(pcost["bytes_patch"])
            cost["bytes_per_step"] = (int(cost["bytes_per_step"])
                                      + int(pcost["bytes_patch"]))
            cost["collectives_per_step"] = (int(cost["collectives_per_step"])
                                            + int(pcost["collectives"]))
            self.last_wire_cost = cost
            _metrics.observe("exchange.conflict_patch_bytes",
                             float(pcost["bytes_patch"]), "gauge")
            _metrics.observe("exchange.overlapped_bytes",
                             float(cost["overlapped_bytes"]), "gauge")
        # hot-cache static costs: cache size per table + the wire bytes of
        # the backward's dense hot reduce, priced by hot_reduce_cost for the
        # resolved hot format (ring allreduce for fp32/bf16, the two-stage
        # a2a+all_gather exchange for int8) — the cheap-collective price the
        # replicated hot set pays instead of riding the a2a (SparCML's
        # dense-ified hot aggregate). hot_wire=None follows each TABLE's
        # resolved format, so mixed wire prices hot tables per format too
        hot_by_fmt: Dict[str, list] = {}
        for name, spec in ps_specs.items():
            H = self.hot_rows_for(name)
            if not H:
                continue
            _metrics.observe("hot.rows", float(H), "gauge",
                             labels={"table": name})
            hfmt = (wire_mod.wire_format(self.hot_wire)
                    if self.hot_wire is not None else self.wire_for(name))
            hot_by_fmt.setdefault(hfmt, []).append(
                {"dim": spec.output_dim, "hot": H})
        if hot_by_fmt:
            tot = a2a = ag = 0
            for hfmt, hot_tables in hot_by_fmt.items():
                hcost = wire_mod.hot_reduce_cost(hot_tables, self.num_shards,
                                                 hfmt)
                tot += int(hcost["bytes"])
                a2a += int(hcost["a2a_bytes"])
                ag += int(hcost["all_gather_bytes"])
            _metrics.observe("hot.replicate_bytes_per_step", float(tot),
                             "gauge")
            cost = dict(cost)
            cost["hot_replicate_bytes"] = tot
            cost["hot_a2a_bytes"] = a2a
            cost["hot_all_gather_bytes"] = ag
            cost["hot_wire_format"] = ",".join(sorted(hot_by_fmt))
            self.last_wire_cost = cost

    def table_lookup(self, spec, table, ids):
        return sharded_lookup(spec, table, ids, axis=self.axis,
                              capacity_factor=self.capacity_factor)

    # -- jitted drivers ------------------------------------------------------

    def jit_train_step(self, sample_batch=None, sample_state=None):
        """Builds the shard_map'ped step. Needs a sample batch/state on first call to
        derive the pytree partition specs."""
        if self._train_step_fn is not None:
            return self._wrap_measured(self._train_step_fn)
        if sample_batch is None or sample_state is None:
            raise ValueError("first call needs (sample_batch, sample_state)")
        state_spec = self._state_pspec_tree(sample_state)
        batch_spec = self._batch_pspec(sample_batch)
        metrics_spec = {"loss": P(), "logits": self._logits_pspec(),
                        "stats": P()}

        stepped = jax.shard_map(
            self.train_step, mesh=self.mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, metrics_spec),
            check_vma=False,
        )
        self._train_step_fn = jax.jit(stepped, donate_argnums=(0,))
        return self._wrap_measured(self._train_step_fn)

    def jit_train_many(self, sample_batches=None, sample_state=None):
        """Scan-fused K-step driver under shard_map (see Trainer.train_many):
        `sample_batches` has a leading K dim on every leaf. State DONATED."""
        if getattr(self, "_train_many_fn", None) is not None:
            return self._train_many_fn
        if sample_batches is None or sample_state is None:
            raise ValueError("first call needs (sample_batches, sample_state)")
        state_spec = self._state_pspec_tree(sample_state)
        one = jax.tree_util.tree_map(lambda x: x[0], sample_batches)
        bspec = self._batch_pspec(one)
        stacked_spec = jax.tree_util.tree_map(
            lambda p: P(None, *p), bspec, is_leaf=lambda x: isinstance(x, P))

        metrics_spec = {"loss": P(), "overflow": P(),
                        "owner_fill": P(), "owner_full_steps": P(),
                        "apply_fill": P(), "apply_full_steps": P(),
                        "line_mates": P()}
        if self._pipeline_on():
            # the pipelined window reports two extra replicated counters;
            # the serial branch keeps EXACTLY the round-17 spec dict (the
            # byte-identical-HLO guarantee extends to the jit cache key)
            metrics_spec["conflict"] = {n: P()
                                        for n in self.model.ps_specs()}
            metrics_spec["conflict_overflow"] = P()
        many = jax.shard_map(
            self.train_many, mesh=self.mesh,
            in_specs=(state_spec, stacked_spec),
            out_specs=(state_spec, metrics_spec),
            check_vma=False,
        )
        self._train_many_fn = TrainManyDispatch(
            jax.jit(many, donate_argnums=(0,)), self._window_fold())
        return self._train_many_fn

    def _many_fn(self, batches, state):
        return self.jit_train_many(batches, state)

    def train_stream(self, state, windows, *, block: bool = True):
        """Drive `jit_train_many` over a stream of already-resident stacked
        K-step windows (a `data.ingest.FeedRing` in window mode) with the
        input-wait attribution lane wired in: each window's blocking
        `next()` lands in `trainer.input_wait_ms` (via `input_timed`) and
        each window's wall time in the `trainer.window_ms` histogram — the
        denominator `data.ingest.input_wait_share` folds the waits against.
        The first window compiles the driver (`jit_train_many`), whose
        dispatch object folds every window's counters into series.

        `block=True` brackets every window with `block_until_ready` — the
        measured-soak mode, where window_ms is honest wall time per window.
        With `block=False` only dispatch is timed (dispatch-limited loops,
        e.g. when an outer StepWatch already samples).

        Returns `(state, {"windows": n, "loss": last_loss})`."""
        import time as _time

        import numpy as np
        n = 0
        last_loss = None
        many = None
        for w in self.input_timed(windows):
            if many is None:
                many = self.jit_train_many(w, state)
            t0 = _time.perf_counter()
            # one profiler step per window, so a profile has steps
            with jax.profiler.StepTraceAnnotation("train", step_num=n):
                state, m = many(state, w)
                if block:
                    jax.block_until_ready(state)
            _metrics.observe("trainer.window_ms",
                             (_time.perf_counter() - t0) * 1e3, "hist")
            last_loss = m.get("loss") if isinstance(m, dict) else None
            n += 1
        if last_loss is not None:
            last_loss = float(np.asarray(jax.device_get(last_loss))[-1])
        return state, {"windows": n, "loss": last_loss}

    def jit_eval_step(self, sample_batch=None, sample_state=None):
        if self._eval_step_fn is not None:
            return self._eval_step_fn
        if sample_batch is None or sample_state is None:
            raise ValueError("first call needs (sample_batch, sample_state)")
        state_spec = self._state_pspec_tree(sample_state)
        batch_spec = self._batch_pspec(sample_batch)
        out_spec = {"logits": self._logits_pspec(), "loss": P()}

        def eval_fn(state, batch):
            out = self.eval_step(state, batch)
            out["loss"] = self._reduce_loss(out["loss"])
            return out

        self._eval_step_fn = jax.jit(jax.shard_map(
            eval_fn, mesh=self.mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=out_spec,
            check_vma=False,
        ))
        return self._eval_step_fn


class SeqMeshTrainer(MeshTrainer):
    """Context-parallel trainer over a 2-D mesh ("data", "seq").

    Layout (the long-context design SURVEY.md §5/§7 reserves the axis for):
    - batch rows over 'data' (DP), the sequence dim over 'seq' (CP: ring or
      Ulysses attention inside the module, `parallel/sequence.py`);
    - embedding tables row-sharded over the WHOLE mesh (tuple axis
      ('data','seq')): the pull/push all_to_all and the dense-grad psum ride
      both ICI dimensions; per-device code in `parallel/sharded.py` is unchanged
      because JAX collectives accept the flattened axis tuple;
    - dense params replicated; dense grads psum'd over all devices (Horovod-SUM
      parity like MeshTrainer — with CP the seq shards of one sample also sum,
      matching the reference's sum-not-average convention).

    The model's module must use attention="ring" or "ulysses" with seq_axis
    equal to the mesh's second axis (e.g. `make_sasrec(..., attention="ring")`).
    Batches follow the sequential convention: sparse ids (B, ..., S) — the LAST
    dim is the sequence and is sharded over 'seq'; label (B, S)."""

    def __init__(self, model, optimizer=None, *, mesh: Mesh, seed: int = 0,
                 capacity_factor: float = 0.0, wire: Optional[str] = None,
                 shard_stats: bool = True,
                 hot_rows: "int | Dict[str, int]" = 0,
                 mig_rows: "int | Dict[str, int]" = 0,
                 hot_wire: Optional[str] = None,
                 error_feedback: Optional[bool] = None,
                 sentinel: bool = False,
                 halt_on_nonfinite: bool = False,
                 measure_every: int = 0):
        if len(mesh.axis_names) != 2:
            raise ValueError(
                f"SeqMeshTrainer needs a 2-D (data, seq) mesh, got axes "
                f"{mesh.axis_names}")
        super().__init__(model, optimizer, mesh=mesh, seed=seed,
                         capacity_factor=capacity_factor, wire=wire,
                         shard_stats=shard_stats, hot_rows=hot_rows,
                         mig_rows=mig_rows, hot_wire=hot_wire,
                         error_feedback=error_feedback,
                         sentinel=sentinel,
                         halt_on_nonfinite=halt_on_nonfinite,
                         measure_every=measure_every)
        self.data_axis, self.seq_axis = mesh.axis_names
        # collectives (sparse exchange, psum, metrics) span the flattened mesh
        self.axis = tuple(mesh.axis_names)

    def _batch_pspec(self, batch):
        d, s = self.data_axis, self.seq_axis

        def sparse_spec(x, spec):
            from ..ops.id64 import is_pair
            nd = jnp.ndim(x)
            if spec is not None and spec.use_hash_table and is_pair(x):
                # trailing dim is the id lane pair, not sequence positions
                return P(d, *([None] * (nd - 3)), s, None)
            return P(d, *([None] * (nd - 2)), s)

        by_feat = {s.feature_name: s for s in self.model.specs.values()}
        out = {}
        for key, value in batch.items():
            if key == "sparse":
                out[key] = {k: sparse_spec(v, by_feat.get(k))
                            for k, v in value.items()}
            elif key == "label" and jnp.ndim(value) >= 2:
                out[key] = P(d, s)
            elif key == "dense":
                out[key] = P(d)
            else:
                out[key] = P(d)
        return out

    def _logits_pspec(self):
        # (B, S, ...) logits: batch over data, positions over seq
        return P(self.data_axis, self.seq_axis)

    def _loss(self, logits, batch):
        """Normalize by the GLOBAL count when the loss fn supports it: with the
        sequence dim sharded, a per-shard mean would upweight positions on
        padding-heavy shards relative to non-CP training of the same batch."""
        import inspect

        loss_fn = self.model.loss_fn
        if "norm_axis" in inspect.signature(loss_fn).parameters:
            w = batch.get("weight")
            args = (logits, batch["label"]) if w is None else (
                logits, batch["label"], jnp.asarray(w))
            return loss_fn(*args, norm_axis=self.axis)
        return super()._loss(logits, batch)

    def _reduce_loss(self, loss):
        import inspect
        if "norm_axis" in inspect.signature(self.model.loss_fn).parameters:
            # per-device loss = local_sum / global_count: the global mean is
            # the SUM over devices, not the mean of means
            return jax.lax.psum(loss, self.axis)
        return super()._reduce_loss(loss)
