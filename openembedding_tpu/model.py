"""EmbeddingModel + Trainer: the train-step builder.

Counterpart of the reference's `distributed_optimizer` / `distributed_model`
(`tensorflow/exb.py:446-642`). The reference splits one Keras optimizer into (a) the
dense path (Horovod-allreduced Keras apply) and (b) the PS sparse path (translated
config, server-side apply). Here ONE `SparseOptimizer` drives both paths with identical
math: dense params are updated as single-row "tables" (every leaf touched every step, so
per-row beta^t == Keras's global iteration count), and embedding tables via the fused
sparse apply. No fake-grad trick is needed (`exb.py:89-97`): dense grads psum under
pjit/shard_map, sparse grads ride the all-to-all push path.

Batch convention: {"sparse": {var_name: int ids (B,) or (B, F)},
                   "dense":  optional float (B, D),
                   "label":  (B,) or (B, 1)}.

The flax dense module is called as `module.apply({'params': p}, embedded, dense)` where
`embedded` maps var_name -> (B, ..., dim) pulled rows.
"""

from __future__ import annotations

import dataclasses
import threading
import weakref
from collections import deque
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from .embedding import (Embedding, EmbeddingSpec, EmbeddingTableState,
                        apply_gradients, combine, init_table_state, lookup,
                        lookup_train)
from .optimizers import Adagrad, SparseOptimizer
from .utils import compile_cache as _compile_cache
from .utils import metrics as _metrics
from .utils import trace as _trace


def binary_logloss(logits: jax.Array, labels: jax.Array,
                   weight: Optional[jax.Array] = None) -> jax.Array:
    """Mean sigmoid binary cross-entropy (the reference benchmarks train CTR models
    with keras BinaryCrossentropy, `test/benchmark/criteo_deepctr.py`). `weight`
    (per-sample, e.g. 0 for the padded tail rows of a partial batch from
    `data.CriteoBatcher`) turns the mean into a weighted mean."""
    logits = logits.reshape(-1)
    labels = labels.reshape(-1).astype(logits.dtype)
    per = (jnp.clip(logits, 0) - logits * labels +
           jnp.log1p(jnp.exp(-jnp.abs(logits))))
    if weight is None:
        return jnp.mean(per)
    w = weight.reshape(-1).astype(per.dtype)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


# ---------------------------------------------------------------------------
# Dense-path optimizer reuse: every dense leaf is a 1-row table.
# ---------------------------------------------------------------------------

# A dense leaf of this many elements or more keeps its optimizer slots IN ITS OWN
# SHAPE and is updated in it. The historical layout, every leaf one row of
# `size` columns, makes the update reshape weight, gradient and new weight
# between the leaf's tiled layout and a (1, size) one: free for a 400 x 400
# kernel, three passes over 160 MB for an (8, 2688, 1856) stack of experts
# (68 ms of a step at 623M parameters, v5e). Smaller leaves keep (1, size),
# which is what every checkpoint written so far holds.
DENSE_LEAF_SHAPED_SLOTS = 1 << 20


def _leaf_shaped(optimizer: SparseOptimizer, p) -> bool:
    widths = optimizer.slot_shapes(p.size)
    return (p.ndim >= 2 and p.size >= DENSE_LEAF_SHAPED_SLOTS and bool(widths)
            and all(w == p.size for w in widths.values()))


def init_dense_slots(optimizer: SparseOptimizer, params) -> Any:
    def init(p):
        slots = optimizer.init_slots(1, p.size, p.dtype)
        if _leaf_shaped(optimizer, p):
            slots = {k: v.reshape(p.shape) for k, v in slots.items()}
        return slots
    return jax.tree_util.tree_map(init, params)


def dense_apply(optimizer: SparseOptimizer, params, slots, grads) -> Tuple[Any, Any]:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    slot_leaves = treedef.flatten_up_to(slots)
    grad_leaves = treedef.flatten_up_to(grads)
    ones = jnp.ones((1,), jnp.int32)
    new_params, new_slots = [], []
    for p, s, g in zip(leaves, slot_leaves, grad_leaves):
        # one row of `size` columns, or (slots in the leaf's shape) its own
        # leading dim as the rows: the update is elementwise either way
        shape, counts = (1, p.size), ones
        if {v.shape for v in s.values()} == {p.shape}:
            shape, counts = p.shape, jnp.ones((p.shape[0],), jnp.int32)
        # optimizer math in f32 (see SparseOptimizer.init_slots) even for bf16 params
        nw, ns = optimizer.apply(p.reshape(shape).astype(jnp.float32), s,
                                 g.reshape(shape).astype(jnp.float32), counts)
        new_params.append(nw.reshape(p.shape).astype(p.dtype))
        new_slots.append(ns)
    return (jax.tree_util.tree_unflatten(treedef, new_params),
            jax.tree_util.tree_unflatten(treedef, new_slots))


# Reserved key inside the `embedded` dict handed to modules that declare
# `takes_ids = True`: maps variable name -> that variable's RAW id batch.
# Lets such modules derive id-level masks (e.g. SASRec's key-padding mask
# from `ids >= 0` / `pair_valid`) instead of heuristics over pulled rows (an
# all-zero embedding row is NOT proof of padding). Opt-in, because the
# documented module contract is "embedded maps variable name -> pulled rows"
# and modules may iterate the dict.
IDS_KEY = "__ids__"


def raw_ids(model: "EmbeddingModel", batch) -> Dict[str, jax.Array]:
    """The {var_name: raw id batch} map published under `embedded[IDS_KEY]`
    (train/eval/init/serving) when the dense module sets `takes_ids`."""
    return {name: jnp.asarray(batch["sparse"][spec.feature_name])
            for name, spec in model.specs.items()}


def attach_ids(embedded: Dict[str, Any], model: "EmbeddingModel",
               batch) -> Dict[str, Any]:
    """Add `embedded[IDS_KEY]` iff the module opted in via `takes_ids`."""
    if getattr(model.module, "takes_ids", False):
        embedded[IDS_KEY] = raw_ids(model, batch)
    return embedded


# Reserved key for modules that declare `takes_tables = True`: maps the name
# of every `sparse_as_dense` variable to the WHOLE table (input_dim,
# output_dim), the very leaf of `dense_params["__embeddings__"]` its rows are
# looked up from. A module that ties a table to another use (an output head
# over the token table, `models/zaya1.py`) reads it here; autodiff then sums
# the lookup's and that use's gradients and `dense_apply` takes ONE optimizer
# step on the sum. Tables on the sparse path are never handed over whole.
TABLES_KEY = "__tables__"


def attach_tables(embedded: Dict[str, Any], model: "EmbeddingModel",
                  sad_tables) -> Dict[str, Any]:
    """Add `embedded[TABLES_KEY]` iff the module opted in via `takes_tables`."""
    if getattr(model.module, "takes_tables", False):
        embedded[TABLES_KEY] = dict(sad_tables)
    return embedded


# Reserved key for modules that declare `takes_labels = True`: {"label": the
# batch's labels, "weight": its per-sample weight where it carries one}. A
# module whose loss needs more of its output than fits beside the state (the
# per-token cross-entropy of several exits through one head, each exit's
# logits made and dropped in turn: `models/ouro.py`) computes that part where
# the output is, and its `loss_fn` finishes the loss from what comes out.
TARGETS_KEY = "__targets__"


def attach_targets(embedded: Dict[str, Any], model: "EmbeddingModel",
                   batch) -> Dict[str, Any]:
    """Add `embedded[TARGETS_KEY]` iff the module opted in via `takes_labels`."""
    if getattr(model.module, "takes_labels", False):
        embedded[TARGETS_KEY] = {
            k: jnp.asarray(batch[k]) for k in ("label", "weight")
            if batch.get(k) is not None}
    return embedded


def sad_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Dense-mirrored ('Cache' mode) table gather through `lookup_rows` — the
    ONE implementation of the invalid-id contract (-1 pads and out-of-range
    ids pull zero rows and train nothing, in value and gradient). A bare
    `jnp.take(table, ids)` would wrap -1 onto the last table row; serving's
    lookups already zero-fill, so anything else here is train/serve skew."""
    from .ops.sparse import lookup_rows
    return lookup_rows(table, ids)


class TrainState(struct.PyTreeNode):
    """All mutable training state as one pytree (shards/donates/checkpoints whole)."""

    step: jax.Array
    dense_params: Any
    dense_slots: Any
    tables: Dict[str, EmbeddingTableState]
    # model_version mirrors the reference's float64 CPU counter used to build serving
    # signs `uuid-floor(version)` (`exb.py:131-138`); incremented 0.1 per step there,
    # +1 per step here with signs taken at save time.
    model_version: jax.Array


class EmbeddingModel:
    """A flax dense module + its embedding variables.

    reference: `distributed_model()` clone-replacing Keras Embedding layers
    (`exb.py:593-642`); here the user declares the embeddings explicitly (idiomatic
    functional style) or uses the models in `openembedding_tpu.models` which do it.
    """

    def __init__(self, module, embeddings: List[Embedding],
                 loss_fn: Callable = binary_logloss,
                 config: Optional[dict] = None):
        # `config` (family + kwargs, set by the `models.make_*` factories) lets a
        # standalone export rebuild the dense module for serving (`export.py`) the way
        # the reference's SavedModel carries its graph (`exb.py:506-547`). None for
        # hand-built modules: export still works, predict() just needs the module
        # passed back in explicitly.
        self.module = module
        self.config = config
        # optional pure fn batch -> batch applied at the top of every
        # train/eval/init path (jit-traceable). The Keras converter uses it to
        # synthesize the concatenated id feature of a SHARED Embedding layer
        # (one table, N call sites — reference `exb.py:593-642` clones such
        # graphs without restriction); None for everything else.
        self.batch_transform = None
        self.specs: Dict[str, EmbeddingSpec] = {}
        for i, e in enumerate(embeddings):
            spec = dataclasses.replace(e.spec, variable_id=i)
            if spec.name in self.specs:
                raise ValueError(f"duplicate embedding name {spec.name!r}")
            if spec.sparse_as_dense and spec.optimizer is not None:
                # sad tables train on the dense path with the Trainer's optimizer
                # (reference parity: 'Cache' vars are plain mirrored tf.Variables,
                # `exb.py:241-248`); honoring a per-variable optimizer there would
                # silently lie, so reject the combination.
                raise ValueError(
                    f"embedding {spec.name!r}: sparse_as_dense tables cannot have a "
                    "per-variable optimizer (they train with the dense optimizer)")
            self.specs[spec.name] = spec
        self.loss_fn = loss_fn

    def sad_specs(self) -> Dict[str, EmbeddingSpec]:
        """sparse_as_dense variables (the reference's 'Cache' mode, `exb.py:241-248`):
        small tables kept as dense mirrored params, trained by the dense path."""
        return {n: s for n, s in self.specs.items() if s.sparse_as_dense}

    def ps_specs(self) -> Dict[str, EmbeddingSpec]:
        return {n: s for n, s in self.specs.items() if not s.sparse_as_dense}

    def dim_groups(self) -> List[List[str]]:
        """PS-table names grouped by embedding dim (declaration order): the
        unit of the fused multi-table exchange. A dim-group's tables share one
        set of 3 all_to_alls per train step (`parallel/sharded.grouped_*`), so
        a T-table model with G groups launches 3*G collectives, not 3*T.
        Static per model — built once and cached."""
        if getattr(self, "_dim_groups", None) is None:
            groups: Dict[int, List[str]] = {}
            for name, spec in self.ps_specs().items():
                groups.setdefault(spec.output_dim, []).append(name)
            self._dim_groups = list(groups.values())
        return self._dim_groups


def _table_stats(stats, names) -> Dict[str, Dict[str, jax.Array]]:
    """{stat: {table: value}} of a step's `{table}/{stat}` keys named in
    `names`; a mesh's per-shard vector folds to its largest entry (the
    fullest shard; 1 where any shard ran full size)."""
    kept: Dict[str, Dict[str, jax.Array]] = {}
    for key, v in stats.items():
        table, _, stat = key.partition("/")
        if stat in names:
            kept.setdefault(stat, {})[table] = jnp.max(v)
    return kept


def _fold_table_stats(kept, names) -> Dict[str, Dict[str, jax.Array]]:
    """`_table_stats` stacked over a window's steps -> the fullest step of a
    `*_fill`, the count of steps of a `*_full_steps`; a name no table
    reported reads {}."""
    return {stat: {t: (jnp.max if _metrics.is_fill(stat) else jnp.sum)(v)
                   for t, v in kept.get(stat, {}).items()}
            for stat in names}


def _window_values(metrics, keys) -> Dict:
    """The entries of a window's metrics under `keys` that hold anything, on
    the host (ONE device_get)."""
    if not isinstance(metrics, dict):
        return {}
    return jax.device_get({k: metrics[k] for k in keys
                           if jax.tree_util.tree_leaves(metrics.get(k))})


def _observe_table_stats(vals) -> None:
    for stat, by_table in vals.items():
        for table, v in by_table.items():
            _metrics.observe_table_stat(table, stat, v)


def _fold_window(module_stats, metrics) -> None:
    """What `Trainer.record_window_stats` folds (its doc), given the module's
    {stat: fold} and nothing else of the trainer: a pending window keeps this
    function and never the trainer, so a trainer dropped with windows in
    flight frees its programs."""
    vals = _window_values(metrics, ("module",) + _metrics.APPLY_STATS)
    for name, v in vals.pop("module", {}).items():
        _metrics.observe(name, float(v), module_stats[name])
    _observe_table_stats(vals)


def _leaves_ready(window) -> bool:
    """Every device array of a window's metrics is there to be read (host
    values always are; a DELETED array has nothing to wait for, and asking it
    `is_ready()` kills the process: the fold will say it is gone)."""
    for leaf in jax.tree_util.tree_leaves(window):
        if isinstance(leaf, jax.Array) and leaf.is_deleted():
            continue
        if hasattr(leaf, "is_ready") and not leaf.is_ready():
            return False
    return True


def _first_leaf(window):
    """What a window is known by: the first array of its metrics (None for a
    window that holds none)."""
    return next(iter(jax.tree_util.tree_leaves(window)), None)


class _PendingWindows:
    """The windows `jit_train_many`'s dispatch object has sent whose counters
    are not in the registry yet, oldest first; the process has ONE.

    A dispatch never waits on the device for a counter: `sent` keeps the new
    window's metrics (a few device scalars) and folds AT MOST ONE older
    window, and only if its arrays are ready. Whatever is still pending is
    folded, blocking, when somebody READS the registry (`drain`, which
    `metrics.report()` and `prometheus_text()` run first), and the oldest
    entries when the queue would outgrow `LIMIT` (a loop that never fences
    is held to `LIMIT` windows in flight by that). A window is folded ONCE
    whoever asks: `fold_now` (`record_window_stats`) takes a pending window
    out of the queue and passes over one that has been folded already, known
    by a weak reference to its first array."""

    LIMIT = 8

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: deque = deque()  # (fold, metrics); guarded-by: _lock
        self._folded: Dict[int, Any] = {}  # id(first leaf) -> weakref to it

    def __len__(self) -> int:
        return len(self._pending)

    def sent(self, fold, window) -> None:
        with self._lock:
            self._pending.append((fold, window))
            due = []
            while len(self._pending) > self.LIMIT:
                due.append(self._pending.popleft())
            if not due and len(self._pending) > 1 \
                    and _leaves_ready(self._pending[0][1]):
                due.append(self._pending.popleft())
        for entry in due:
            self._fold(*entry)

    def fold_now(self, fold, window) -> None:
        with self._lock:
            for i, (_, pending) in enumerate(self._pending):
                if pending is window:
                    del self._pending[i]
                    break
            else:
                mark = _first_leaf(window)
                seen = self._folded.get(id(mark))
                if seen is not None and seen() is mark:
                    return
        self._fold(fold, window)

    def drain(self) -> None:
        with self._lock:
            due = list(self._pending)
            self._pending.clear()
        for entry in due:
            self._fold(*entry)

    def _fold(self, fold, window) -> None:
        try:
            fold(window)
        except Exception as e:  # a counter never costs a dispatch or a read:
            # (arrays deleted under it, a backend gone) the window goes uncounted
            _trace.event("trainer", "window_fold_error",
                         error=f"{type(e).__name__}: {e}")
            return
        _metrics.observe("trainer.windows", 1, "sum",
                         labels={"fn": "train_many"})
        mark = _first_leaf(window)
        try:
            self._folded[id(mark)] = weakref.ref(
                mark, lambda _, key=id(mark): self._folded.pop(key, None))
        except TypeError:  # no leaf, or a plain Python number: not remembered
            pass


_WINDOWS = _PendingWindows()
_metrics.before_read(_WINDOWS.drain)


class TrainManyDispatch:
    """What `jit_train_many` returns: the jitted K-step scan, called the same
    way, with the program's own account of each call. Per call: the jitted
    call runs inside `trace.span("trainer", "dispatch")` (the annotation
    `oetpu.trainer.dispatch` on the profiler's clock, so an idle gap of the
    device in ANY loop over this entry point has the program's name on it;
    the histogram `trainer.dispatch.ms`) and inside
    `compile_cache.entry("train_many")` (whatever it traces, compiles or
    loads is `compile.*{fn="train_many"}`); the returned window's metrics go
    to `_PendingWindows.sent`. Nothing else: `.lower` and every other
    attribute are the jitted function's."""

    def __init__(self, fn, fold):
        self._fn = fn
        self._fold = fold

    def __call__(self, *args, **kwargs):
        with _compile_cache.entry("train_many"), \
                _trace.span("trainer", "dispatch"):
            out = self._fn(*args, **kwargs)
        if not isinstance(out[1]["loss"], jax.core.Tracer):
            _WINDOWS.sent(self._fold, out[1])  # (a trace of it sends nothing)
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class Trainer:
    """Builds jitted train/eval steps for an EmbeddingModel on one device.

    The multi-device version (mesh / shard_map, DP dense + row-sharded tables) is
    `parallel.MeshTrainer`, which reuses these per-device step functions.
    """

    num_shards = 1  # MeshTrainer overrides with the mesh size

    def __init__(self, model: EmbeddingModel,
                 optimizer: Optional[SparseOptimizer] = None, seed: int = 0,
                 *, offload_pipeline: bool = False, offload_densify: int = 1,
                 offload_stage_depth: int = 1,
                 sentinel: bool = False, halt_on_nonfinite: bool = False,
                 measure_every: int = 0):
        self.model = model
        self.optimizer = optimizer or Adagrad()
        self.seed = seed
        # numerics sentinel: adds additive health stats to the step's stats
        # dict (per-table grad sumsq / non-finite counts, loss finiteness, ef
        # residual magnitude, int8/bf16 quantization error), folded into
        # `health.*` gauges by `metrics.record_step_stats`. A static Python
        # bool, so sentinel=False traces byte-identical HLO to before.
        # halt_on_nonfinite implies sentinel and makes
        # `Trainer.record_step_stats` raise NonFiniteError naming the
        # offending table/phase.
        self.halt_on_nonfinite = bool(halt_on_nonfinite)
        self.sentinel = bool(sentinel) or self.halt_on_nonfinite
        # sampled measured step timing (utils/stepwatch.StepWatch): sample one
        # call in N with a block_until_ready bracket into `trainer.step_ms`
        # plus HLO-byte attribution and `exchange.cost_drift`; 0 = off
        self.measure_every = int(measure_every)
        self._stepwatch = None
        # host_cached pipeline knobs (tables/host_offload.py): pipeline=True
        # double-buffers the next batch's host lookup + admit upload on a
        # background thread (drive it via `offload_stage`); densify K>1
        # accumulates evict/flush writebacks and merges once per K batches;
        # stage_depth D>1 turns the single staging slot into a ring so the
        # loop can run the host lookup up to D batches ahead
        self.offload_pipeline = bool(offload_pipeline)
        self.offload_densify = int(offload_densify)
        self.offload_stage_depth = int(offload_stage_depth)
        # storage="host_cached" variables (tables/host_offload.py), filled by
        # init_tables; empty when every table lives fully in HBM
        self.offload: Dict[str, Any] = {}
        # heavy-hitter skew telemetry (utils/sketch.py), opt-in via
        # enable_skew_monitor(): per-table id batches feed the global
        # Space-Saving sketches off the hot path
        self._skew = None

    def enable_skew_monitor(self, monitor=None):
        """Feed every trained batch's ids (per table) into the heavy-hitter
        sketches (`utils/sketch.MONITOR` unless one is given). The feed is a
        bounded-queue put per table per batch — batches are DROPPED (and
        counted in `skew.dropped_batches`) when the sketch worker falls
        behind, so it can never slow the loop it measures."""
        from .utils import sketch
        self._skew = monitor if monitor is not None else sketch.MONITOR
        return self._skew

    def record_batch_skew(self, batch) -> None:
        """Enqueue one batch's per-table ids into the skew monitor (no-op
        until `enable_skew_monitor()`). Called by `offload_prepare`, so the
        example loops get it for free; scan windows pass stacked batches
        (the sketch flattens)."""
        if self._skew is None:
            return
        if self.model.batch_transform is not None:
            batch = self.model.batch_transform(batch)
        sparse = batch.get("sparse") or {}
        for name, spec in self.model.ps_specs().items():
            ids = sparse.get(spec.feature_name)
            if ids is not None:
                self._skew.observe(name, ids)

    # -- checkpointing (reference: model.save/save_weights/load_weights wiring,
    #    `exb.py:550-583`) -------------------------------------------------------
    def _stage_save(self, write_fn, path: str):
        """Remote-URI checkpoints write locally then push through the URI's
        filesystem adapter (`utils/fs.py` — the reference's HDFS dump via
        hadoop pipes, `EmbeddingShardFile.h`). Each process pushes only the
        files it wrote, so multi-host uploads compose."""
        from .utils import fs as fsmod
        if not fsmod.is_remote(path):
            return write_fn(path)
        import shutil
        import tempfile
        local = tempfile.mkdtemp(prefix="oetpu_ckpt_out_")
        try:
            meta = write_fn(local)
            fsmod.stage_out(local, path)
            return meta
        finally:
            shutil.rmtree(local, ignore_errors=True)

    def _stage_load(self, read_fn, path: str):
        from .utils import fs as fsmod
        with fsmod.staged(path) as local:
            return read_fn(local)

    def save(self, state: "TrainState", path: str, **kw):
        from .checkpoint import save_server_model
        return self._stage_save(
            lambda p: save_server_model(
                state, self.model, p, num_shards=self.num_shards,
                offload_stores=self.offload_store_snapshots(state), **kw),
            path)

    def load(self, state: "TrainState", path: str):
        """Dispatches on the checkpoint layout: single-file (this class's save)
        or per-shard streaming (`MeshTrainer.save` / `parallel/checkpoint.py`) —
        either loads at any target mesh size. Remote URIs stage to local disk
        first (the loaders are random-access/memmap'd)."""
        def read(p):
            from .parallel.checkpoint import checkpoint_layout, load_sharded
            if checkpoint_layout(p) == "sharded":
                return load_sharded(state, self.model, p,
                                    num_shards=self.num_shards,
                                    offload=self.offload)
            from .checkpoint import load_server_model
            return load_server_model(state, self.model, p,
                                     num_shards=self.num_shards,
                                     offload=self.offload)

        return self._stage_load(read, path)

    # -- host offload drivers (storage="host_cached" variables) ---------------
    #
    # The reference picks the PMem-backed table per variable at init
    # (`EmbeddingInitOperator.cpp:146-168`) and its cache admission rides pull
    # requests server-side; here ids are known host-side from the input
    # pipeline, so the Trainer drives the cache around the jitted step:
    #
    #     state = trainer.offload_prepare(state, batch)   # admit/flush
    #     state, metrics = step(state, batch)             # pure device step
    #
    # For scan-fused multi-step driving (`jit_train_many`), pass the stacked
    # batches: the union of the K batches' ids is admitted up front.

    def offload_prepare(self, state: "TrainState", batch) -> "TrainState":
        """Admit the batch's ids into each host-cached table's device cache
        (flushing first if the cache would exceed its high-water mark) and
        return the state with the refreshed cache tables. No-op without
        host-cached variables. Also the per-batch host-side hook the skew
        monitor rides (`record_batch_skew` — no-op unless enabled)."""
        self.record_batch_skew(batch)
        if not self.offload:
            return state
        if self.model.batch_transform is not None:
            batch = self.model.batch_transform(batch)
        new_tables = dict(state.tables)
        for name, ot in self.offload.items():
            ot.adopt(state.tables[name])
            ot.prepare(batch["sparse"][self.model.specs[name].feature_name])
            new_tables[name] = ot.state
        self._offload_prepared = True  # train_many's trace-time guard
        return state.replace(tables=new_tables)

    def offload_stage(self, batch) -> None:
        """Kick off the background host lookup + upload for a FUTURE batch
        while the device is busy with the current step (no-op unless the
        trainer was built with offload_pipeline=True). Pipelined loop shape:

            trainer.offload_stage(batches[0])
            for i, batch in enumerate(batches):
                state = trainer.offload_prepare(state, batch)  # consumes stage
                if i + 1 < len(batches):
                    trainer.offload_stage(batches[i + 1])      # overlaps step
                state, m = step(state, batch)

        With offload_stage_depth=D > 1 the stage slot is a ring: call this up
        to D batches ahead (`trainer.offload_stage(batches[i + d])` for
        d = 1..D) and each `offload_prepare` consumes the oldest matching
        entry, so D host lookups run under D device steps.

        Staging is a hint: `offload_prepare` verifies the staged ids match and
        falls back to the synchronous path when they don't."""
        if not self.offload:
            return
        if self.model.batch_transform is not None:
            batch = self.model.batch_transform(batch)
        for name, ot in self.offload.items():
            ot.stage(batch["sparse"][self.model.specs[name].feature_name])

    def offload_flush(self, state: "TrainState") -> "TrainState":
        """Write every resident row back to the host store and reset the
        caches (end of training / before handing tables elsewhere)."""
        if not self.offload:
            return state
        new_tables = dict(state.tables)
        for name, ot in self.offload.items():
            ot.adopt(state.tables[name])
            ot.flush()
            new_tables[name] = ot.state
        return state.replace(tables=new_tables)

    # hot-row replication is a mesh concept (MeshTrainer(hot_rows=...));
    # the base hooks are identities so persisters/loops drive either trainer
    # uniformly (see parallel/sharded.py "HOT-ROW REPLICATION")
    hot_enabled = False

    def hot_sync(self, state: "TrainState") -> "TrainState":
        """Write replicated hot rows back into their owner shards before any
        external consumer reads raw table state. No-op off-mesh; MeshTrainer
        overrides (the persisters call it before every snapshot/delta so
        on-disk artifacts stay byte-identical to a hot-off run)."""
        return state

    def externalize(self, state: "TrainState") -> "TrainState":
        """Return the state in its CANONICAL external layout: hot/migrated
        rows written home (`hot_sync`) and — under MeshTrainer(dense_shard=
        True) — the flat sharded dense optimizer state unsharded back to the
        per-leaf baseline form. Checkpoint/persist/export writers go through
        this hook, which is what keeps their artifacts byte-identical to a
        placement-off, ZeRO-off run. The returned state is for EXTERNAL
        readers; keep training on the original."""
        return self.hot_sync(state)

    @staticmethod
    def overflow_count(metrics) -> int:
        """Exchange-bucket drops in a step's (or scan window's) metrics.
        Single-device tables have no bounded buckets — always 0 here;
        MeshTrainer overrides with the real counter read, so training loops
        can call the governance hooks on either trainer."""
        del metrics
        return 0

    def check_overflow(self, metrics, **kw) -> bool:
        """Overflow-policy hook (no-op off-mesh; see MeshTrainer)."""
        del metrics, kw
        return False

    def table_overflow(self, state: "TrainState", name: str) -> int:
        """Lifetime dropped-id count for one table — includes overflow banked
        across host-offload cache resets (the device counter alone restarts at
        0 on every flush)."""
        ts = state.tables.get(name)
        dev = int(ts.overflow) if ts is not None and ts.overflow is not None \
            else 0
        if name in self.offload:
            return self.offload[name]._overflow_flushed + dev
        return dev

    def offload_store_snapshots(self, state: Optional["TrainState"] = None):
        """{name: HostStore snapshot} with all resident rows written back —
        what the checkpoint writers serialize for host-cached variables.
        Empty dict when nothing is offloaded."""
        out = {}
        for name, ot in self.offload.items():
            if state is not None:
                ot.adopt(state.tables[name])
            ot.sync_to_store()
            out[name] = ot.store.snapshot()
        return out

    def opt_for(self, spec: EmbeddingSpec) -> SparseOptimizer:
        return spec.optimizer or self.optimizer

    def _loss(self, outputs, batch):
        """Pass the per-sample weight through when the batch carries one (padded
        tail batches from `data.CriteoBatcher`); loss fns without a weight arg
        keep working for weightless batches. `outputs` is whatever the module
        returned, one array of logits or a pytree of several (`_loss_terms`)."""
        w = batch.get("weight")
        if w is None:
            return self.model.loss_fn(outputs, batch["label"])
        return self.model.loss_fn(outputs, batch["label"], jnp.asarray(w))

    def _loss_terms(self, outputs, batch):
        """-> (loss, {stat: scalar}, the main logits). A module's output may
        be a pytree (several heads): the `loss_fn` receives all of it, and
        its FIRST leaf is the main logits, which is all the step's metrics
        keep. A loss of several terms may name them by returning (loss,
        {stat: scalar}); they ride with the module's own step stats."""
        loss = self._loss(outputs, batch)
        loss, terms = loss if isinstance(loss, tuple) else (loss, {})
        return loss, terms, jax.tree_util.tree_leaves(outputs)[0]

    # -- init ---------------------------------------------------------------

    def init(self, sample_batch: Dict[str, Any]) -> TrainState:
        """The first TrainState, inside `trace.span("trainer", "init")`
        (series `trainer.init.ms`); what it traces and compiles is
        `compile.*{fn="init"}` (`utils/compile_cache.py`)."""
        with _compile_cache.entry("init"), _trace.span("trainer", "init"):
            return self._init_state(sample_batch)

    def _init_state(self, sample_batch: Dict[str, Any]) -> TrainState:
        # the one warning jit can't emit: int64 ids under x64-off silently
        # truncate at the device boundary (hi lane lost) — the pair layout
        # (`ops/id64.py`, `synthetic_criteo(ids_dtype='pair')`) is the fix
        if not jax.config.jax_enable_x64:
            import numpy as _np
            for name, spec in self.model.ps_specs().items():
                if not spec.use_hash_table:
                    continue
                ids = _np.asarray(sample_batch["sparse"][spec.feature_name])
                if ids.dtype == _np.int64 and (ids >= (1 << 31)).any():
                    import warnings
                    warnings.warn(
                        f"embedding {name!r}: int64 ids >= 2^31 with "
                        "jax_enable_x64 off TRUNCATE to int32 on device "
                        "(ids congruent mod 2^32 collide). Feed the split-"
                        "pair layout instead (ops/id64.np_split_ids or "
                        "ids_dtype='pair').", UserWarning)
        key = jax.random.PRNGKey(self.seed)
        if self.model.batch_transform is not None:
            sample_batch = self.model.batch_transform(sample_batch)
        embedded = self._fake_embedded(sample_batch)
        dense_inputs = sample_batch.get("dense")
        # ONE traced program: called eagerly a tower's init is a dispatch an
        # operation (66 for a DeepFM, each a compile or a cache read, all of
        # it under the interpreter's lock: 2.1 of `trainer.init_s`' 2.3 s on
        # the chip's host, PERF.md section 6, PR 42). The state is the eager
        # init's to a few roundings (the compiler contracts an initializer's
        # multiply-add inside a program: `tests/test_compile_account.py`).
        variables = jax.jit(self.module_init)(key, embedded, dense_inputs)
        params = variables["params"]
        # sparse_as_dense tables live inside dense params under a reserved scope
        sad = {}
        for name, spec in self.model.sad_specs().items():
            k = jax.random.fold_in(key, 7919 + spec.variable_id)
            sad[name] = spec.initializer(k, (spec.input_dim, spec.output_dim),
                                         spec.dtype)
        if sad:
            params = dict(params)
            params["__embeddings__"] = sad
        # optimizer slots only for the TRAINABLE subtree: modules carrying
        # frozen state (Keras BatchNorm stats, seed-generator counters) split
        # it out — those leaves update from the forward pass, never the
        # optimizer, and integer leaves cannot take optimizer math anyway
        split = getattr(self.model.module, "split_params", None)
        slots_over = split(params)[0] if split is not None else params
        tables = self.init_tables()
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            dense_params=params,
            dense_slots=jax.jit(partial(init_dense_slots, self.optimizer))(
                slots_over),
            tables=tables,
            model_version=jnp.zeros((), jnp.int32),
        )

    def _check_num_shards(self) -> None:
        """`EmbeddingSpec.num_shards` exists for reference API parity
        (`exb.py:388-419`: rows spread over N PS processes, placement round-
        robined in `WorkerContext.cpp:66-85`). Under SPMD there are no server
        processes to place onto — every table shards over the WHOLE mesh, which
        strictly dominates sub-mesh placement on TPU (the all_to_all spans all
        ICI links either way; fewer shards would only idle devices). A value
        other than -1/mesh-size is therefore NOT honored, and silence would be
        a lying knob — say so loudly."""
        for name, spec in self.model.ps_specs().items():
            if spec.num_shards not in (-1, self.num_shards):
                import warnings
                warnings.warn(
                    f"embedding {name!r}: num_shards={spec.num_shards} is not "
                    f"honored — tables always shard over the whole mesh "
                    f"({self.num_shards} device(s)) under SPMD; see "
                    "PARITY.md 'num_shards'", UserWarning)

    def init_tables(self) -> Dict[str, EmbeddingTableState]:
        """Hook: single-device tables. MeshTrainer overrides to create the tables
        directly sharded (a huge table must never materialize on one device)."""
        self._check_num_shards()
        tables = {}
        for name, spec in self.model.ps_specs().items():
            if spec.storage == "host_cached":
                from .tables.host_offload import HostOffloadTable
                ot = HostOffloadTable(spec, self.opt_for(spec), seed=self.seed,
                                      pipeline=self.offload_pipeline,
                                      densify_k=self.offload_densify,
                                      stage_depth=self.offload_stage_depth)
                self.offload[name] = ot
                tables[name] = ot.state
            else:  # one program a table, as the tower's (`_init_state`)
                tables[name] = jax.jit(partial(
                    init_table_state, spec, self.opt_for(spec),
                    seed=self.seed))()
        return tables

    def module_init(self, key, embedded, dense_inputs):
        return self.model.module.init(key, embedded, dense_inputs)

    def _fake_embedded(self, batch):
        from .ops.id64 import is_pair
        out = {}
        for name, spec in self.model.specs.items():
            ids = jnp.asarray(batch["sparse"][spec.feature_name])
            shape = (ids.shape[:-1] if spec.use_hash_table and is_pair(ids)
                     else ids.shape)
            if spec.combiner:  # pooling collapses the trailing field axis
                shape = shape[:-1]
            out[name] = jnp.zeros(shape + (spec.output_dim,), spec.dtype)
        attach_ids(out, self.model, batch)
        if getattr(self.model.module, "takes_tables", False):
            out[TABLES_KEY] = {
                name: jnp.zeros((spec.input_dim, spec.output_dim), spec.dtype)
                for name, spec in self.model.sad_specs().items()}
        return attach_targets(out, self.model, batch)

    # -- the per-device step (pure; shard_map-able) -------------------------

    # oelint: hot-path device_get=0 (the traced step: zero host syncs; the
    # ONE allowed per-step device_get lives in metrics.record_step_stats)
    def train_step(self, state: TrainState, batch, *,
                   packed=None) -> Tuple[TrainState, Dict]:
        """One synchronous step: pull -> fwd/bwd -> dense apply + sparse apply.

        The reference needs a 4-RPC protocol with batch-version gating for this
        (`EmbeddingPullOperator`/`Push`/`Store` + `exb_barrier`); under SPMD the whole
        step is one XLA program and is synchronous by construction.

        `packed`: {name: column layout} for tables whose state currently holds
        the packed weights+slots array (only inside `train_many`'s scan; see
        `ops/sparse.packed_layout`).

        The step's stages carry `trace.scope` names (`sparse.pull`,
        `dense.tower`, `dense.reduce`, `dense.update`, `sparse.apply`, ...;
        `utils/trace.py`): HLO metadata that a device profile reads
        (`tools/trace_report.py --xplane`), never a /metrics series — this
        body runs once per compile, so a clock read here would time tracing.
        Per-step wall time is the CALLER's span around the jitted fn
        (`vtimer("train", "step")`, `measure_every`). `trainer.traces{fn=}`
        counts the times this body ran, i.e. the traces.
        """
        _metrics.observe("trainer.traces", 1, "sum",
                         labels={"fn": "train_step"})
        model = self.model
        if model.batch_transform is not None:
            batch = model.batch_transform(batch)
        ps_specs = model.ps_specs()
        sad_specs = model.sad_specs()
        packed = packed or {}
        # modules with frozen (non-trainable) state: differentiate only the
        # trainable subtree, thread the frozen one through as a constant, and
        # take its NEW values from the training forward pass (Keras BatchNorm
        # moving stats / seed counters; reference graphs train them the same
        # way inside `distributed_model()`, `exb.py:593-642`)
        split = getattr(model.module, "split_params", None)
        train_apply = getattr(model.module, "apply_train", None)
        if split is not None:
            tr0, fr0 = split(state.dense_params)
        else:
            tr0, fr0 = state.dense_params, None

        # PULL: gather rows for this batch (non-differentiated w.r.t. the table — the
        # rows themselves are the leaf, exactly the reference's pull/push contract).
        # Hash tables insert unseen ids here, so pull threads the table state.
        # MeshTrainer's tables_pull/tables_apply are the sharded exchange
        # (3 all_to_alls per dim-group).
        pulled_tables, pulled, stats, pull_plans = self.tables_pull(
            state.tables, batch, ps_specs, packed)

        return self._train_step_tail(state, batch, ps_specs, sad_specs,
                                     packed, tr0, fr0, pulled_tables, pulled,
                                     stats, pull_plans)

    def _train_step_tail(self, state, batch, ps_specs, sad_specs, packed,
                         tr0, fr0, pulled_tables, pulled, stats, pull_plans):
        """The post-pull remainder of `train_step` — fwd/bwd + dense apply +
        sparse apply — factored out so the software-pipelined
        `MeshTrainer.train_many` can feed it a pull PREFETCHED one scan
        iteration earlier (parallel/trainer.py). The serial path calls it
        straight after its own pull: pure code motion (the getattr re-lookups
        below trace no equations), so pipeline-off HLO stays byte-identical.
        `batch` is the already-transformed batch."""
        model = self.model
        split = getattr(model.module, "split_params", None)
        train_apply = getattr(model.module, "apply_train", None)

        def loss_fn(tr_params, pulled_rows):
            dense_params = (model.module.merge_params(tr_params, fr0)
                            if split is not None else tr_params)
            # combiner pooling happens INSIDE the differentiated function so
            # autodiff hands table_apply per-slot (B, F, dim) grads that line
            # up with the (B, F) id array; the mask multiply zeroes pad-slot
            # grads (see embedding.combine)
            embedded = {
                name: combine(ps_specs[name],
                              jnp.asarray(batch["sparse"][
                                  ps_specs[name].feature_name]), rows)
                for name, rows in pulled_rows.items()}
            for name, spec in sad_specs.items():
                table = dense_params["__embeddings__"][name]
                ids = jnp.asarray(batch["sparse"][spec.feature_name])
                embedded[name] = combine(spec, ids, sad_rows(table, ids))
            attach_ids(embedded, model, batch)
            attach_tables(embedded, model,
                          dense_params.get("__embeddings__", {}))
            attach_targets(embedded, model, batch)
            fr_new, module_stats = None, {}
            if train_apply is not None:
                logits, fr_new = train_apply({"params": dense_params},
                                             embedded, batch.get("dense"))
            elif self._module_stats:
                logits, module_stats = model.module.apply_with_stats(
                    {"params": dense_params}, embedded, batch.get("dense"))
            else:
                logits = model.module.apply({"params": dense_params},
                                            embedded, batch.get("dense"))
            loss, terms, logits = self._loss_terms(logits, batch)
            return loss, (logits, fr_new, {**module_stats, **terms})

        # forward, combine, loss and backward: one stage (the backward's ops
        # read `transpose(jvp(dense.tower))`, which still holds the name)
        with _trace.scope("dense", "tower"):
            (loss, (logits, fr_new, module_stats)), (dense_grads, row_grads) = \
                jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
                    tr0, pulled)
        stats.update({"module/" + k: v for k, v in module_stats.items()})

        # sentinel reads the PRE-reduction dense grads: per-shard local
        # sumsq psums (via reduce_metrics) to one well-defined global
        # quantity in both the allreduce and the ZeRO (unreduced-here)
        # paths
        raw_dense_grads = dense_grads if self.sentinel else None
        with _trace.scope("dense", "reduce"):
            stats.update(self.dense_grad_stats(dense_grads))
            dense_grads = self.reduce_dense_grads(dense_grads)

        # DENSE apply (reference: Keras optimizer after Horovod allreduce;
        # MeshTrainer(dense_shard=True) overrides with the ZeRO-sharded
        # reduce_scatter -> chunk update -> all_gather path)
        with _trace.scope("dense", "update"):
            new_params, new_slots = self.dense_update(
                tr0, state.dense_slots, dense_grads)
            if split is not None:
                fr = fr_new if fr_new is not None else fr0
                new_params = model.module.merge_params(
                    new_params, self.reduce_module_state(fr))

        # SPARSE push+update (reference: PushGradients + UpdateWeights
        # store op)
        new_tables = dict(state.tables)
        applied, push_stats = self.tables_apply(
            ps_specs, pulled_tables, batch, row_grads, packed, pull_plans)
        new_tables.update(applied)
        stats.update(push_stats)
        if self.sentinel:
            stats.update(self._sentinel_stats(
                loss, raw_dense_grads, row_grads, new_tables))

        new_state = TrainState(
            step=state.step + 1,
            dense_params=new_params,
            dense_slots=new_slots,
            tables=new_tables,
            model_version=state.model_version + 1,
        )
        metrics = self.reduce_metrics({"loss": loss, "logits": logits,
                                       "stats": stats})
        return new_state, metrics

    # hooks overridden by MeshTrainer:
    def tables_pull(self, tables, batch, ps_specs, packed):
        """Pull every PS table's rows for this batch: one local pull per
        table. MeshTrainer overrides with the sharded dim-group exchange.
        -> ({name: new_table}, {name: rows}, {stat: v}, {name: plan})."""
        pulled_tables, pulled, stats, plans = {}, {}, {}, {}
        for name, spec in ps_specs.items():
            ids = jnp.asarray(batch["sparse"][spec.feature_name])
            pulled_tables[name], pulled[name], pull_stats, plans[name] = (
                self._packed_pull(spec, tables[name], ids, packed[name])
                if name in packed else self.table_pull(spec, tables[name], ids))
            for k, v in pull_stats.items():
                stats[f"{name}/{k}"] = v
        return pulled_tables, pulled, stats, plans

    def tables_apply(self, ps_specs, pulled_tables, batch, row_grads, packed,
                     plans):
        """Push + fused update for every PS table: one local apply per
        table. MeshTrainer overrides with the sharded dim-group exchange.
        -> ({name: new_table}, {stat: v})."""
        new_tables, stats = {}, {}
        for name, spec in ps_specs.items():
            ids = jnp.asarray(batch["sparse"][spec.feature_name])
            if name in packed:
                new_tables[name], push_stats = self._packed_apply(
                    spec, pulled_tables[name], ids, row_grads[name],
                    packed[name], plans[name])
            else:
                new_tables[name], push_stats = self.table_apply(
                    spec, pulled_tables[name], ids, row_grads[name],
                    plans[name])
            for k, v in push_stats.items():
                stats[f"{name}/{k}"] = v
        return new_tables, stats

    def reduce_dense_grads(self, grads):
        return grads

    def dense_grad_stats(self, grads):
        """Stats read off the PRE-reduction dense grads (they ride the
        step's per-key stats psum like everything else in `stats`).
        Default: none. MeshTrainer(dense_stats=True) publishes the
        `dense/grad_density` nonzero fraction the sparse dense-wire policy
        prices against."""
        del grads
        return {}

    def dense_update(self, params, slots, grads):
        """Apply the dense optimizer update. `grads` arrive already reduced
        by `reduce_dense_grads`. MeshTrainer(dense_shard=True) overrides with
        the ZeRO-sharded update (parallel/zero.py)."""
        return dense_apply(self.optimizer, params, slots, grads)

    def reduce_module_state(self, fr):
        """Frozen-state updates from the training forward pass. On meshes the
        float leaves (BatchNorm moving stats computed from LOCAL batch
        statistics — same per-replica behavior the reference's Horovod DP
        has) pmean to one replicated value; integer leaves (seed counters,
        identical on every shard) pass through."""
        return fr

    def reduce_metrics(self, metrics):
        return metrics

    # oelint: hot-path device_get=0 (pure traced math appended to the step's
    # stats dict — the ONE host sync still happens in record_step_stats)
    def _sentinel_stats(self, loss, dense_grads, row_grads,
                        tables) -> Dict[str, jax.Array]:
        """Numerics-sentinel stats for this shard, every value ADDITIVE so
        `MeshTrainer.reduce_metrics`'s per-key psum yields the global figure:
        sumsq (host takes sqrt after the psum), non-finite element counts, ef
        abs-sum + element counts, and the wire-quantization error sumsq
        (fp32-vs-roundtrip through `ops.wire.pack_inband`, skipped when the
        exchange ships fp32 or there is no exchange at all)."""
        with _trace.scope("trainer", "sentinel"):
            f32 = jnp.float32
            out: Dict[str, jax.Array] = {}
            loss_arr = jnp.asarray(loss, f32)
            out["health/loss_nonfinite"] = jnp.sum(
                ~jnp.isfinite(loss_arr)).astype(f32)
            sumsq = jnp.zeros((), f32)
            nonfin = jnp.zeros((), f32)
            for leaf in jax.tree_util.tree_leaves(dense_grads):
                g = jnp.asarray(leaf, f32)
                sumsq = sumsq + jnp.sum(jnp.square(g))
                nonfin = nonfin + jnp.sum(~jnp.isfinite(g)).astype(f32)
            out["health/dense_grad_sumsq"] = sumsq
            out["health/dense_grad_nonfinite"] = nonfin
            fmt = None
            if self.num_shards > 1:
                from .ops.wire import wire_format
                fmt = wire_format(getattr(self, "wire", None))
                if fmt == "fp32":
                    fmt = None
            for name, g in (row_grads or {}).items():
                g = jnp.asarray(g, f32)
                out[f"{name}/grad_sumsq"] = jnp.sum(jnp.square(g))
                out[f"{name}/grad_nonfinite"] = jnp.sum(
                    ~jnp.isfinite(g)).astype(f32)
                if fmt is not None and g.ndim >= 2 and g.shape[-1] > 0:
                    from .ops.wire import pack_inband, unpack_inband
                    rows = g.reshape(-1, g.shape[-1])
                    back = unpack_inband(pack_inband(rows, fmt),
                                         rows.shape[-1], fmt)
                    out[f"{name}/quant_err_sumsq"] = jnp.sum(
                        jnp.square(back - rows))
            for name, ts in tables.items():
                ef = getattr(ts, "ef", None)
                if ef is None:
                    continue
                out[f"{name}/ef_abs_sum"] = jnp.sum(jnp.abs(jnp.asarray(ef, f32)))
                # a trace-time constant, shipped as a stat so the host-side mean
                # divides by the GLOBAL (psum'd) element count
                out[f"{name}/ef_elems"] = jnp.asarray(float(ef.size), f32)
            return out

    def record_step_stats(self, step_metrics):
        """Fold one step's metrics through the spine
        (`metrics.record_step_stats` — the single allowed per-step
        device_get) and, with `halt_on_nonfinite=True`, raise
        `metrics.NonFiniteError` naming the offending table/phase when the
        sentinel saw a non-finite loss or gradient. Returns the health
        summary dict."""
        stats = step_metrics
        if isinstance(step_metrics, dict) and "stats" in step_metrics:
            stats = step_metrics["stats"]
        health = _metrics.record_step_stats(stats)
        if self.halt_on_nonfinite and health.get("nonfinite"):
            from .utils import capsule as _capsule
            _capsule.trigger("nonfinite", offenders=health["nonfinite"])
            raise _metrics.NonFiniteError(health["nonfinite"])
        return health

    def _ensure_stepwatch(self):
        """The (lazily created, cached) StepWatch for this trainer — shared
        by the measured step wrapper and the input-wait lane so step samples
        and input waits land under one label with one counter/baseline.
        None when measurement is off (`measure_every` <= 0)."""
        if self.measure_every <= 0:
            return None
        if self._stepwatch is None:
            from .utils.stepwatch import StepWatch
            self._stepwatch = StepWatch(
                every=self.measure_every,
                wire_cost=lambda: getattr(self, "last_wire_cost", None))
        return self._stepwatch

    def _wrap_measured(self, fn):
        """Wrap a jitted step with the sampled measurement mode
        (`measure_every` > 0): one call in N is bracketed host-side with
        `block_until_ready` into `trainer.step_ms` + `exchange.cost_drift`. The watch is cached so repeated
        `jit_train_step()` calls share one sample counter/baseline."""
        watch = self._ensure_stepwatch()
        return fn if watch is None else watch.wrap(fn)

    def input_timed(self, batches):
        """Wrap a batch iterator (typically a `data.ingest.FeedRing`) so the
        time the train loop blocks on each `next()` lands in the
        `trainer.input_wait_ms` histogram — the measured input-wait
        attribution lane (`data.ingest.input_wait_share` folds it against
        step time). Records through this trainer's StepWatch when
        measurement is on, straight into the spine otherwise:

            for batch in trainer.input_timed(ring):
                state, m = step(state, batch)
        """
        from .utils.stepwatch import timed_batches
        return timed_batches(batches, self._ensure_stepwatch())

    def table_pull(self, spec, table, ids):
        """-> (new_table, rows, stats, plan). The plan (routing/dedup state) is handed
        back to table_apply so push reuses pull's work; None here, where the
        table is split into weights and slots and every position gathers its
        row (inside the scan a packable table takes `_packed_pull`)."""
        _metrics.observe("sparse.pulls", 1, "sum",
                         labels={"path": "per_position"})
        with _trace.scope("sparse", "pull"):
            table, rows = lookup_train(spec, table, ids)
        return table, rows, {}, None

    def table_apply(self, spec, table, ids, grads, plan=None):
        """-> (new_table, stats)."""
        with _trace.scope("sparse", "apply"):
            return apply_gradients(spec, table, self.opt_for(spec), ids,
                                   grads, with_load=True)

    def table_lookup(self, spec, table, ids):
        return lookup(spec, table, ids)

    def eval_step(self, state: TrainState, batch) -> Dict:
        model = self.model
        if model.batch_transform is not None:
            batch = model.batch_transform(batch)
        embedded = {
            name: combine(
                spec, jnp.asarray(batch["sparse"][spec.feature_name]),
                self.table_lookup(spec, state.tables[name],
                                  jnp.asarray(batch["sparse"][spec.feature_name])))
            for name, spec in model.ps_specs().items()
        }
        for name, spec in model.sad_specs().items():
            table = state.dense_params["__embeddings__"][name]
            ids = jnp.asarray(batch["sparse"][spec.feature_name])
            embedded[name] = combine(spec, ids, sad_rows(table, ids))
        attach_ids(embedded, model, batch)
        attach_tables(embedded, model,
                      state.dense_params.get("__embeddings__", {}))
        attach_targets(embedded, model, batch)
        logits = model.module.apply({"params": state.dense_params}, embedded,
                                    batch.get("dense"))
        loss, _, logits = self._loss_terms(logits, batch)
        return {"logits": logits, "loss": loss}

    # -- jitted drivers ------------------------------------------------------

    def jit_train_step(self):
        """NOTE: the input TrainState is DONATED (huge tables must update in place,
        not 2x HBM) — always rebind: `state, metrics = step(state, batch)`; a stale
        `state` reference is dead after the call."""
        return self._wrap_measured(jax.jit(self.train_step,
                                           donate_argnums=(0,)))

    def _packed_layouts(self, state: TrainState):
        """{name: column layout} for tables worth packing inside the scan
        (see `ops/sparse.packed_layout`). Applies per shard under MeshTrainer
        too (widths are shard-invariant): its exchange takes the layout at
        the owner's serve and apply (parallel/sharded.py "THE OWNER PLANS
        ONCE A STEP")."""
        from .ops.sparse import packed_layout
        out = {}
        for name, spec in self.model.ps_specs().items():
            ts = state.tables[name]
            lay = packed_layout(spec.output_dim, ts.slots, ts.weights.dtype)
            if lay is not None:
                out[name] = lay
        return out

    def _packed_pull(self, spec, table, ids, layout):
        """Pull from the packed layout -> (table, rows, stats, plan). An
        array table plans its step first (`ops/sparse.plan_packed_rows`: the
        apply's dedup, and ONE gather of the unique packed rows at the
        apply's working size) and expands the weight columns to positions
        from that small array; the plan goes on to `_packed_apply`, which
        neither dedups nor gathers again. Hash tables keep their normal
        probe/insert, per position (keys are a separate array either way),
        and hand on no plan. `sparse.pulls{path=}` counts which, once a
        table a trace. Stages: the dedup `sparse.dedup`, its counts
        `sparse.reduce`, the unique gather and the expansion `sparse.pull`."""
        _metrics.observe("sparse.pulls", 1, "sum", labels={
            "path": "per_position" if spec.use_hash_table else "shared"})
        with _trace.scope("sparse", "pull"):
            from .embedding import _flat_ids
            flat, out_shape = _flat_ids(spec, ids)
            plan = None
            if spec.use_hash_table:
                from .tables.hash_table import hash_lookup_train
                table, rows = hash_lookup_train(table, flat,
                                                out_dim=spec.output_dim,
                                                layout=layout)
            else:
                from .ops.sparse import (lookup_rows, packed_width,
                                         plan_packed_rows)
                plan = plan_packed_rows(
                    table.weights, flat,
                    width=packed_width(spec.output_dim, layout))
                rows = lookup_rows(plan.rows[:, :spec.output_dim],
                                   plan.uniq.inverse)
            rows = rows.astype(spec.dtype).reshape(out_shape + (spec.output_dim,))
            return table, rows, {}, plan

    def _packed_apply(self, spec, table, ids, grads, layout, plan=None):
        with _trace.scope("sparse", "apply"):
            from .embedding import _flat_ids
            from .ops.sparse import sparse_apply_packed_table
            flat_ids, _ = _flat_ids(spec, ids)
            flat_grads = grads.reshape(-1, spec.output_dim)
            if spec.use_hash_table:
                from .tables.hash_table import hash_apply_gradients_packed
                return hash_apply_gradients_packed(
                    table, self.opt_for(spec), flat_ids, flat_grads, layout,
                    spec.output_dim)
            packed, load = sparse_apply_packed_table(
                self.opt_for(spec), table.weights, layout, spec.output_dim,
                flat_ids, flat_grads, plan=plan)
            return table.replace(weights=packed), load

    def train_many(self, state: TrainState, batches) -> Tuple[TrainState, Dict]:
        """K steps in ONE compiled program via lax.scan over stacked batches
        (every leaf has a leading K dim). One dispatch per K steps instead of K —
        host dispatch latency (worst over remote runtimes) amortizes away, the
        TPU-idiomatic step-fusion the reference cannot do (its step spans 4 RPCs).
        Returns (state, {"loss": (K,)}).

        Packable array tables run the scan on the PACKED weights+slots layout
        (one latency-bound gather/scatter pair per step instead of one per
        array — 1.44x on the fused apply, PERF.md): pack once at entry, unpack
        once at exit, amortized over K steps. State layout outside this
        function is unchanged.

        storage="host_cached" tables work too, but the caller MUST admit the
        union of the K batches' ids first — `offload_prepare(state, batches)`
        does it in one jitted admission (a scan cannot interleave host-side
        admission, so an unprepared cache would silently train initializer
        rows where the host store holds trained ones). Use
        `offload_train_many`, which drives prepare -> scan -> adopt."""
        _metrics.observe("trainer.traces", 1, "sum",
                         labels={"fn": "train_many"})
        if self.offload and not getattr(self, "_offload_prepared", False):
            # trace-time fail-fast for the old misuse (an unprepared cache
            # trains initializer rows over the store's trained ones); repeat
            # calls bypass Python, so the per-window prepare contract itself
            # is enforced by convention — offload_train_many does it right
            raise ValueError(
                "train_many on storage='host_cached' tables needs the union "
                "of the K batches' ids admitted first: use "
                "trainer.offload_train_many(state, batches) (or call "
                "offload_prepare(state, batches) before every window).")
        from .ops.sparse import pack_table, unpack_table
        layouts = self._packed_layouts(state)
        if layouts:
            tables = dict(state.tables)
            for name, lay in layouts.items():
                ts = tables[name]
                tables[name] = ts.replace(
                    weights=pack_table(ts.weights, ts.slots, lay), slots={})
            state = state.replace(tables=tables)

        def body(state, batch):
            state, metrics = self.train_step(state, batch, packed=layouts)
            oflow = jnp.zeros((), jnp.int32)
            for k, v in metrics.get("stats", {}).items():
                if k.endswith("_overflow"):
                    oflow = oflow + jnp.asarray(v).astype(jnp.int32)
            return state, (metrics["loss"], oflow,
                           self._scan_stats(metrics.get("stats", {})))

        state, (losses, oflows, kept) = jax.lax.scan(body, state, batches)

        if layouts:
            tables = dict(state.tables)
            for name, lay in layouts.items():
                spec = self.model.specs[name]
                ts = tables[name]
                w, slots = unpack_table(ts.weights, lay, spec.output_dim,
                                        spec.dtype)
                tables[name] = ts.replace(weights=w, slots=slots)
            state = state.replace(tables=tables)
        # "overflow": exchange-bucket drops summed over the window (the scan
        # returns no per-step stats; this one scalar is what capacity
        # governance needs — see MeshTrainer.check_overflow)
        return state, {"loss": losses, "overflow": jnp.sum(oflows),
                       **self._window_stats(kept)}

    @property
    def _module_stats(self) -> Dict[str, str]:
        """{stat: fold} a module counts a step (`module.window_stats`, with
        `apply_with_stats` handing them over): `moe.pairs_here` and the like.
        The fold ("avg", "max", "min", "sum") is over a window's steps and is the
        series' kind in `metrics.report()`."""
        return dict(getattr(self.model.module, "window_stats", None) or ())

    def _scan_stats(self, stats) -> Dict:
        """What a `train_many` window keeps of each step's stats beside the
        overflow sum (the scan stacks it over the K steps), and
        `_window_stats` what the window's metrics say of it: each table's
        apply load (`ops/sparse.py` "WHAT THE APPLY WORKS OVER") as
        "apply_fill" {table: the fullest step} and "apply_full_steps"
        {table: steps on the last rung}, and the module's own counters under
        "module" (`MeshTrainer` adds what the owner side of its exchange
        counted)."""
        return {**{k: stats["module/" + k] for k in self._module_stats},
                **_table_stats(stats, _metrics.APPLY_STATS)}

    def _window_stats(self, kept) -> Dict:
        fold = {"avg": jnp.mean, "max": jnp.max, "min": jnp.min,
                "sum": jnp.sum}
        out = _fold_table_stats(kept, _metrics.APPLY_STATS)
        module = {k: fold[how](kept[k])
                  for k, how in self._module_stats.items() if k in kept}
        if module:
            out["module"] = module
        return out

    def _window_fold(self):
        """-> fold(metrics): a `train_many` window's counters into series,
        holding nothing of this trainer (`_fold_window`)."""
        return partial(_fold_window, self._module_stats)

    def record_window_stats(self, metrics) -> None:
        """Fold a `train_many` window's counters into series NOW: each
        table's `sparse.apply_fill{table=}` (gauge) and
        `sparse.apply_full_steps{table=}` (counter), the module's own named
        as the module names them (`moe.pairs_here`, ...), and
        `trainer.windows{fn="train_many"}` (windows folded). ONE device_get,
        which waits for the window. Nobody has to call it: a window sent
        through `jit_train_many()` is folded by the entry point
        (`_PendingWindows`), and ONCE whoever asks, so calling it on such a
        window before or after counts nothing twice."""
        _WINDOWS.fold_now(self._window_fold(), metrics)

    def jit_train_many(self):
        """Scan-fused multi-step driver (state DONATED, like jit_train_step),
        as the program's dispatch object (`TrainManyDispatch`)."""
        return TrainManyDispatch(
            jax.jit(self.train_many, donate_argnums=(0,)),
            self._window_fold())

    def _many_fn(self, batches, state):
        """Cached jitted train_many (MeshTrainer overrides: its jit_train_many
        needs the samples to derive partition specs and caches internally)."""
        if getattr(self, "_cached_many_fn", None) is None:
            self._cached_many_fn = self.jit_train_many()
        return self._cached_many_fn

    def offload_train_many(self, state: TrainState, batches
                           ) -> Tuple[TrainState, Dict]:
        """Scan-fused driving of host-cached models: ONE jitted admission of the
        union of the K batches' ids (flushing first if over high-water), then
        the fused K-step scan — the 2x scan-fusion lever and the >HBM capacity
        story compose instead of excluding each other. The reference serves any
        table through the same hot path regardless of backing store
        (`PmemEmbeddingOptimizerVariable.h:88-198` folds its DRAM cache into
        pull/update); this is the scan-era equivalent.

        The cache must be able to hold the K-batch union: size `capacity` (and
        pick K) so `union_unique_ids <= high_water * capacity`, or admission
        warns and overflowed rows fall back to insert-on-pull semantics.
        Works (as a plain fused scan) for models with no offloaded tables."""
        state = self.offload_prepare(state, batches)
        many = self._many_fn(batches, state)
        state, m = many(state, batches)
        for name, ot in self.offload.items():
            ot.adopt(state.tables[name])
        return state, m

    def jit_eval_step(self):
        return jax.jit(self.eval_step)
