"""Driver `train_scan`: closed loop, one client. K distinct batches are made
from the seed, staged on the device once, and every dispatch runs the program's
own K-step scan (`Trainer.jit_train_many`, or `MeshTrainer.jit_train_many` where
the configuration says so) over them, each fenced on its losses, with
`dispatches_in_flight` of them sent before the host waits for the oldest (2: a
stall of the host shorter than one scan leaves the device fed). The input
pipeline does not run.

The ONE compiled scan with its state is built in set-up, driven from the seed
through its first dispatch (which is what the plain reference follows), and
handed on to the window. The staging and the fence are `bench.py`'s
(`_stacked_batches`, `_measure_many`), copied; `bench.py` is not imported.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, generators


EARLY_STEPS = 3  # the steps whose losses and whose rows' change are compared apart
_EVENTS = []  # (time, name) of every trace / compile / cache-load event of this process


def _on_event(name, secs, **kw):
    if "compile" in name or "jaxpr_trace" in name or "cache_retrieval" in name:
        _EVENTS.append((time.perf_counter(), name))


jax.monitoring.register_event_duration_secs_listener(_on_event)  # once: sessions hold no listener


def _path(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in keypath)


class Session:
    def __init__(self, *, cfg: Dict, traffic: Dict, chips: int, seed: int, reference):
        self.cfg, self.traffic, self.chips, self.seed = cfg, traffic, chips, seed
        self.ref = reference
        self.k_steps = int(traffic["steps_per_dispatch"])
        self.batch = int(traffic["batch_per_chip"]) * chips
        self.attempted = self.failed = 0
        self.phases = {}  # set-up, by phase, in seconds (printed on standard error)

    # -- set-up ---------------------------------------------------------------

    def _phase(self, name, since):
        now = time.perf_counter()
        self.phases[name] = round(now - since, 3)
        return now

    def setup(self):
        cfg, t = self.cfg, self.traffic
        mark = time.perf_counter()
        batches = generators.zipf_criteo_batches(
            batch_size=self.batch, steps=self.k_steps, id_space=cfg["vocabulary"],
            seed=self.seed, alpha=t["zipf_alpha"], num_fields=cfg["num_sparse"],
            dense_dim=cfg["num_dense"])
        self.host = generators.stack(batches)
        self.ids = self.host["sparse"]["categorical"]
        mark = self._phase("make_batches", mark)
        self._build_program(batches[0])
        mark = self._phase("build_program_and_state", mark)
        self._first_dispatch(mark)
        mark = time.perf_counter()
        for _ in range(int(t["warm_dispatches"])):
            self._dispatch()
        self._phase("warm_dispatches", mark)

    def _build_program(self, sample):
        import openembedding_tpu as embed
        from openembedding_tpu import models
        cfg = self.cfg
        model = getattr(models, "make_" + cfg["family"])(
            vocabulary=cfg["vocabulary"], dim=cfg["embedding_dim"],
            hidden=tuple(cfg["hidden"]), compute_dtype=jnp.dtype(cfg["tower_dtype"]),
            first_order=cfg["first_order"])
        opt = embed.Adagrad(learning_rate=cfg["learning_rate"],
                            initial_accumulator_value=cfg["adagrad_initial_accumulator"],
                            epsilon=cfg["adagrad_epsilon"])
        devices = jax.devices()[:self.chips]
        if cfg["trainer"] == "MeshTrainer":
            from jax.sharding import NamedSharding, PartitionSpec as P
            from openembedding_tpu.parallel import MeshTrainer, make_mesh
            self.trainer = MeshTrainer(model, opt, mesh=make_mesh(devices))
            self.mesh, self.axis = self.trainer.mesh, self.trainer.axis
            feed = NamedSharding(self.mesh, P(None, self.axis))
        else:
            from openembedding_tpu.model import Trainer
            self.trainer = Trainer(model, opt)
            self.mesh = self.axis = None
            feed = devices[0]
        self.shards = self.chips if self.mesh is not None else 1
        mark = time.perf_counter()
        template = self.trainer.init(sample)
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, template)
        shapes = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), template)
        del template
        self._phase("program_init", mark)
        self.keys = self.ref.make_keys(self.seed, cfg)
        self.state = jax.jit(lambda keys: self._make_state(shapes, keys),
                             out_shardings=shardings)(self.keys)
        self.stacked = jax.device_put(self.host, feed)
        if self.mesh is not None:
            self.many = self.trainer.jit_train_many(self.stacked, self.state)
        else:
            self.many = self.trainer.jit_train_many()

    def _make_state(self, shapes, keys):
        """The program's TrainState, every leaf made here from the seed."""
        cfg, acc0 = self.cfg, self.cfg["adagrad_initial_accumulator"]
        dense = self.ref.init_dense(keys, cfg)
        flat, treedef = jax.tree_util.tree_flatten_with_path(shapes.dense_params)
        got = {_path(p): s.shape for p, s in flat}
        want = {p: tuple(s) for p, s, _ in self.ref.dense_leaves(cfg)}
        if got != want:
            raise SystemExit(f"tower leaves differ: program {got}, configuration {want}")
        params = jax.tree_util.tree_unflatten(treedef, [dense[_path(p)] for p, _ in flat])
        slots = jax.tree_util.tree_map(lambda s: jnp.full(s.shape, acc0, s.dtype), shapes.dense_slots)
        tables = {}
        spec = self.ref.tables_of(cfg)
        if set(spec) != set(shapes.tables):
            raise SystemExit(f"tables differ: program {set(shapes.tables)}, configuration {set(spec)}")
        for name, ts in shapes.tables.items():
            rows, width = ts.weights.shape
            if width != spec[name]["width"] or set(ts.slots) != {"accum"} or ts.keys is not None:
                raise SystemExit(f"table {name}: unexpected layout {ts}")
            pos = jnp.arange(rows, dtype=jnp.uint32)
            per = rows // self.shards  # owner = id % shards, local row = id // shards
            ids = (pos % per) * self.shards + pos // per
            w = self.ref.init_rows(keys, cfg, ids)[name]
            tables[name] = ts.replace(weights=w, slots={"accum": jnp.full((rows, width), acc0, jnp.float32)})
        return shapes.replace(step=jnp.zeros((), jnp.int32), dense_params=params,
                              dense_slots=slots, tables=tables,
                              model_version=jnp.zeros((), jnp.int32))

    # -- the timed call -------------------------------------------------------

    def _send(self):
        """Enqueue one K-step scan on the state the last one leaves (JAX returns
        at once; the state is donated along the chain)."""
        with jax.profiler.TraceAnnotation("dispatch_scan"):
            self.state, metrics = self.many(self.state, self.stacked)
        return metrics

    def _fence(self, metrics):
        """Wait for a scan's losses on the host: that forces the whole scan."""
        with jax.profiler.TraceAnnotation("fence_loss"):
            metrics = jax.device_get(metrics)
        losses = np.asarray(metrics["loss"]).reshape(-1)
        bad = int(np.sum(~np.isfinite(losses)))
        if int(np.asarray(metrics["overflow"])) > 0:
            bad = losses.size  # rows dropped by a bounded bucket somewhere in this scan
        self.attempted += losses.size
        self.failed += bad
        return losses

    def _dispatch(self):
        return self._fence(self._send())

    def _first_dispatch(self, mark):
        """Steps 1..K from the seed, through the window's own call and feed; then
        the summary the comparison needs, read from the state it left."""
        losses = self._dispatch()
        mark = self._phase("first_dispatch_trace_compile_or_load", mark)
        per_step = [np.unique(step) for step in self.ids]
        uniq = np.unique(np.concatenate(per_step))
        later = np.unique(np.concatenate(per_step[1:])) if self.k_steps > 1 else uniq[:0]
        n = self.ids.size  # a fixed length, so that one program serves every seed
        ids = np.zeros((n,), np.int32)
        ids[:uniq.size] = uniq
        m_all = (np.arange(n) < uniq.size).astype(np.float32)
        m_first = np.zeros((n,), np.float32)
        m_first[np.searchsorted(uniq, np.setdiff1d(per_step[0], later))] = 1.0
        early = min(EARLY_STEPS, self.k_steps)  # rows that only the first three steps touch
        after = np.unique(np.concatenate(per_step[early:])) if self.k_steps > early else uniq[:0]
        m_early = np.zeros((n,), np.float32)
        m_early[np.searchsorted(uniq, np.setdiff1d(np.unique(np.concatenate(per_step[:early])), after))] = 1.0
        self.ref_feed = {"ids": ids, "uniq": uniq, "masks": np.stack([m_all, m_first, m_early]),
                         "touches": sum(u.size for u in per_step)}
        summ = self._probe_fn()(self.state.tables, self.keys, ids, self.ref_feed["masks"])
        dense = jax.device_get((self.state.dense_params, self.state.dense_slots))
        dense0 = jax.device_get(jax.jit(lambda k: self.ref.init_dense(k, self.cfg))(self.keys))
        self.prog = _summary(losses, jax.device_get(summ), dense0, dense,
                             self.cfg["adagrad_initial_accumulator"])
        self._phase("read_state_for_comparison", mark)

    def _probe_fn(self):
        cfg, shards, axis = self.cfg, self.shards, self.axis
        acc0 = cfg["adagrad_initial_accumulator"]

        def probe(tables, keys, ids, masks):
            m_all, m_first, m_early = masks
            me = jax.lax.axis_index(axis) if axis else 0
            own = (ids % shards == me).astype(jnp.float32)
            local = ids // shards
            w0 = self.ref.init_rows(keys, cfg, ids)
            out = {}
            for name, ts in tables.items():
                g2 = jnp.sum(ts.slots["accum"][local] - acc0, axis=-1) * own
                d2 = jnp.sum(jnp.square(ts.weights[local] - w0[name]), axis=-1) * own
                out[name] = jnp.stack([jnp.sum(g2 * m_all), jnp.sum(g2 * m_first),
                                       jnp.sum(d2 * m_all), jnp.sum(d2 * m_early)])
            return jax.lax.psum(out, axis) if axis else out

        if not axis:
            return jax.jit(probe)
        from jax.sharding import PartitionSpec as P
        rep = P()
        tspec = jax.tree_util.tree_map(lambda _: P(axis), self.state.tables)
        return jax.jit(jax.shard_map(probe, mesh=self.mesh,
                                     in_specs=(tspec, rep, rep, rep), out_specs=rep,
                                     check_vma=False))

    def window(self, seconds: float) -> Dict:
        """Scans back to back until `seconds` have passed, then the ones in flight
        to their end; the rate is every example of every step over the whole
        window, first send to last fence."""
        steps0, n0 = self.attempted, len(_EVENTS)
        depth = int(self.traffic["dispatches_in_flight"])
        t0 = time.perf_counter()
        sent = [self._send() for _ in range(depth - 1)]
        while time.perf_counter() - t0 < seconds:
            sent.append(self._send())   # the next scan is queued on the device ...
            self._fence(sent.pop(0))    # ... before the host waits for the oldest
        for metrics in sent:
            self._fence(metrics)
        t1 = time.perf_counter()
        steps = self.attempted - steps0
        rate = steps * self.batch / (t1 - t0) / self.chips
        return {"t0": t0, "seconds": t1 - t0, "steps": steps,
                "end_to_end": {"train_examples_per_s_per_chip": rate},
                "compiles_in_window": len(_EVENTS) - n0,
                "compile_events": [n for _, n in _EVENTS[n0:]][:5]}

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices()[:self.chips])

    def context(self) -> Dict:
        """What the per-layer readers may use besides the trace."""
        return {"cfg": self.cfg, "traffic": self.traffic, "chips": self.chips,
                "ids": self.ids, "batch": self.batch}

    def free(self):
        """Drop the program's state before the reference runs."""
        for leaf in jax.tree_util.tree_leaves((self.state, self.stacked)):
            leaf.delete()
        self.state = self.stacked = self.many = self.trainer = None

    # -- correct --------------------------------------------------------------

    def reference_summary(self, precision: str = "f32", fault: str = "") -> Dict:
        f = self.ref_feed
        if "idx" not in f:  # only the reference needs it: not part of set-up
            f["idx"] = np.searchsorted(f["uniq"], self.ids).astype(np.int32)
        out = self.ref.follow(self.seed, self.cfg, self.chips, f["ids"], f["idx"],
                              self.host["dense"], self.host["label"], f["masks"],
                              precision=precision, fault=fault)
        return ref_summary(jax.device_get(out), self._grad_floor())

    def _grad_floor(self) -> Dict[str, float]:
        """Per leaf, the norm that 4 ulps of the accumulator's start an
        element-update would leave: under it `acc_end - acc_start` is round-off."""
        ulp = float(np.spacing(np.float32(self.cfg["adagrad_initial_accumulator"])))
        updates = {"dense/" + p: int(np.prod(shape)) * self.k_steps
                   for p, shape, _ in self.ref.dense_leaves(self.cfg)}
        for name, t in self.ref.tables_of(self.cfg).items():
            updates["tables/" + name] = self.ref_feed["touches"] * t["width"]
        return {leaf: float(np.sqrt(4.0 * ulp * n)) for leaf, n in updates.items()}

    def check(self, limits: Dict) -> Dict:
        with jax.default_matmul_precision("highest"):
            ref = self.reference_summary()
        return compare.judge(compare.numbers(self.prog, ref), limits)


def _summary(losses, tables, dense0, dense, acc0) -> Dict:
    """The program's side, in the comparison's terms."""
    grad, first, delta, early = {}, {}, {}, {}
    for name, v in tables.items():
        leaf = "tables/" + name
        grad[leaf], first[leaf], delta[leaf], early[leaf] = (
            float(x) for x in np.sqrt(np.maximum(np.asarray(v, np.float64), 0.0)))
    params, slots = dense
    for (kp, p), (_, s) in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                               jax.tree_util.tree_flatten_with_path(
                                   slots, is_leaf=lambda x: isinstance(x, dict) and "accum" in x)[0]):
        path = _path(kp)
        acc = np.asarray(s["accum"], np.float64).reshape(-1)
        grad["dense/" + path] = float(np.sqrt(max(float(np.sum(acc - acc0)), 0.0)))
        delta["dense/" + path] = float(np.linalg.norm(
            np.asarray(p, np.float64).reshape(-1) - np.asarray(dense0[path], np.float64).reshape(-1)))
    return {"losses": np.asarray(losses, np.float64), "grad": grad, "first_grad": first,
            "delta": delta, "early_delta": early}


def ref_summary(out: Dict, grad_floor: Dict) -> Dict:
    """The reference's side (or a control's, or a planted fault's)."""
    grad, first, delta, early = {}, {}, {}, {}
    for name, v in out["tables"].items():
        leaf = "tables/" + name
        grad[leaf], first[leaf], delta[leaf], early[leaf] = (
            float(x) for x in np.sqrt(np.maximum(np.asarray(v, np.float64), 0.0)))
    for path, v in out["dense"].items():
        g, _, dl, _ = np.sqrt(np.maximum(np.asarray(v, np.float64), 0.0))
        grad["dense/" + path], delta["dense/" + path] = float(g), float(dl)
    return {"losses": np.asarray(out["losses"], np.float64), "grad": grad, "first_grad": first,
            "delta": delta, "early_delta": early, "grad_floor": grad_floor}


def open_session(**kw) -> Session:
    ref = importlib.import_module("benchmark.reference." + kw["cfg"]["family"])
    return Session(reference=ref, **kw)
