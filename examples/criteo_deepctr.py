"""Criteo CTR training — the reference benchmark workload, TPU-native.

Counterpart of `test/benchmark/criteo_deepctr.py` + `examples/criteo_deepctr_network*`:
pick a model family (WDL/DeepFM/xDeepFM/DLRM), optimizer, dim; train data-parallel
over every visible device with row-sharded embedding tables (the reference needs
Horovod + PS servers; here it is one SPMD program on a mesh).

Flag map to the reference benchmark:
  --model/--dim/--optimizer/--batch-size  same sweep axes
  --mesh            reference `--server` (PS sharding) -> MeshTrainer on all devices
  --cache N         reference `--cache` ("small tables dense-mirrored"): tables with
                    input_dim <= N become sparse_as_dense
  --prefetch        reference `--prefetch` (`pulling()` pipeline) -> device prefetch
  --persist ROOT    reference pmem AutoPersist -> async persist every --persist-steps
  --data/--synthetic  Criteo TSV file(s) or the synthetic Zipfian stream

CPU smoke:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/criteo_deepctr.py --mesh --steps 20 --synthetic
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import openembedding_tpu as embed  # noqa: E402
from openembedding_tpu.data import (CriteoBatcher, prefetch_to_device,  # noqa: E402
                                    read_criteo_tsv, synthetic_criteo)
from openembedding_tpu.model import Trainer  # noqa: E402
from openembedding_tpu import models as zoo  # noqa: E402
from openembedding_tpu.utils import metrics as M  # noqa: E402

OPTIMIZERS = {
    "adagrad": lambda lr: embed.Adagrad(learning_rate=lr),
    "adam": lambda lr: embed.Adam(learning_rate=lr),
    "ftrl": lambda lr: embed.Ftrl(learning_rate=lr),
    "sgd": lambda lr: embed.SGD(learning_rate=lr),
    "rmsprop": lambda lr: embed.RMSprop(learning_rate=lr),
}


from openembedding_tpu.utils.metrics import auc  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="deepfm", choices=sorted(zoo._FAMILIES))
    ap.add_argument("--dim", type=int, default=9)
    ap.add_argument("--optimizer", default="adagrad", choices=sorted(OPTIMIZERS))
    ap.add_argument("--learning-rate", type=float, default=0.05)
    ap.add_argument("--batch-size", type=int, default=4096,
                    help="global batch (split across devices with --mesh)")
    ap.add_argument("--vocabulary", type=int, default=1 << 22)
    ap.add_argument("--data", nargs="*", default=None, help="Criteo TSV file(s)")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", action="store_true",
                    help="MeshTrainer over all visible devices")
    ap.add_argument("--capacity-factor", type=float, default=0.0,
                    help="a2a exchange bucket headroom (0 = exact, never "
                         "drops; sizing rule in parallel/sharded.py)")
    ap.add_argument("--on-overflow", default="count",
                    choices=["count", "grow", "raise"],
                    help="bounded-bucket drop policy: watch counters, grow "
                         "capacity_factor adaptively (recompiles between "
                         "windows), or fail loud")
    ap.add_argument("--offload", type=int, default=0, metavar="SLOTS",
                    help="train the table bigger than HBM: keep a SLOTS-row "
                         "device cache, full table in host RAM "
                         "(storage='host_cached', tables/host_offload.py)")
    ap.add_argument("--cache", type=int, default=0,
                    help="sparse_as_dense for vocab <= N (reference --cache)")
    ap.add_argument("--scan", type=int, default=0, metavar="K",
                    help="fuse K steps per dispatch (jit_train_many / "
                         "offload_train_many): one union admission per window "
                         "for --offload tables; per-step logits (and so the "
                         "train AUC) are not collected in this mode")
    ap.add_argument("--prefetch", action="store_true")
    ap.add_argument("--persist", default="", help="async persist root dir")
    ap.add_argument("--persist-steps", type=int, default=50)
    ap.add_argument("--persist-incremental", action="store_true",
                    help="dirty-window persistence: deltas proportional to "
                         "touched rows between full bases "
                         "(persist.IncrementalPersister; single-device)")
    ap.add_argument("--save", default="")
    ap.add_argument("--load", default="")
    ap.add_argument("--export", default="", help="standalone serving export dir")
    ap.add_argument("--report-interval", type=float, default=0.0)
    ap.add_argument("--metrics-log", default="", metavar="PATH",
                    help="append each periodic report (and a final snapshot "
                         "at exit) as a timestamped JSONL record to PATH")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="capture a jax.profiler trace of the train loop "
                         "into DIR (view with xprof/tensorboard)")
    ap.add_argument("--flight-recorder", type=int, default=0, metavar="N",
                    help="resize the span/event flight recorder "
                         "(utils/trace.py; 0 keeps the default)")
    ap.add_argument("--trace-dump", default="", metavar="PATH",
                    help="at exit, dump the flight recorder (train-loop "
                         "spans, persist commits) as Chrome-trace JSON; "
                         "summarize with tools/trace_report.py")
    ap.add_argument("--skew-report", action="store_true",
                    help="feed per-table id batches into the heavy-hitter "
                         "sketches (utils/sketch.py, off the hot path) and "
                         "print the end-of-run hot-id + shard-balance "
                         "tables beside the trace dump")
    args = ap.parse_args()
    from openembedding_tpu.utils import compile_cache
    compile_cache.enable()
    if args.flight_recorder > 0:
        from openembedding_tpu.utils import trace as T
        T.configure(args.flight_recorder)

    if args.model == "two_tower":
        ap.error("two_tower has its own batch schema; use the zoo API directly")

    make = zoo._FAMILIES[args.model]
    kwargs = dict(vocabulary=args.vocabulary, dim=args.dim)
    if args.model == "lr":
        kwargs.pop("dim")
    model = make(**kwargs)
    if args.cache > 0 and args.vocabulary <= args.cache:
        import dataclasses
        spec = model.specs["categorical"]
        model.specs["categorical"] = dataclasses.replace(
            spec, sparse_as_dense=True)
        print(f"cache mode: categorical ({args.vocabulary}) is dense-mirrored")
    if args.offload > 0:
        if args.cache > 0:
            ap.error("--cache (dense-mirrored) and --offload (host-cached) "
                     "are mutually exclusive")
        import dataclasses
        spec = model.specs["categorical"]
        model.specs["categorical"] = dataclasses.replace(
            spec, input_dim=-1, capacity=args.offload, storage="host_cached",
            sparse_as_dense=False)
        print(f"offload mode: {args.offload}-row device cache, "
              "full table in host RAM")

    opt = OPTIMIZERS[args.optimizer](args.learning_rate)
    if args.mesh:
        from openembedding_tpu.parallel import MeshTrainer
        trainer = MeshTrainer(model, opt,
                              capacity_factor=args.capacity_factor,
                              on_overflow=args.on_overflow)
        print(f"mesh: {trainer.num_shards} devices, tables row-sharded, "
              f"batch data-parallel")
    else:
        trainer = Trainer(model, opt)
    if args.skew_report:
        # per-table id batches ride offload_prepare into the sketches
        trainer.enable_skew_monitor()

    if args.data:
        rows = read_criteo_tsv(args.data, args.batch_size,
                               id_space=args.vocabulary, drop_remainder=True,
                               repeat=True)
        batches = iter(CriteoBatcher(rows, args.batch_size))
    else:
        batches = synthetic_criteo(args.batch_size, id_space=args.vocabulary,
                                   ids_dtype=np.int32)
    if args.prefetch:
        batches = prefetch_to_device(batches)

    first = next(batches)
    state = trainer.init(first)
    if args.load:
        state = trainer.load(state, args.load)
        print(f"resumed at step {int(state.step)}")
    if args.mesh:
        step = trainer.jit_train_step(first, state)
    else:
        step = trainer.jit_train_step()

    persister = None
    if args.persist:
        cls = (embed.IncrementalPersister if args.persist_incremental
               else embed.AsyncPersister)
        persister = cls(
            trainer, model, args.persist,
            policy=embed.PersistPolicy(every_steps=args.persist_steps))

    reporter = M.PeriodicReporter(args.report_interval,
                                  jsonl_path=args.metrics_log or None).start()
    all_labels, all_scores = [], []

    def report_overflow():
        # the static-capacity divergence must be *managed*, not just
        # counted: surface dropped ids as they happen (see also the
        # pull/push_overflow step stats on the mesh path).
        # table_overflow includes counts banked across offload flushes.
        for name in state.tables:
            ov = trainer.table_overflow(state, name)
            if ov > 0:
                print(f"  WARNING: {name}: {ov} ids have overflowed the "
                      "hash capacity (rows dropped) — raise capacity or "
                      "capacity_factor")

    import atexit
    import contextlib
    profile_stack = contextlib.ExitStack()
    if args.profile:
        import jax as _jax
        profile_stack.enter_context(_jax.profiler.trace(args.profile))
        # close() is idempotent: atexit finalizes the trace even when the
        # loop dies mid-run — the run being profiled is often the broken one
        atexit.register(profile_stack.close)
        print(f"profiling -> {args.profile}")

    t0 = time.perf_counter()
    if args.scan > 1:
        # scan-fused windows: K steps per device dispatch; host_cached tables
        # get one union-of-K admission per window (model.offload_train_many).
        # Per-step logits are not collected in this mode (no train AUC).
        import jax as _jax
        done = 0
        window = [first]
        while done < args.steps:
            while len(window) < min(args.scan, args.steps - done):
                window.append(next(batches))
            stacked = _jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *window)
            with M.vtimer("train", "window"):
                state, m = trainer.offload_train_many(state, stacked)
            done += len(window)
            window = []
            m = dict(m, loss=np.asarray(m["loss"])[-1])
            if persister is not None:
                persister.maybe_persist(state, batch=stacked)
            print(f"step {done}: loss {float(m['loss']):.4f}")
            report_overflow()
            if trainer.check_overflow(m):
                print(f"  exchange capacity grew to "
                      f"f={trainer.capacity_factor} (recompiling)")
        trained = done
        mode = f" (scan K={args.scan})"
    else:
        state = trainer.offload_prepare(state, first)
        state, m = step(state, first)
        if persister is not None:
            persister.maybe_persist(state, batch=first)
        pending_overflow = 0  # drops accumulate across steps between checks
        for i in range(1, args.steps):
            batch = next(batches)
            with M.vtimer("train", "step"):
                state = trainer.offload_prepare(state, batch)
                state, m = step(state, batch)
            all_labels.append(np.asarray(batch["label"]))
            all_scores.append(np.asarray(m["logits"]).reshape(-1))
            M.record_step_stats({k: v for k, v in m.get("stats", {}).items()})
            pending_overflow += trainer.overflow_count(m)
            if persister is not None:
                persister.maybe_persist(state, batch=batch)
            if i % 20 == 0:
                print(f"step {i}: loss {float(m['loss']):.4f}")
                report_overflow()
                # every step's drops since the last check count — a policy
                # that only sampled the 20th step would miss the other 19
                if trainer.check_overflow({"overflow": pending_overflow}):
                    print(f"  exchange capacity grew to "
                          f"f={trainer.capacity_factor} (recompiling)")
                    step = trainer.jit_train_step(batch, state)
                pending_overflow = 0
        trained = args.steps
        mode = ""
    loss = float(m["loss"])  # fences the device work
    dt = time.perf_counter() - t0
    profile_stack.close()
    reporter.stop()
    if persister is not None:
        persister.close()

    examples = trained * args.batch_size
    print(f"trained {trained} steps{mode}, loss {loss:.4f}, "
          f"{examples / dt:,.0f} examples/s "
          f"({examples / dt / max(1, getattr(trainer, 'num_shards', 1)):,.0f}"
          f"/chip)")
    if all_labels:
        print(f"train AUC {auc(np.concatenate(all_labels), np.concatenate(all_scores)):.4f}")
    print(M.report_table())
    if args.skew_report:
        from openembedding_tpu.utils import sketch
        sketch.MONITOR.drain()  # fold every enqueued batch before printing
        print("== workload skew: hot ids (Space-Saving top-K) ==")
        print(sketch.MONITOR.render_text())
        print("== workload skew: shard balance (exchange load) ==")
        print(sketch.shard_balance_text())
    if args.trace_dump:
        from openembedding_tpu.utils import trace as T
        print(f"trace dump -> {T.dump_chrome(args.trace_dump)}")

    if args.save:
        trainer.save(state, args.save)
        print(f"checkpoint -> {args.save}")
    if args.export:
        from openembedding_tpu.export import export_standalone
        export_standalone(state, model, args.export,
                          num_shards=getattr(trainer, "num_shards", 1),
                          offload_stores=trainer.offload_store_snapshots(state))
        print(f"standalone serving export -> {args.export}")


if __name__ == "__main__":
    main()
