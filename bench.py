"""Headline benchmark suite: DeepFM on synthetic Criteo, examples/sec/chip.

Mirrors the reference's headline number (`documents/en/benchmark.md:41-56`): DeepFM,
Adagrad, batch 4096/chip, Criteo-like Zipfian ids over a 2^24-row table. The reference
reports 692k examples/s on 8x Tesla T4 + 1 remote PS = 86.5k examples/s/chip, which is
the `vs_baseline` denominator. The reference sweep also covers dim 64
(`documents/en/benchmark.md:6-16`) and the north-star metric list includes
embedding-pull p50 latency (BASELINE.md), so both are measured here too, plus the
MeshTrainer path on a 1-device mesh (captures the dedup/bucket/all_to_all exchange
overhead that the single-device Trainer path does not pay).

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline",            # primary: deepfm dim-9 ex/s/chip
   "extra": {case: {...}},                              # secondary case results
   "errors": {case: "..."},                             # failed/skipped cases
   "stage": "..."}                                      # with errors: last stage reached
`extra` also names where it ran: platform, device_kind, device_count.

One process: `python bench.py` runs `main()` in the process that owns the chip
(a chip belongs to one process at a time; nothing here starts another). It
exits non-zero, before building any model, when JAX's first device is not a
TPU — unless the caller itself set `JAX_PLATFORMS=cpu` (`make bench-smoke`: a
harness check at tiny sizes whose JSON says `"platform": "cpu"`). It exits
non-zero when ANY requested case failed or was skipped; the JSON line still
carries every case that did finish. Per-stage progress goes to stderr; a stage
that outlives its deadline (`Watchdog`) or a SIGTERM/SIGINT flushes the partial
JSON and exits non-zero. A per-run wall-clock budget (OETPU_BENCH_BUDGET_S,
default 540s) skips remaining SECONDARY cases — a skip counts as a failure.
XLA programs are kept in the persistent compile cache
(`openembedding_tpu/utils/compile_cache.py`).

Measurement: K train steps are fused into one compiled program with lax.scan
(`Trainer.jit_train_many`) over device-staged batches, so the number is device
throughput, not host dispatch latency — the same way production input pipelines
drive TPUs.

Env knobs: OETPU_BENCH_CASES=dim9[,dim64][,mesh1][,mesh1f][,pull][,wire][,wire_inband][,sync][,skew][,hot][,placement][,zero][,zero_sparse][,offload_pipe][,pipeline][,ingest][,health][,obs2] (default: all),
OETPU_BENCH_BUDGET_S (default 540), OETPU_BENCH_SCAN_STEPS / _REPEATS (smoke runs).
"""

import json
import os
import signal
import sys
import threading
import time

import numpy as np

BATCH = int(os.environ.get("OETPU_BENCH_BATCH", "4096"))
VOCAB = int(os.environ.get("OETPU_BENCH_VOCAB", str(1 << 24)))
SCAN_STEPS = int(os.environ.get("OETPU_BENCH_SCAN_STEPS", "50"))
REPEATS = int(os.environ.get("OETPU_BENCH_REPEATS", "3"))
BUDGET_S = float(os.environ.get("OETPU_BENCH_BUDGET_S", "540"))
BASELINE_PER_CHIP = 692_000 / 8  # reference Criteo-1TB DeepFM dim 9, per chip
PULL_SCAN = 64  # pulls fused per dispatch for the p50 case

T0 = time.time()
RESULT = {"metric": "deepfm_dim9_examples_per_sec_per_chip", "value": None,
          "unit": "examples/s/chip", "vs_baseline": None}
EXTRA = {}
ERRORS = {}
_STAGE = ["boot"]
_EMITTED = [False]


def log(msg):
    print(f"[bench t={time.time() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def emit():
    """Print the single stdout JSON line (idempotent) and return the exit
    code: non-zero when any requested case failed or was skipped."""
    if not _EMITTED[0]:
        _EMITTED[0] = True
        out = dict(RESULT)
        if EXTRA:
            out["extra"] = EXTRA
        if ERRORS:
            out["errors"] = ERRORS
            out["stage"] = _STAGE[0]
        print(json.dumps(out), flush=True)
    return 1 if ERRORS else 0


class Watchdog:
    """Per-stage deadline: a compile or collective that never returns blocks the
    main thread in C++ (uninterruptible by signals), so on expiry the partial
    result is flushed and the process hard-exits NON-ZERO."""

    def __init__(self):
        self._deadline = None
        self._lock = threading.Lock()
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def stage(self, name, timeout_s):
        _STAGE[0] = name
        with self._lock:
            self._deadline = time.time() + timeout_s
        log(f"stage={name} (timeout {timeout_s:.0f}s)")

    def clear(self):
        with self._lock:
            self._deadline = None

    def _run(self):
        while True:
            time.sleep(1.0)
            with self._lock:
                d = self._deadline
            if d is not None and time.time() > d:
                log(f"WATCHDOG: stage {_STAGE[0]!r} exceeded its deadline")
                ERRORS.setdefault(_STAGE[0].split(":")[0],
                                  f"watchdog timeout in {_STAGE[0]}")
                rc = emit()
                sys.stderr.flush()
                os._exit(rc)


WD = Watchdog()


def _on_signal(signum, frame):
    log(f"received signal {signum}")
    ERRORS.setdefault(_STAGE[0].split(":")[0], f"killed by signal {signum}")
    os._exit(emit())


signal.signal(signal.SIGTERM, _on_signal)
signal.signal(signal.SIGINT, _on_signal)


def run_case(name, fn):
    try:
        WD.stage(f"{name}:start", 60)
        out = fn()
    except Exception as e:  # noqa: BLE001 — recorded; the run exits non-zero
        ERRORS[name] = f"{type(e).__name__}: {e}"[:500]
        log(f"case {name} FAILED: {ERRORS[name]}")
        return None
    finally:
        WD.clear()
    EXTRA[name] = out
    log(f"case {name} OK: {out}")
    return out


def _stacked_batches(dim_unused, steps, ids_dtype=np.int32, seed=7,
                     id_space=None):
    import jax
    from openembedding_tpu.data import synthetic_criteo
    batches = list(synthetic_criteo(BATCH, id_space=id_space or VOCAB,
                                    steps=steps, seed=seed,
                                    ids_dtype=ids_dtype))
    stacked = jax.device_put(jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *batches))
    return batches, stacked


def _measure_many(name, many, state, stacked, extra_out=None,
                  compile_s=420):
    WD.stage(f"{name}:compile", compile_s)
    state, metrics = many(state, stacked)
    loss = float(metrics["loss"][-1])  # fence: forces the whole scan
    log(f"{name}: compile+warmup done, loss={loss:.4f}")
    WD.stage(f"{name}:measure", 240)
    best = None
    overflow = 0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        state, metrics = many(state, stacked)
        loss = float(metrics["loss"][-1])
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
        overflow += int(np.asarray(metrics.get("overflow", 0)))
    assert np.isfinite(loss), f"non-finite loss {loss}"
    if extra_out is not None:
        # bounded-bucket drops during the measured windows (mesh1f's f=1.0
        # is the production capacity config — a silent drop count would
        # make its throughput number quietly incomparable)
        extra_out["overflow_measured_steps"] = overflow
    return BATCH * SCAN_STEPS / best


def case_trainer(dim):
    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm

    name = f"dim{dim}"
    WD.stage(f"{name}:init", 240)
    # dim 64 runs a 2^23-row table on one chip: at 2^24 the program needs
    # ~17.1 G HBM (> 15.75 G v5e) — weights+accum are 2 x 4.06 G and XLA's
    # gather lowering for 32 < width < 128 materializes a 128-lane-padded
    # temp copy of the table (2.0x, measured via compiled.memory_analysis();
    # PERF.md "dim-64 single-chip HBM budget"). The reference never fits
    # this table on one device either (it lives on a 175 GB remote PS,
    # documents/en/benchmark.md:41-56); multi-chip meshes shard it 1/S.
    vocab = min(VOCAB, 1 << 23) if dim >= 64 else VOCAB
    model = make_deepfm(vocabulary=vocab, dim=dim)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    # int32 ids: keep x64 off on TPU (VOCAB < 2^31)
    batches, stacked = _stacked_batches(dim, SCAN_STEPS, id_space=vocab)
    state = trainer.init(batches[0])
    packed = bool(trainer._packed_layouts(state))
    eps = _measure_many(name, trainer.jit_train_many(), state, stacked)
    return {"examples_per_sec_per_chip": round(eps, 1),
            "vs_baseline_dim9": round(eps / BASELINE_PER_CHIP, 3),
            "vocab": vocab, "packed": packed}


def case_mesh1(capacity_factor=0.0, name="mesh1"):
    """MeshTrainer on a 1-device mesh: same workload as dim9, but through the
    sharded protocol entry points — the honest number for the multi-chip
    path's per-chip overhead. NOTE (round 4): at S=1 `make_plan` specializes
    to identity routing, so the bucket scatters and collectives are gone and
    `capacity_factor` has no effect (mesh1 == mesh1f by construction; both
    cases are kept so a regression that reintroduces S-invariant overhead is
    visible against dim9). Bounded buckets engage from S >= 2."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    WD.stage(f"{name}:init", 240)
    model = make_deepfm(vocabulary=VOCAB, dim=9)
    mesh = make_mesh(jax.devices()[:1])
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh,
                          capacity_factor=capacity_factor)
    batches, stacked = _stacked_batches(9, SCAN_STEPS)
    state = trainer.init(batches[0])
    many = trainer.jit_train_many(stacked, state)
    extra = {}
    # the sorted dedup+route pipeline is a much bigger HLO than the
    # single-device scan; give the FIRST compile more rope
    eps = _measure_many(name, many, state, stacked, extra_out=extra,
                        compile_s=700)
    return {"examples_per_sec_per_chip": round(eps, 1),
            "vs_baseline_dim9": round(eps / BASELINE_PER_CHIP, 3),
            "capacity_factor": capacity_factor,
            # at S=1 the exchange specializes away (0 collectives, 0 wire
            # bytes) — recorded so multi-chip captures are comparable
            "wire_cost": trainer.last_wire_cost, **extra}


def case_wire():
    """Wire-codec overhead on-device: jitted encode+decode round-trip of a
    (26*4096, 64) f32 row payload for bf16 and int8 — the quantize compute
    the fused exchange adds around its all_to_alls. The BYTE savings need
    S >= 2 and are modeled + measured on the CPU mesh in
    tools/wire_microbench.py; this case bounds the on-chip compute cost."""
    import jax
    from openembedding_tpu.ops import wire as wire_mod

    WD.stage("wire:init", 120)
    rng = np.random.default_rng(0)
    rows = jax.device_put(
        rng.standard_normal((26 * 4096, 64)).astype(np.float32))
    out = {}
    for fmt in ("bf16", "int8"):
        fn = jax.jit(lambda x, fmt=fmt: wire_mod.decode_rows(
            wire_mod.encode_rows(x, fmt), x.shape[1], fmt))
        WD.stage(f"wire:{fmt}", 180)
        jax.block_until_ready(fn(rows))
        times = []
        for _ in range(max(REPEATS, 5)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(rows))
            times.append(time.perf_counter() - t0)
        best = min(times)
        out[f"{fmt}_roundtrip_ms"] = round(best * 1e3, 3)
        # bytes touched: read f32 + write f32 (the wire array in between)
        out[f"{fmt}_gbps"] = round(rows.size * 4 * 2 / best / 1e9, 1)
    return out


def case_wire_inband():
    """Round-13 in-collective codec on-device: jitted pack_inband/unpack_inband
    round-trip of a (26*4096, 64) f32 payload — dim 64 = 2 scale blocks, so
    the in-band scale lanes and per-block int8 quantization do real work —
    for bf16 and int8, int8 additionally with stochastic rounding (the
    training-push mode). The EF columns price the owner-side error-feedback
    serve (encode q(w+ef), ef <- (w+ef) - deq(q)) against the plain int8
    encode: `ef_overhead_ms` is what the residual update adds per serve.
    Byte savings need S >= 2 and are HLO-measured in tools/wire_microbench.py
    and pinned by the oelint hlo-budget pass; this case bounds compute."""
    import jax
    from openembedding_tpu.ops import wire as wire_mod

    WD.stage("wire_inband:init", 120)
    rng = np.random.default_rng(0)
    dim = 64
    rows = jax.device_put(
        rng.standard_normal((26 * 4096, dim)).astype(np.float32))
    ef = jax.device_put(
        (rng.standard_normal((26 * 4096, dim)) * 1e-3).astype(np.float32))
    out = {"dim": dim, "scale_blocks": int(wire_mod.scale_blocks(dim))}

    def timed(label, fn, *args):
        jfn = jax.jit(fn)
        WD.stage(f"wire_inband:{label}", 180)
        jax.block_until_ready(jfn(*args))
        times = []
        for _ in range(max(REPEATS, 5)):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*args))
            times.append(time.perf_counter() - t0)
        best = min(times)
        out[f"{label}_ms"] = round(best * 1e3, 3)
        return best

    for fmt in ("bf16", "int8"):
        best = timed(f"{fmt}_inband", lambda x, fmt=fmt: wire_mod.unpack_inband(
            wire_mod.pack_inband(x, fmt), dim, fmt), rows)
        # bytes touched: read f32 + write f32 (the wire array in between)
        out[f"{fmt}_inband_gbps"] = round(rows.size * 4 * 2 / best / 1e9, 1)
    timed("int8_sr_inband", lambda x: wire_mod.unpack_inband(
        wire_mod.pack_inband(x, "int8", stochastic=True), dim, "int8"), rows)

    def ef_serve(w, e):
        wire = wire_mod.pack_inband(w + e, "int8")
        return wire, (w + e) - wire_mod.unpack_inband(wire, dim, "int8")

    plain = timed("int8_encode", lambda x: wire_mod.pack_inband(x, "int8"),
                  rows)
    withef = timed("int8_ef_serve", ef_serve, rows, ef)
    out["ef_overhead_ms"] = round((withef - plain) * 1e3, 3)
    out["ef_overhead_x"] = round(withef / max(plain, 1e-9), 3)
    return out


def case_sync():
    """Online-sync delta pipeline end to end, in-process HTTP and all: a
    2^20-row dim-16 table trains 3 persisted deltas of a 4096x26 Zipfian
    batch each; a subscriber-backed ModelManager then follows the published
    feed per wire format. Reported: per-delta sync latency (fetch + decode +
    apply + RCU swap), applied rows/s, and bytes/delta — the knobs the
    PERF.md sync wire-cost stanza models. Mostly host-side work by design
    (the apply path's device cost is one scatter per table), so CPU numbers
    are already representative; the chip battery entry pins that claim."""
    import shutil
    import tempfile
    import threading

    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.persist import IncrementalPersister, PersistPolicy
    from openembedding_tpu.export import export_standalone
    from openembedding_tpu.serving import ModelManager, ModelRegistry, make_server
    from openembedding_tpu.sync import SyncSubscriber
    from openembedding_tpu.utils import metrics as metrics_mod

    WD.stage("sync:init", 240)
    vocab, dim, steps = 1 << 20, 16, 4
    model = make_deepfm(vocabulary=vocab, dim=dim)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    batches, _ = _stacked_batches(dim, steps, id_space=vocab)
    state = trainer.init(batches[0])
    step = trainer.jit_train_step()
    work = tempfile.mkdtemp(prefix="oetpu_bench_sync_")
    out = {}
    try:
        root = os.path.join(work, "persist")
        WD.stage("sync:train_persist", 300)
        with IncrementalPersister(trainer, model, root, window=2,
                                  policy=PersistPolicy(every_steps=1),
                                  full_every=100) as p:
            state, _m = step(state, batches[0])
            p.maybe_persist(state, batch=batches[0])
            p.wait()
            export_dir = os.path.join(work, "export")
            export_standalone(state, model, export_dir, model_sign="bench")
            touched = 0
            for b in batches[1:]:
                state, _m = step(state, b)
                ids = np.unique(np.asarray(b["sparse"]["categorical"]))
                touched += int(ids.size)
                p.maybe_persist(state, batch=b)
            p.wait()
        pub = make_server(os.path.join(work, "reg"), publish={"bench": root})
        threading.Thread(target=pub.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{pub.server_address[1]}"
        n_deltas = steps - 1
        for fmt in ("fp32", "bf16", "int8"):
            WD.stage(f"sync:{fmt}", 240)
            mgr = ModelManager(ModelRegistry(os.path.join(work, f"r_{fmt}")))
            mgr.load_model("bench", export_dir)
            sub = SyncSubscriber(mgr, "bench", url, wire=fmt)
            b0 = metrics_mod.Accumulator.get("sync.bytes_fetched").value()
            t0 = time.perf_counter()
            applied = sub.poll()
            dt = time.perf_counter() - t0
            assert applied == n_deltas, (applied, sub.last_error)
            bytes_fetched = (metrics_mod.Accumulator.get(
                "sync.bytes_fetched").value() - b0)
            out[f"{fmt}_ms_per_delta"] = round(dt * 1e3 / n_deltas, 2)
            out[f"{fmt}_rows_per_sec"] = round(touched / dt, 1)
            out[f"{fmt}_bytes_per_delta"] = int(bytes_fetched / n_deltas)
        out["deltas"] = n_deltas
        out["touched_rows_total"] = touched
        out["vs_fp32_bytes"] = round(
            out["fp32_bytes_per_delta"] / out["bf16_bytes_per_delta"], 2)
        pub.shutdown()
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


def case_skew():
    """Workload-skew telemetry overhead (round 9): (a) the per-shard load
    accounting inside the jitted exchange (`sharded.exchange_load_stats`,
    always-on by default) measured as shard_stats=True vs False on the
    mesh1 workload, and (b) the host-side Space-Saving + count-min sketch
    (`utils/sketch.py`) in ms per 4096x26 Zipfian batch — the acceptance
    bound is combined overhead <= 5% of step time at the defaults."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.utils.sketch import SpaceSaving

    WD.stage("skew:init", 240)
    batches, stacked = _stacked_batches(9, SCAN_STEPS)
    eps = {}
    for flag in (True, False):
        model = make_deepfm(vocabulary=VOCAB, dim=9)
        trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05),
                              mesh=make_mesh(jax.devices()[:1]),
                              shard_stats=flag)
        state = trainer.init(batches[0])
        many = trainer.jit_train_many(stacked, state)
        # same compile allowance as mesh1 (the fused-exchange HLO)
        eps[flag] = _measure_many(f"skew:stats_{'on' if flag else 'off'}",
                                  many, state, stacked, compile_s=700)
    out = {
        "stats_on_examples_per_sec": round(eps[True], 1),
        "stats_off_examples_per_sec": round(eps[False], 1),
        # positive = the load accounting costs throughput
        "stats_overhead_pct": round((eps[False] / eps[True] - 1.0) * 100, 2),
    }
    WD.stage("skew:sketch", 180)
    sk = SpaceSaving(k=64)
    id_batches = [np.asarray(b["sparse"]["categorical"]) for b in batches]
    sk.update(id_batches[0])  # warm the numpy paths
    t0 = time.perf_counter()
    for ids in id_batches:
        sk.update(ids)
    sketch_ms = (time.perf_counter() - t0) * 1e3 / len(id_batches)
    step_ms = BATCH / eps[True] * 1e3
    out["sketch_ms_per_batch"] = round(sketch_ms, 3)
    # the monitor enqueues and updates on a worker thread, so this is the
    # WORKER's cost; the step only pays the queue put. Reported against the
    # step anyway as the worst (synchronous) case.
    out["sketch_pct_of_step"] = round(sketch_ms / step_ms * 100, 2)
    out["total_overhead_pct"] = round(
        out["stats_overhead_pct"] + out["sketch_pct_of_step"], 2)
    return out


def case_health():
    """Numerics-sentinel + measured-step-timing overhead (round 16): the
    PER-STEP train loop — jit_train_step + record_step_stats each step, the
    examples' convention — with the in-jit health sentinel and the sampled
    step-time watch ON (sentinel=True, measure_every=8) vs OFF, dim9
    single-chip workload. The sentinel's stat reductions ride the step's
    existing stats dict, so the acceptance bound is overhead <= 2%."""
    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm

    WD.stage("health:init", 240)
    batches, _ = _stacked_batches(9, SCAN_STEPS)
    eps = {}
    for flag in (True, False):
        tag = "on" if flag else "off"
        model = make_deepfm(vocabulary=VOCAB, dim=9)
        trainer = Trainer(model, embed.Adagrad(learning_rate=0.05),
                          sentinel=flag, measure_every=8 if flag else 0)
        state = trainer.init(batches[0])
        step = trainer.jit_train_step()
        WD.stage(f"health:{tag}:compile", 420)
        state, mets = step(state, batches[0])
        health = trainer.record_step_stats(mets)
        assert not health.get("nonfinite"), health
        WD.stage(f"health:{tag}:measure", 240)
        best = None
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            for b in batches:
                state, mets = step(state, b)
                trainer.record_step_stats(mets)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        eps[flag] = BATCH * len(batches) / best
    from openembedding_tpu.utils import metrics as M
    with M._LOCK:
        acc = M._REGISTRY.get("trainer.step_ms")
    return {
        "sentinel_on_examples_per_sec": round(eps[True], 1),
        "sentinel_off_examples_per_sec": round(eps[False], 1),
        # positive = the sentinel + step watch cost throughput
        "sentinel_overhead_pct": round((eps[False] / eps[True] - 1.0) * 100,
                                       2),
        "step_ms_samples": int(acc.hist_snapshot()[2]) if acc else 0,
    }


def case_obs2():
    """Flight-data layer overhead (round 21): the PER-STEP mesh train loop
    with the full observability stack ON — capsules armed, metric history
    sampled + the jsonl reporter ticked + the memwatch ledger re-published
    every 8 steps (a far tighter cadence than production's PeriodicReporter
    interval) — vs the stack OFF. The history sample and memory publish are
    host-side bookkeeping over the registry and array METADATA (no device
    sync), so the acceptance bound is overhead <= 2%."""
    import tempfile

    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.utils import capsule, history
    from openembedding_tpu.utils import metrics as M

    WD.stage("obs2:init", 240)
    batches, _ = _stacked_batches(9, SCAN_STEPS)
    eps = {}
    n_series = 0
    for flag in (True, False):
        tag = "on" if flag else "off"
        with M._LOCK:
            M._REGISTRY.clear()
        history.HISTORY.clear()
        model = make_deepfm(vocabulary=VOCAB, dim=9)
        trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05),
                              mesh=make_mesh(jax.devices()[:1]))
        state = trainer.init(batches[0])
        step = trainer.jit_train_step(batches[0], state)
        WD.stage(f"obs2:{tag}:compile", 420)
        state, mets = step(state, batches[0])
        trainer.record_step_stats(mets)
        rep = None
        if flag:
            obs_dir = tempfile.mkdtemp(prefix="benchobs2")
            capsule.configure(obs_dir)
            rep = M.PeriodicReporter(
                interval=3600, sink=lambda s: None,
                jsonl_path=os.path.join(obs_dir, "metrics.jsonl"),
                jsonl_max_bytes=1 << 20, jsonl_keep=2)
            trainer.publish_memory(state)  # warm the ledger paths
            rep._tick()
        WD.stage(f"obs2:{tag}:measure", 240)
        best = None
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            for i, b in enumerate(batches):
                state, mets = step(state, b)
                trainer.record_step_stats(mets)
                if flag and i % 8 == 0:
                    rep._tick()
                    trainer.publish_memory(state)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        eps[flag] = BATCH * len(batches) / best
        if flag:
            n_series = len(history.HISTORY.names())
        capsule.configure(None)
    return {
        "obs_on_examples_per_sec": round(eps[True], 1),
        "obs_off_examples_per_sec": round(eps[False], 1),
        # positive = the flight-data layer costs throughput
        "obs_overhead_pct": round((eps[False] / eps[True] - 1.0) * 100, 2),
        "history_series": n_series,
    }


def case_causality():
    """Fleet-causality layer overhead (round 22): the PER-STEP mesh train
    loop with the cross-process propagation stack ON — every step opens a
    traced request from an injected+extracted `X-OETPU-Trace` header pair,
    runs under a span, folds a hop decomposition into a lineage book, and
    closes the chain with an idempotent note_serve — vs the stack OFF.
    Everything added is host-side contextvar/dict bookkeeping (no device
    sync), so the acceptance bound is overhead <= 2%."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.sync import lineage
    from openembedding_tpu.utils import metrics as M
    from openembedding_tpu.utils import trace

    WD.stage("causality:init", 240)
    batches, _ = _stacked_batches(9, SCAN_STEPS)
    eps = {}
    best = {}
    for flag in (True, False):
        tag = "on" if flag else "off"
        with M._LOCK:
            M._REGISTRY.clear()
        book = lineage.LineageBook(capacity=64)
        model = make_deepfm(vocabulary=VOCAB, dim=9)
        trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05),
                              mesh=make_mesh(jax.devices()[:1]))
        state = trainer.init(batches[0])
        step = trainer.jit_train_step(batches[0], state)
        WD.stage(f"causality:{tag}:compile", 420)
        state, mets = step(state, batches[0])
        trainer.record_step_stats(mets)
        WD.stage(f"causality:{tag}:measure", 240)
        best[flag] = None
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            for i, b in enumerate(batches):
                if flag:
                    with trace.request():  # caller side: stamp the headers
                        hdrs = trace.inject_headers({})
                    ctx = trace.extract_context(hdrs)
                    with trace.request(ctx.trace_id,
                                       remote_parent=ctx.parent_span):
                        with trace.span("sync", "bench_step", step=i):
                            state, mets = step(state, b)
                        trainer.record_step_stats(mets)
                        now = time.time()
                        book.record("bench", i, birth=now - 0.1,
                                    seen=now - 0.05, fetched=now - 0.03,
                                    applied=now - 0.02, swapped=now - 0.01,
                                    hops={"fetch": 20.0}, offset_s=0.0)
                        book.note_serve("bench", i, now=now)
                else:
                    state, mets = step(state, b)
                    trainer.record_step_stats(mets)
            dt = time.perf_counter() - t0
            best[flag] = dt if best[flag] is None else min(best[flag], dt)
        eps[flag] = BATCH * len(batches) / best[flag]
    per_step_us = (best[True] - best[False]) / len(batches) * 1e6
    return {
        "causality_on_examples_per_sec": round(eps[True], 1),
        "causality_off_examples_per_sec": round(eps[False], 1),
        # positive = the propagation + lineage bookkeeping costs throughput
        "causality_overhead_pct": round((eps[False] / eps[True] - 1.0) * 100,
                                        2),
        "per_step_overhead_us": round(per_step_us, 1),
    }


def case_hot():
    """Skew-aware hot-row replication (round 10): a TRUNCATED Zipf(1.05) id
    stream (item-popularity ids over a bounded catalog — no per-field
    hashing, so the head is genuinely hot and owner shards genuinely skew)
    through the sharded exchange, replicated hot cache on vs off, plus a
    uniform-id control. Needs S >= 2 shards for the byte/imbalance wins, so
    the battery entry runs it on the 8-virtual-device CPU mesh (like
    tools/wire_microbench.py).

    Methodology: every config runs in exact mode (capacity_factor=0 — drops
    impossible) and the tuned zero-drop bucket capacity is READ OFF the
    measured `bucket_fill` stat (max (src,dst) occupancy over the stream,
    +10% headroom) — hot rows leaving the buckets is what shrinks it.
    `exchange_bytes_at_fit_capacity` prices the 3-a2a wire at that capacity
    (`ops/wire.exchange_cost`, production bf16 wire); the acceptance ratio
    `payload_reduction_pct` compares it cache-on vs cache-off. The hot set's
    own dense psum is a SEPARATE, bandwidth-friendly collective class
    (SparCML's point) and is reported beside it as
    `replicate_bytes_per_step`, never hidden inside the a2a number."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.ops import wire as wire_mod
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    WD.stage("hot:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    mesh = make_mesh(devs[:S])
    HOT = int(os.environ.get("OETPU_BENCH_HOT_ROWS", "1024"))
    alpha = float(os.environ.get("OETPU_BENCH_HOT_ALPHA", "1.05"))
    vocab = int(os.environ.get("OETPU_BENCH_HOT_VOCAB", str(1 << 13)))
    cpu = devs[0].platform == "cpu"
    batch = min(BATCH, 2048) if cpu else BATCH
    steps = min(SCAN_STEPS, 6) if cpu else min(SCAN_STEPS, 16)
    fields = 26

    def stream(uniform, seed=11):
        rng = np.random.default_rng(seed)
        bs = []
        a = alpha - 1.0
        norm = 1.0 - float(vocab) ** (-a)
        for _ in range(steps):
            if uniform:
                ids = rng.integers(0, vocab, (batch, fields))
            else:
                # inverse-CDF truncated Zipf(alpha) over [1, vocab]
                u = rng.random((batch, fields))
                ids = np.floor((1.0 - u * norm) ** (-1.0 / a)).astype(
                    np.int64) - 1
                ids = np.clip(ids, 0, vocab - 1)
            bs.append({
                "sparse": {"categorical": ids.astype(np.int32)},
                "dense": rng.normal(size=(batch, 13)).astype(np.float32),
                "label": rng.integers(0, 2, (batch,)).astype(np.float32)})
        return bs

    def top_ids(bs):
        ids = np.concatenate([b["sparse"]["categorical"].reshape(-1)
                              for b in bs])
        uniq, cnt = np.unique(ids, return_counts=True)
        return uniq[np.argsort(-cnt)][:HOT].astype(np.int64)

    def one_config(name, hot_rows, bs):
        WD.stage(f"hot:{name}", 420)
        model = make_deepfm(vocabulary=vocab, dim=9)
        tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh,
                         capacity_factor=0.0, hot_rows=hot_rows)
        state = tr.init(bs[0])
        if hot_rows and tr.hot_enabled:
            state = tr.refresh_hot_rows(
                state, hot_ids={"categorical": top_ids(bs)})
        step = tr.jit_train_step(bs[0], state)
        out, times, max_fill = {}, [], 0.0
        cap_exact = bs[0]["sparse"]["categorical"].size // S
        for i, b in enumerate(bs):
            t0 = time.perf_counter()
            state, m = step(state, b)
            float(m["loss"])
            if i:  # first dispatch is compile+warm
                times.append(time.perf_counter() - t0)
            stats = {k: np.asarray(v) for k, v in
                     jax.device_get(m["stats"]).items()}
            fill = stats.get("categorical/bucket_fill")
            if fill is not None:
                max_fill = max(max_fill, float(fill.max()))
            if i == 0:
                pos = stats.get("categorical/shard_positions")
                if pos is not None and pos.mean() > 0:
                    out["shard_imbalance"] = round(
                        float(pos.max() / pos.mean()), 3)
                if "categorical/hot_hits" in stats:
                    out["hit_ratio"] = round(
                        float(stats["categorical/hot_hits"])
                        / float(stats["categorical/pull_indices"]), 4)
                    out["bytes_saved_per_step"] = int(
                        stats["categorical/hot_bytes_saved"])
        out["ms_per_step"] = round(min(times) * 1e3, 2) if times else None
        cost = dict(tr.last_wire_cost or {})
        out["replicate_bytes_per_step"] = int(
            cost.get("hot_replicate_bytes", 0))
        if S > 1 and max_fill > 0:
            # zero-drop bucket capacity measured off the exchange's own
            # occupancy telemetry (+10% headroom), and the 3-a2a wire cost
            # at it — what a tuned capacity_factor would actually ship
            fit_cap = int(max_fill * cap_exact * 1.1) + 1
            out["fit_bucket_capacity"] = fit_cap
            fit = wire_mod.exchange_cost(
                [{"dim": 10, "cap": fit_cap, "pair": False,
                  "id_itemsize": 4}], S, wire_mod.wire_format(None))
            out["exchange_bytes_at_fit_capacity"] = fit["bytes_per_step"]
        return out

    out = {"num_shards": S, "hot_rows": HOT, "alpha": alpha, "vocab": vocab,
           "batch": batch, "wire": None}
    from openembedding_tpu.ops.wire import wire_format
    out["wire"] = wire_format(None)
    zipf = stream(False)
    out["zipf_off"] = one_config("zipf_off", 0, zipf)
    out["zipf_on"] = one_config("zipf_on", HOT, zipf)
    uni = stream(True)
    out["uniform_off"] = one_config("uniform_off", 0, uni)
    out["uniform_on"] = one_config("uniform_on", HOT, uni)
    off_b = out["zipf_off"].get("exchange_bytes_at_fit_capacity")
    on_b = out["zipf_on"].get("exchange_bytes_at_fit_capacity")
    if off_b and on_b:
        out["payload_reduction_pct"] = round((1 - on_b / off_b) * 100, 1)
        out["net_reduction_with_replicate_pct"] = round(
            (1 - (on_b + out["zipf_on"]["replicate_bytes_per_step"])
             / off_b) * 100, 1)
    # the default path must stay free: hot_rows=0 attaches no cache state and
    # traces no probe/psum — same program as before the feature existed
    # (tests/test_hot.py pins the HLO); recorded so the artifact says so
    out["hot_off_is_baseline_trace"] = True
    return out


def case_placement():
    """Self-driving placement (round 12): drifting-Zipf traffic (a planted
    heavy pool homed on ONE shard, rotated to a different shard mid-run)
    through the sharded exchange, `placement.PlacementController` on vs off
    — the controller sees ONLY a replicated-byte budget and must size the
    hot cache, pace refreshes and re-shard the cold tail itself.

    Reported per config: steady-state shard imbalance BEFORE and AFTER the
    drift (mean of each phase's last third — the controller's recovery is
    the product), hot hit ratio, refresh/migration counts, the off-hot-path
    migration traffic (2 all_gathers of the (M, W) annex per migrate call),
    and ms/step (controller on-step overhead rides the number). Needs
    S >= 2; the battery entry runs the 8-virtual-device CPU mesh."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.placement import (PlacementController,
                                             PlacementPolicy)
    from openembedding_tpu.placement.policy import row_bytes
    from openembedding_tpu.utils import metrics as metrics_mod
    from openembedding_tpu.utils.sketch import SkewMonitor

    WD.stage("placement:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    mesh = make_mesh(devs[:S])
    vocab = int(os.environ.get("OETPU_BENCH_PLACEMENT_VOCAB", str(1 << 13)))
    cpu = devs[0].platform == "cpu"
    batch = min(BATCH, 2048) if cpu else BATCH
    steps_per_phase = 16 if cpu else 30
    fields = 26
    POOL, HOT_SHARE = 64, 0.6
    budget = int(os.environ.get("OETPU_BENCH_PLACEMENT_BUDGET",
                                str(32 * row_bytes(9 + 1))))

    def pools():
        # heavy pool homed entirely on one shard pre-drift, another after
        return ((np.arange(POOL) * S + S - 1).astype(np.int64),
                (np.arange(POOL) * S + 1).astype(np.int64))

    def stream(seed=13):
        rng = np.random.default_rng(seed)
        pre, post = pools()
        w = 1.0 / (np.arange(POOL) + 1.0)
        w /= w.sum()
        bs = []
        for i in range(2 * steps_per_phase):
            pool = pre if i < steps_per_phase else post
            ids = rng.integers(0, vocab, (batch, fields))
            mask = rng.random((batch, fields)) < HOT_SHARE
            ids[mask] = pool[rng.choice(POOL, size=int(mask.sum()), p=w)]
            bs.append({
                "sparse": {"categorical": ids.astype(np.int32)},
                "dense": rng.normal(size=(batch, 13)).astype(np.float32),
                "label": rng.integers(0, 2, (batch,)).astype(np.float32)})
        return bs

    def one_config(name, on, bs):
        WD.stage(f"placement:{name}", 600)
        metrics_mod._REGISTRY.clear()
        model = make_deepfm(vocabulary=vocab, dim=9)
        tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05),
                         mesh=mesh, capacity_factor=0.0)
        ctrl = None
        mon = SkewMonitor(k=256, sync=True, decay=0.9)
        if on:
            policy = PlacementPolicy(budget, mig_rows=POOL,
                                     refresh_cooldown_steps=3,
                                     imbalance_target=1.05)
            ctrl = PlacementController(tr, policy, monitor=mon,
                                       interval_steps=3)
            for b in bs[:3]:
                mon.observe("categorical", b["sparse"]["categorical"])
        state = tr.init(bs[0])
        if ctrl is not None:
            state = ctrl.prime(state)
        step = tr.jit_train_step(bs[0], state)
        out, times, imbs, hits = {}, [], [], []
        for i, b in enumerate(bs):
            if ctrl is not None:
                mon.observe("categorical", b["sparse"]["categorical"])
            t0 = time.perf_counter()
            state, m = step(state, b)
            float(m["loss"])
            if i:
                times.append(time.perf_counter() - t0)
            stats = {k: np.asarray(v) for k, v in
                     jax.device_get(m["stats"]).items()}
            pos = stats.get("categorical/shard_positions")
            if pos is not None and pos.mean() > 0:
                imbs.append(float(pos.max() / pos.mean()))
            if "categorical/hot_hits" in stats:
                hits.append(float(stats["categorical/hot_hits"])
                            / float(stats["categorical/pull_indices"]))
            metrics_mod.record_step_stats(m["stats"])
            if ctrl is not None:
                state = ctrl.on_step(state, step=i + 1)
        third = max(steps_per_phase // 3, 1)
        out["imbalance_pre_drift"] = round(
            float(np.mean(imbs[steps_per_phase - third:steps_per_phase])), 3)
        out["imbalance_post_drift"] = round(float(np.mean(imbs[-third:])), 3)
        out["ms_per_step"] = round(min(times) * 1e3, 2) if times else None
        if hits:
            out["hit_ratio_final"] = round(float(np.mean(hits[-third:])), 4)
        if ctrl is not None:
            st = ctrl.status()
            migrations = st["migrations_applied"]
            rep = metrics_mod.report()
            out["refreshes"] = rep.get("placement.refreshes", 0)
            out["migrations"] = migrations
            out["hot_rows"] = st["hot_rows"]
            # off-hot-path annex traffic: 2 all_gathers of (M, W) per
            # migrate call, W = fp32 weights + slots
            W = row_bytes(9 + 1)
            out["migration_bytes_total"] = int(
                2 * (S - 1) * POOL * W * max(migrations, 0))
        return out

    bs = stream()
    out = {"num_shards": S, "vocab": vocab, "batch": batch,
           "steps_per_phase": steps_per_phase,
           "hot_budget_bytes": budget}
    out["controller_off"] = one_config("off", False, bs)
    out["controller_on"] = one_config("on", True, bs)
    return out


def case_zero():
    """ZeRO dense-state sharding (round 14): `MeshTrainer(dense_shard=True)`
    vs the replicated baseline on the same batches — optimizer-state bytes
    per replica (the S-fold win), ms/step (reduce_scatter + 1/S-chunk update
    + all_gather vs psum + full update), and the pinned bit-parity of the
    final loss. Needs S >= 2; the battery entry runs the 8-virtual-device
    CPU mesh."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.utils import metrics as metrics_mod

    WD.stage("zero:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    mesh = make_mesh(devs[:S])
    cpu = devs[0].platform == "cpu"
    vocab = int(os.environ.get("OETPU_BENCH_ZERO_VOCAB", str(1 << 13)))
    batch = min(BATCH, 1024) if cpu else BATCH
    steps = 12 if cpu else 30

    def stream(seed=17):
        rng = np.random.default_rng(seed)
        return [{"sparse": {"categorical":
                            rng.integers(0, vocab, (batch, 26)).astype(
                                np.int32)},
                 "dense": rng.normal(size=(batch, 13)).astype(np.float32),
                 "label": rng.integers(0, 2, (batch,)).astype(np.float32)}
                for _ in range(steps)]

    def one_config(name, dense_shard, bs):
        WD.stage(f"zero:{name}", 600)
        metrics_mod._REGISTRY.clear()
        model = make_deepfm(vocabulary=vocab, dim=9)
        # Adam: two vector slots + scalar beta powers — the heavy opt state
        tr = MeshTrainer(model, embed.Adam(learning_rate=0.001), mesh=mesh,
                         capacity_factor=0.0, dense_shard=dense_shard)
        state = tr.init(bs[0])
        step = tr.jit_train_step(bs[0], state)
        times, loss = [], None
        for i, b in enumerate(bs):
            t0 = time.perf_counter()
            state, m = step(state, b)
            loss = float(m["loss"])
            if i:
                times.append(time.perf_counter() - t0)
        rep = metrics_mod.report()
        out = {"ms_per_step": round(min(times) * 1e3, 2),
               "loss_final": loss}
        if dense_shard:
            out["params_total"] = int(rep.get("dense.params_total", 0))
            out["opt_state_bytes_per_replica"] = int(
                rep.get("dense.opt_state_bytes_per_replica", 0))
            out["reduce_scatter_bytes"] = int(
                rep.get("dense.reduce_scatter_bytes", 0))
            out["all_gather_bytes"] = int(
                rep.get("dense.all_gather_bytes", 0))
        else:
            # replicated baseline: every replica holds full vector slots
            from openembedding_tpu.parallel import zero as zero_mod
            plan = zero_mod.build_plan(
                tr._dense_trainable(state), tr.optimizer, S)
            out["params_total"] = plan.total
            out["opt_state_bytes_per_replica"] = int(
                len(plan.vector_slots) * plan.total * 4
                + len(plan.scalar_slots) * 4)
        return out

    bs = stream()
    out = {"num_shards": S, "vocab": vocab, "batch": batch, "steps": steps}
    out["replicated"] = one_config("replicated", False, bs)
    out["sharded"] = one_config("sharded", True, bs)
    rep_b, sh_b = (out["replicated"]["opt_state_bytes_per_replica"],
                   out["sharded"]["opt_state_bytes_per_replica"])
    if sh_b:
        out["opt_state_reduction"] = round(rep_b / sh_b, 2)
    # fp32 bit-parity rides every bench run, not just the test suite
    out["loss_bit_equal"] = (out["replicated"]["loss_final"]
                             == out["sharded"]["loss_final"])
    return out


def case_zero_sparse():
    """Round-20 sparsity-aware dense collectives: dense_wire="sparse_topk"
    vs the int8 and fp32 dense-grad wires across a PLANTED gradient-density
    sweep. The tower is one wide Dense(1) over D input features with only a
    density-p column subset ever nonzero, so the kernel gradient's density
    is p by construction and the crossover math is measurable, not assumed.
    Per density: the dense-grad exchange bytes of all three wires from the
    COMPILED HLO (`collective_payloads` — reduce_scatter f32 result bytes
    for fp32, the s8 a2a payload for int8/sparse; the sparse-table exchange
    stays fp32 so the s8 bytes are exactly the dense-grad wire), the
    measured `dense.grad_density` gauge vs planted p, the policy's
    crossover verdict (`recommend_dense_wire`), and final-loss parity vs
    the fp32 control. Asserted floors: in the sparse regime the top-k wire
    ships <= 0.5x the int8 dense path's grad bytes, the policy picks sparse
    below the crossover and dense above it, and every wire's loss tracks
    fp32. Needs S >= 2; the battery entry rides the 8-virtual-device CPU
    mesh."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import openembedding_tpu as embed
    from openembedding_tpu.model import EmbeddingModel
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.placement.policy import PlacementPolicy
    from openembedding_tpu.utils import metrics as metrics_mod
    from tools.oelint.passes.hlo_budget import collective_payloads

    WD.stage("zero_sparse:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    if S < 2:
        return {"skipped": "needs S >= 2 shards (battery entry runs the "
                           "8-virtual-device CPU mesh)"}
    mesh = make_mesh(devs[:S])
    cpu = devs[0].platform == "cpu"
    D = int(os.environ.get("OETPU_BENCH_SPARSE_D", str(8192)))
    vocab = 1 << 10
    batch = min(BATCH, 256) if cpu else BATCH
    steps = 6
    densities = (0.01, 0.1, 0.5)

    class Tower(nn.Module):
        @nn.compact
        def __call__(self, embedded, dense):
            first = jnp.sum(embedded["e"][..., 0].astype(jnp.float32),
                            axis=1)
            return nn.Dense(1, use_bias=False)(dense)[..., 0] + first

    def build():
        return EmbeddingModel(Tower(),
                              [embed.Embedding(vocab, 1, name="e")])

    def stream(p, seed=31):
        # the density-p column subset is fixed for the sweep point: a
        # column outside it never sees a nonzero input, so its kernel
        # gradient is exactly zero every step
        rng = np.random.default_rng(seed)
        cols = rng.choice(D, size=max(1, int(round(p * D))), replace=False)
        bs = []
        for _ in range(steps):
            x = np.zeros((batch, D), np.float32)
            x[:, cols] = rng.standard_normal(
                (batch, cols.size)).astype(np.float32)
            bs.append({"sparse": {"e": rng.integers(
                0, vocab, (batch, 4)).astype(np.int32)},
                "dense": x,
                "label": rng.integers(0, 2, (batch,)).astype(np.float32)})
        return bs

    pol = PlacementPolicy(hot_budget_bytes=0)

    def one_config(name, bs, dense_wire, dense_topk=None):
        WD.stage(f"zero_sparse:{name}", 600)
        metrics_mod._REGISTRY.clear()
        tr = MeshTrainer(build(), embed.Adagrad(learning_rate=0.05),
                         mesh=mesh, capacity_factor=0.0, wire="fp32",
                         dense_shard=True, dense_wire=dense_wire,
                         dense_topk=dense_topk, dense_stats=True)
        state = tr.init(bs[0])
        step = tr.jit_train_step(bs[0], state)
        txt = step.lower(state, bs[0]).compile().as_text()
        pay = collective_payloads(txt, kinds=("all_to_all", "all_gather",
                                              "reduce_scatter"))
        s8_a2a = sum(b for k, d, b in pay
                     if k == "all_to_all" and d == "s8")
        rs = sum(b for k, _d, b in pay if k == "reduce_scatter")
        loss = None
        for b in bs:
            state, m = step(state, b)
            loss = float(m["loss"])
        metrics_mod.record_step_stats(m["stats"])
        rep = metrics_mod.report()
        out = {"grad_wire_bytes": int(s8_a2a if dense_wire else rs),
               "loss_final": loss,
               "measured_density": round(
                   float(rep.get("dense.grad_density", 0.0)), 4)}
        if dense_wire == "sparse_topk":
            out["k"] = int(rep.get("dense.grad_topk", 0))
            out["wire_bytes_saved"] = int(
                rep.get("dense.wire_bytes_saved", 0))
        return out

    out = {"num_shards": S, "dense_features": D, "batch": batch,
           "steps": steps, "crossover": pol.dense_wire_crossover}
    chunk = None
    for p in densities:
        bs = stream(p)
        tag = f"p{p}"
        fp32 = one_config(f"{tag}_fp32", bs, None)
        int8 = one_config(f"{tag}_int8", bs, "int8")
        if chunk is None:
            # the ZeRO chunk is a model static — read it once for the
            # policy's k sizing (margin over planted nnz per chunk)
            tr0 = MeshTrainer(build(), embed.Adagrad(learning_rate=0.05),
                              mesh=mesh, dense_shard=True,
                              dense_wire="sparse_topk")
            st0 = tr0.init(bs[0])
            chunk = tr0._zero_plan_for(tr0._dense_trainable(st0)).chunk
        k = pol._dense_topk(p, chunk)
        sparse = one_config(f"{tag}_sparse", bs, "sparse_topk",
                            dense_topk=k)
        mode, _k, reason = pol.recommend_dense_wire(
            fp32["measured_density"], "int8", chunk=chunk)
        row = {"planted_density": p, "fp32": fp32, "int8": int8,
               "sparse_topk": sparse,
               "policy": {"mode": mode, "reason": reason},
               "sparse_vs_int8_bytes": round(
                   sparse["grad_wire_bytes"]
                   / max(int8["grad_wire_bytes"], 1), 3)}
        for cfg in (int8, sparse):
            row.setdefault("loss_delta_vs_fp32_max", 0.0)
            row["loss_delta_vs_fp32_max"] = round(max(
                row["loss_delta_vs_fp32_max"],
                abs(cfg["loss_final"] - fp32["loss_final"])), 6)
        out[tag] = row
        # loss parity: every wire trains to the fp32 control's loss
        assert np.isfinite(sparse["loss_final"]), row
        np.testing.assert_allclose(sparse["loss_final"],
                                   fp32["loss_final"], rtol=0.02, atol=0.02)
        np.testing.assert_allclose(int8["loss_final"],
                                   fp32["loss_final"], rtol=0.02, atol=0.02)
    out["chunk"] = int(chunk)
    # the acceptance floor: in the sparse regime the top-k wire ships at
    # most half the int8 dense path's grad bytes (compiled-HLO accounting)
    assert out["p0.01"]["sparse_vs_int8_bytes"] <= 0.5, out["p0.01"]
    # the policy sits on the right side of the crossover at both ends
    assert out["p0.01"]["policy"]["mode"] == "sparse_topk", out["p0.01"]
    assert out["p0.5"]["policy"]["mode"] == "int8", out["p0.5"]
    # the density gauge reports the planted fraction (the decision input
    # is measured, not configured)
    for p in densities:
        md = out[f"p{p}"]["fp32"]["measured_density"]
        assert abs(md - p) <= max(0.25 * p, 0.005), (p, md)
    return out


def case_wire_total():
    """Round-17 bytes endgame: TOTAL compiled-HLO wire bytes per step —
    sparse exchange a2as + hot-row reduce + dense grad/param collectives —
    for the round-12 fp32 system (fp32 fused exchange, fp32 hot psum,
    replicated fp32 dense psum) vs a global-int8 config and the POLICY-MIXED
    config: `PlacementPolicy.recommend_wire` sizes per-table precision off
    the measured coverage curves (wide skewed tables int8+EF, the dim-1
    linear table fp32) feeding `MeshTrainer(wire={...})`, with the dense
    side on the quantized ZeRO collectives (`dense_wire="int8"`).

    Bytes come from the lowered HLO via the oelint hlo-budget parser
    (`collective_payloads`), in two accountings:
    - `hlo_bytes`: sum of collective RESULT buffers (the budget counters);
    - `link_bytes`: the same with all-reduce counted twice — its reduce and
      broadcast phases each ship the payload (ring accounting), the honest
      cross-device comparison when one config all-reduces what the other
      a2a + all_gathers.

    The in-band codec's own ceiling is 32*4/36 = 3.56x (4 scale-lane bytes
    per 32-element block) and the id/count lanes and bf16-carrier param
    all_gather are incompressible, so the ROADMAP's aspirational ">= 4x"
    re-anchors to the measured cut asserted here (see PERF.md round 17;
    `vs_target_4x` keeps the original target visible in the artifact).
    Needs S >= 2 for real collectives; the battery entry rides the
    8-virtual-device CPU mesh."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import openembedding_tpu as embed
    from openembedding_tpu.model import EmbeddingModel
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.placement.policy import (PlacementPolicy,
                                                    TableTelemetry)
    from openembedding_tpu.utils import metrics as metrics_mod
    from tools.oelint.passes.hlo_budget import collective_payloads

    WD.stage("wire_total:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    if S < 2:
        return {"skipped": "needs S >= 2 shards (battery entry runs the "
                           "8-virtual-device CPU mesh)"}
    mesh = make_mesh(devs[:S])
    cpu = devs[0].platform == "cpu"
    vocab = 1 << 14
    dim = 64
    batch = min(BATCH, 512) if cpu else BATCH
    steps = 4
    HOT = 1024

    def build():
        class Tower(nn.Module):
            @nn.compact
            def __call__(self, embedded, dense):
                x = jnp.concatenate(
                    [embedded["latent"].reshape(
                        embedded["latent"].shape[0], -1),
                     embedded["hashed"].reshape(
                         embedded["hashed"].shape[0], -1)],
                    axis=-1).astype(jnp.float32)
                x = nn.relu(nn.Dense(256)(x))
                first = jnp.sum(
                    embedded["first_order"][..., 0].astype(jnp.float32),
                    axis=1)
                return nn.Dense(1)(x)[..., 0] + first

        embs = [embed.Embedding(vocab, dim, name="latent"),
                embed.Embedding(-1, dim, name="hashed", capacity=1 << 16),
                embed.Embedding(vocab, 1, name="first_order",
                                feature="latent")]
        return EmbeddingModel(Tower(), embs)

    rng = np.random.default_rng(29)
    bs = []
    for _ in range(steps):
        # Zipf head so the coverage curves genuinely recommend int8
        lat = (rng.zipf(1.3, (batch, 8)) % vocab).astype(np.int32)
        hsh = (rng.zipf(1.3, (batch, 4)).astype(np.int64) * 2654435761
               % (1 << 40))
        bs.append({"sparse": {"latent": lat, "hashed": hsh},
                   "label": rng.integers(0, 2, (batch,))
                   .astype(np.float32)})

    def coverage(ids):
        _, cnt = np.unique(ids, return_counts=True)
        cnt = np.sort(cnt)[::-1]
        cum = np.cumsum(cnt) / max(cnt.sum(), 1)
        return [(k, float(cum[min(k, len(cum)) - 1]))
                for k in (64, 256, 1024, 4096)]

    model = build()
    tels = []
    for name, spec in model.ps_specs().items():
        ids = np.concatenate([np.asarray(
            b["sparse"][spec.feature_name]).reshape(-1) for b in bs])
        tels.append(TableTelemetry(name=name, dim=spec.output_dim,
                                   coverage=coverage(ids),
                                   total=float(ids.size)))
    rec = PlacementPolicy(hot_budget_bytes=0).recommend_wire(tels)

    lat_ids = np.concatenate([b["sparse"]["latent"].reshape(-1) for b in bs])
    uniq, cnt = np.unique(lat_ids, return_counts=True)
    top = uniq[np.argsort(-cnt)][:HOT].astype(np.int64)

    def one_config(name, wire, dense_shard, dense_wire):
        WD.stage(f"wire_total:{name}", 700)
        metrics_mod._REGISTRY.clear()
        tr = MeshTrainer(build(), embed.Adagrad(learning_rate=0.05),
                         mesh=mesh, capacity_factor=0.0,
                         group_exchange=True, hot_rows={"latent": HOT},
                         wire=wire, dense_shard=dense_shard,
                         dense_wire=dense_wire)
        state = tr.init(bs[0])
        state = tr.refresh_hot_rows(state, hot_ids={"latent": top})
        step = tr.jit_train_step(bs[0], state)
        txt = step.lower(state, bs[0]).compile().as_text()
        pay = collective_payloads(txt, kinds=("all_to_all", "all_gather",
                                              "reduce_scatter",
                                              "all_reduce"))
        kinds = {}
        for k, _d, b in pay:
            kinds[k] = kinds.get(k, 0) + b
        ar = kinds.get("all_reduce", 0)
        loss = None
        for b in bs:
            state, m = step(state, b)
            loss = float(m["loss"])
        out = {"hlo_bytes": sum(kinds.values()),
               "link_bytes": sum(kinds.values()) + ar,
               "by_kind": kinds,
               "a2a_dtypes": ",".join(sorted(
                   {d for k, d, _ in pay if k == "all_to_all"})),
               "wire": {n: tr.wire_for(n) for n in tr.model.ps_specs()},
               "loss_final": loss}
        return out

    out = {"num_shards": S, "vocab": vocab, "dim": dim, "batch": batch,
           "hot_rows": HOT, "policy_recommendation": rec}
    out["fp32_round12"] = one_config("fp32_round12", "fp32", False, None)
    out["int8_global"] = one_config("int8_global", "int8", True, "int8")
    out["policy_mixed"] = one_config("policy_mixed", rec, True, "int8")

    base, g8, pol = (out["fp32_round12"], out["int8_global"],
                     out["policy_mixed"])
    out["cut_hlo_x"] = round(base["hlo_bytes"] / pol["hlo_bytes"], 3)
    out["cut_link_x"] = round(base["link_bytes"] / pol["link_bytes"], 3)
    out["vs_target_4x"] = round(out["cut_link_x"] / 4.0, 3)
    out["loss_delta_vs_fp32"] = round(
        abs(pol["loss_final"] - base["loss_final"]), 6)
    # the policy's fp32 pick for the dim-1 table must not COST bytes vs
    # forcing int8 everywhere (int8 widens dim-1 rows: 1 B + scale lanes)
    assert pol["hlo_bytes"] <= g8["hlo_bytes"], (pol, g8)
    # honest floors (compiled shapes are deterministic; see docstring for
    # why the ROADMAP 4x re-anchors): result-byte cut and link-byte cut
    assert out["cut_hlo_x"] >= 2.2, out
    assert out["cut_link_x"] >= 2.7, out
    return out


def case_offload_pipe():
    """Host-offload staging pipeline + densified flush (round 14): the
    two-tier cache under churn — pipeline on/off x densify K in {1,4,16}.
    Reported per config: ms/round of the prepare+train loop (the staging
    thread hides the host lookup), pipeline occupancy (staged-batch hit
    ratio), and drained rows per densified merge. Host-side work; runs on
    any platform."""
    import jax.numpy as jnp
    import openembedding_tpu as embed
    from openembedding_tpu.embedding import (EmbeddingSpec, apply_gradients,
                                             lookup_train)
    from openembedding_tpu.initializers import Constant
    from openembedding_tpu.tables.host_offload import HostOffloadTable
    from openembedding_tpu.utils import metrics as metrics_mod

    WD.stage("offload_pipe:init", 240)
    dim = 16
    capacity = int(os.environ.get("OETPU_BENCH_OFFLOAD_CAP", str(1 << 13)))
    per_round = capacity // 2        # heavy admission pressure every round
    rounds = 20
    spec = EmbeddingSpec(name="t", input_dim=-1, output_dim=dim,
                         capacity=capacity, variable_id=0,
                         initializer=Constant(0.0))
    rng = np.random.default_rng(23)
    batches = [rng.integers(0, 1 << 22, size=per_round).astype(np.int64)
               for _ in range(rounds)]
    grads = [np.asarray(rng.standard_normal((per_round, dim)), np.float32)
             for _ in range(rounds)]

    import jax

    def one_config(name, pipeline, densify_k):
        WD.stage(f"offload_pipe:{name}", 420)
        metrics_mod._REGISTRY.clear()
        opt = embed.Adagrad(learning_rate=0.1)
        off = HostOffloadTable(spec, opt, high_water=0.8,
                               pipeline=pipeline, densify_k=densify_k)
        times = []
        if pipeline:
            off.stage(batches[0])
        for r, ids in enumerate(batches):
            t0 = time.perf_counter()
            off.prepare(ids)
            if pipeline and r + 1 < rounds:
                off.stage(batches[r + 1])
            st, _ = lookup_train(spec, off.state, jnp.asarray(ids))
            off.state = apply_gradients(spec, st, opt, jnp.asarray(ids),
                                        jnp.asarray(grads[r]))
            jax.block_until_ready(off.state)  # fence: device work is real
            if r:
                times.append(time.perf_counter() - t0)
        rep = metrics_mod.report()
        out = {"ms_per_round": round(float(np.mean(times)) * 1e3, 2)}
        if pipeline:
            out["pipeline_occupancy"] = round(
                float(rep.get("offload.pipeline_occupancy", 0.0)), 3)
        if densify_k > 1:
            out["densified_merges"] = int(
                rep.get("offload.densified_merges", 0))
            out["drained_rows"] = int(rep.get("offload.drained_rows", 0))
        return out

    out = {"capacity": capacity, "ids_per_round": per_round,
           "rounds": rounds, "dim": dim,
           "platform": jax.devices()[0].platform}
    out["sync_k1"] = one_config("sync_k1", False, 1)
    out["pipe_k1"] = one_config("pipe_k1", True, 1)
    out["pipe_k4"] = one_config("pipe_k4", True, 4)
    out["pipe_k16"] = one_config("pipe_k16", True, 16)
    base = out["sync_k1"]["ms_per_round"]
    if base:
        out["pipe_k1_speedup"] = round(
            base / out["pipe_k1"]["ms_per_round"], 3)
    return out


def case_pipeline():
    """Round-18 software-pipelined train loop: `MeshTrainer(pipeline_steps=
    True)` vs the serial scan on the same K-step windows — ms/step both
    ways, fp32 bit-parity of the window losses, conflict-patch rows (the
    exact-replay re-gather of rows the previous batch updated), and the
    modeled overlapped vs patch bytes. The overlap needs S >= 2 shards, so
    the battery entry rides the 8-virtual-device CPU mesh — CPU pins
    STRUCTURE only (bit-parity, patch size, collective set); the ms/step
    speedup claim waits for a chip run."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.utils import metrics as metrics_mod

    WD.stage("pipeline:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    mesh = make_mesh(devs[:S])
    cpu = devs[0].platform == "cpu"
    vocab = int(os.environ.get("OETPU_BENCH_PIPE_VOCAB", str(1 << 13)))
    batch = min(BATCH, 1024) if cpu else BATCH
    K = 8                      # steps per compiled window
    windows = 4 if cpu else 8

    def stream(seed=29):
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(windows):
            bs = [{"sparse": {"categorical":
                              rng.integers(0, vocab, (batch, 26)).astype(
                                  np.int32)},
                   "dense": rng.normal(size=(batch, 13)).astype(np.float32),
                   "label": rng.integers(0, 2, (batch,)).astype(np.float32)}
                  for _ in range(K)]
            out.append(jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *bs))
        return out

    def one_config(name, pipe):
        WD.stage(f"pipeline:{name}", 700)
        metrics_mod._REGISTRY.clear()
        model = make_deepfm(vocabulary=vocab, dim=9)
        tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh,
                         capacity_factor=0.0, wire="fp32",
                         pipeline_steps=pipe)
        ws = stream()
        first = jax.tree_util.tree_map(lambda x: x[0], ws[0])
        state = tr.init(first)
        many = tr.jit_train_many(ws[0], state)
        times, losses, m = [], [], None
        for i, w in enumerate(ws):
            t0 = time.perf_counter()
            state, m = many(state, w)
            jax.block_until_ready((state, m))
            if i:
                times.append((time.perf_counter() - t0) / K)
            losses.extend(float(x) for x in np.asarray(m["loss"]))
        out = {"ms_per_step": round(min(times) * 1e3, 2)}
        if pipe:
            tr.record_window_stats(m)  # conflict gauges off the last window
            rep = metrics_mod.report()
            out["conflict_rows_last_window"] = int(
                rep.get('exchange.conflict_rows{table="categorical"}', 0))
            cost = tr.last_wire_cost or {}
            out["overlapped_bytes_per_step"] = int(
                cost.get("overlapped_bytes", 0))
            out["conflict_patch_bytes_per_step"] = int(
                cost.get("conflict_patch_bytes", 0))
        return out, losses

    out = {"num_shards": S, "vocab": vocab, "batch": batch, "window": K,
           "windows": windows, "platform": devs[0].platform}
    out["serial"], l_serial = one_config("serial", False)
    out["pipelined"], l_pipe = one_config("pipelined", True)
    # fp32 bit-parity rides every bench run, not just the test suite
    out["loss_bit_equal"] = l_serial == l_pipe
    base = out["serial"]["ms_per_step"]
    if base and out["pipelined"]["ms_per_step"]:
        out["pipeline_speedup"] = round(
            base / out["pipelined"]["ms_per_step"], 3)
    return out


def case_ingest():
    """Round-20 line-rate ingest: the pipelined `train_many` loop fed by the
    depth-D device feed ring (`data/ingest.py`). Three measurements: (1) the
    COMPUTE CEILING — pre-staged windows, min ms/step, i.e. what the device
    can absorb with input off the books; (2) the ring-fed loop
    (`train_stream` over `ingest.feed`) at generator line rate —
    examples/s/chip plus the measured input-wait share, which must be ~0
    when the producer keeps up; (3) a deliberately THROTTLED producer — the
    same loop must now be attributed input-bound through the
    `trainer.input_wait_ms` lane (the attribution control: if this share
    isn't high, the lane is lying). CPU pins attribution STRUCTURE; the
    examples/s/chip ceiling claim waits for a chip run."""
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.data import ingest
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.utils import metrics as metrics_mod

    WD.stage("ingest:init", 240)
    devs = jax.devices()
    S = min(8, len(devs))
    mesh = make_mesh(devs[:S])
    cpu = devs[0].platform == "cpu"
    vocab = int(os.environ.get("OETPU_BENCH_PIPE_VOCAB", str(1 << 13)))
    batch = min(BATCH, 1024) if cpu else BATCH
    K = 8                      # steps per compiled window
    windows = 4 if cpu else 8

    def ring(label, *, n_windows, throttle_s=0.0, depth=3):
        files = [f"synthetic://steps={n_windows * K // 2}&seed={7 + s}"
                 f"&id_space={vocab}" for s in range(2)]
        return ingest.feed(files, batch, mesh=mesh, source="synthetic",
                           depth=depth, window=K, workers=2, label=label,
                           throttle_s=throttle_s)

    model = make_deepfm(vocabulary=vocab, dim=9)
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh,
                     capacity_factor=0.0, wire="fp32", pipeline_steps=True)

    # (1) compute ceiling: the same windows, pre-staged — input off the books
    WD.stage("ingest:ceiling", 700)
    metrics_mod._REGISTRY.clear()
    staged = list(ring("stage", n_windows=windows))
    first = jax.tree_util.tree_map(lambda x: np.asarray(x[0]), staged[0])
    state = tr.init(first)
    many = tr.jit_train_many(staged[0], state)
    times = []
    for i, w in enumerate(staged):
        t0 = time.perf_counter()
        state, m = many(state, w)
        jax.block_until_ready((state, m))
        if i:
            times.append((time.perf_counter() - t0) / K)
    ceiling_ms = min(times)
    out = {"num_shards": S, "vocab": vocab, "batch": batch, "window": K,
           "windows": windows, "platform": devs[0].platform,
           "compute_ms_per_step": round(ceiling_ms * 1e3, 2),
           "compute_ceiling_eps_per_chip": round(
               batch / ceiling_ms / S, 1)}

    # (2) ring-fed at line rate: input-wait share must stay ~0
    WD.stage("ingest:line_rate", 700)
    metrics_mod._REGISTRY.clear()
    t0 = time.perf_counter()
    state, rep = tr.train_stream(state, ring("line", n_windows=windows))
    elapsed = time.perf_counter() - t0
    share = ingest.input_wait_share()
    out["line_rate"] = {
        "windows": rep["windows"],
        "examples_per_sec_per_chip": round(
            rep["windows"] * K * batch / elapsed / S, 1),
        "input_wait_share": round(share, 4) if share is not None else None,
    }

    # (3) throttled producer: the SAME loop must read input-bound. The
    # throttle scales off the MEASURED ceiling (2x slower than the device
    # can absorb), so the control holds on any platform speed.
    WD.stage("ingest:throttled", 700)
    metrics_mod._REGISTRY.clear()
    state, rep = tr.train_stream(
        state, ring("slow", n_windows=2, throttle_s=2.0 * ceiling_ms,
                    depth=1))
    tshare = ingest.input_wait_share()
    out["throttled"] = {
        "windows": rep["windows"],
        "input_wait_share": round(tshare, 4) if tshare is not None else None,
    }
    out["attribution_ok"] = bool(
        share is not None and tshare is not None and share < 0.05 < tshare)
    return out


def case_pull():
    """Embedding-pull p50 (BASELINE.md metric). A pull = the serving/forward read:
    dedup + row gather for one 4096x26 Zipfian batch against the 2^24-row dim-9
    table. PULL_SCAN pulls over DISTINCT id batches are fused into one program
    (distinct batches so XLA cannot CSE them away); per-pull latency = program
    time / PULL_SCAN; p50 is the median over dispatch repeats. This is device
    latency — the reference's p50 additionally includes its PS RPC wire time,
    while ours has no wire (the table is in local HBM)."""
    import jax
    import jax.numpy as jnp
    import openembedding_tpu as embed
    from openembedding_tpu.embedding import lookup
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm

    WD.stage("pull:init", 240)
    model = make_deepfm(vocabulary=VOCAB, dim=9)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batches, _ = _stacked_batches(9, 1)
    state = trainer.init(batches[0])
    (name, spec), = model.ps_specs().items()
    table = state.tables[name]

    ids = np.stack([b["sparse"][name] for b in
                    _stacked_batches(9, PULL_SCAN, seed=11)[0]])
    ids = jax.device_put(ids.astype(np.int32))

    def pulls(table, all_ids):
        def body(acc, ids):
            rows = lookup(spec, table, ids)
            return acc + rows.astype(jnp.float32).sum(), None
        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), all_ids)
        return acc

    jpulls = jax.jit(pulls)
    WD.stage("pull:compile", 300)
    float(jpulls(table, ids))
    WD.stage("pull:measure", 240)
    times = []
    for _ in range(max(REPEATS, 5)):
        t0 = time.perf_counter()
        float(jpulls(table, ids))
        times.append((time.perf_counter() - t0) / PULL_SCAN)
    p50_us = float(np.median(times) * 1e6)
    return {"pull_p50_us": round(p50_us, 1), "batch": BATCH,
            "fields": int(ids.shape[-1]), "scan": PULL_SCAN}


def main():
    WD.stage("boot", 240)
    log(f"python up; initializing backend (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')})")
    import jax
    devs = jax.devices()
    log(f"devices: {devs}")
    platform = devs[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        # never a silent fallback: a CPU run is something the caller asks for
        log(f"no TPU (platform={platform!r}) and the caller did not set "
            "JAX_PLATFORMS=cpu: refusing to measure")
        return 2
    from openembedding_tpu.utils import compile_cache
    EXTRA["platform"] = platform
    EXTRA["device_kind"] = devs[0].device_kind
    EXTRA["device_count"] = len(devs)
    EXTRA["compile_cache"] = compile_cache.enable()

    cases = os.environ.get(
        "OETPU_BENCH_CASES",
        "dim9,dim64,mesh1,mesh1f,pull,wire,wire_inband,sync,skew,hot,"
        "placement,zero,zero_sparse,wire_total,offload_pipe,pipeline,"
        "ingest,health,obs2,causality").split(",")

    # PRIMARY first: whatever happens later, this number is in the artifact.
    if "dim9" in cases:
        out = run_case("dim9", lambda: case_trainer(9))
        if out:
            RESULT["value"] = out["examples_per_sec_per_chip"]
            RESULT["vs_baseline"] = out["vs_baseline_dim9"]

    secondary = [("dim64", lambda: case_trainer(64)),
                 ("mesh1", case_mesh1),
                 ("mesh1f", lambda: case_mesh1(capacity_factor=1.0,
                                               name="mesh1f")),
                 ("pull", case_pull),
                 ("wire", case_wire),
                 ("wire_inband", case_wire_inband),
                 ("sync", case_sync),
                 ("skew", case_skew),
                 ("hot", case_hot),
                 ("placement", case_placement),
                 ("zero", case_zero),
                 ("zero_sparse", case_zero_sparse),
                 ("wire_total", case_wire_total),
                 ("offload_pipe", case_offload_pipe),
                 ("pipeline", case_pipeline),
                 ("ingest", case_ingest),
                 ("health", case_health),
                 ("obs2", case_obs2),
                 ("causality", case_causality)]
    for name, fn in secondary:
        if name not in cases:
            continue
        if time.time() - T0 > BUDGET_S:
            ERRORS[name] = f"skipped: over wall-clock budget ({BUDGET_S:.0f}s)"
            log(ERRORS[name])
            continue
        run_case(name, fn)

    WD.clear()
    return emit()


if __name__ == "__main__":
    sys.exit(main())
