"""Chip bring-up smoke: the DeepFM train -> serve -> mesh path, once, on the chip.

    python chip_smoke.py            # on a machine with a TPU; full width

ONE process (a chip belongs to one process at a time), three stages through
the entry points a user calls, each timed with compile seconds apart from run
seconds:

- `train`: `Trainer` on `make_deepfm(vocabulary=2**24, dim=9)` / Adagrad(0.05) /
  batch 4096 / int32 `synthetic_criteo` ids — 3 `jit_train_step()` calls on a
  FIXED batch (loss finite and falling), then one 16-step `jit_train_many()`
  window on fresh batches.
- `serve`: on the train stage's state — `export_standalone` -> `make_server` on
  127.0.0.1 in a thread -> `ServingClient.create_model` -> 3 pulls (equal to the
  live table rows) and 3 predicts (equal to `jit_eval_step` on the same rows
  within bf16 tolerance) -> `shutdown()`. The train state is dropped before
  the mesh allocates.
- `mesh`: `MeshTrainer(mesh=make_mesh())` over ALL `jax.devices()` with the
  constructor defaults, global batch 4096 x S, same step/scan sequence. Asserts
  every table's `weights` sit in S shards of rows/S on S distinct devices, that
  `bytes_in_use` is balanced across devices, that at S > 1 the traced wire
  cost has collectives and bytes, and that at S = 1 the losses equal the train
  stage's on the same batches (the exchange specialises to identity there).

Any failing stage — exception, assertion, non-finite loss — ends the process
with a non-zero exit code and nothing on stdout. A good run prints exactly two
stdout lines, each one JSON object: first the report (`{"report": {...}}`:
versions, config, compile cache, per-stage timings, device memory), then, as
the LAST line, the verdict with exactly these keys and nothing else —
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}` —
which is what the driver parses.

The program refuses to run (exit 2, before any stage) when JAX's first device
is not a TPU. A CPU rehearsal is something the CALLER asks for by name —
`JAX_PLATFORMS=cpu` in the environment AND explicit `--vocabulary`/`--batch`
sizes (`make chip-smoke-cpu`) — and it prints `"platform": "cpu"`; the
program never chooses it. Timings printed here are set-up facts about one
run, not benchmark metrics.

`--profile DIR` (off by default; no effect on pass/fail) records the train and
the mesh stage's scan window under `jax.profiler.trace` into `DIR/train` and
`DIR/mesh`, each with the compiled scan's HLO text beside the xplane — what
`python tools/trace_report.py --xplane DIR/train --steps 16` reduces to device
time per `trace.scope` stage.
"""

import argparse
import contextlib
import faulthandler
import gc
import importlib.metadata
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

FULL_VOCABULARY = 1 << 24
FULL_BATCH = 4096
DIM = 9
LEARNING_RATE = 0.05
FIXED_STEPS = 3
# the driver allows 1200 s, compilation included; past this the process is
# hung (a compile or a collective that never returns cannot be interrupted
# from Python) — faulthandler dumps every thread's stack and hard-exits 1
DEADLINE_S = 1150
# predict runs the same bf16 tower as eval_step but at another batch shape, so
# the matmuls tile differently: agreement is to a few bf16 ulps (2^-8), not f32
PREDICT_TOL = 2e-2
# at S = 1 the mesh program and the single-device program do the same
# arithmetic on the same batches
S1_LOSS_TOL = 1e-3

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"


def log(msg):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spent in the backend compiler (or loading from the
    persistent cache), read from `jax.monitoring` events — so a stage's wall
    time splits into compile and run without calling the jitted functions any
    other way than a user does."""

    def __init__(self):
        import jax.monitoring
        self.compile_s = 0.0
        self.counts = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _COMPILE_EVENT:
            self.compile_s += secs

    def _event(self, event, **kw):
        if event == _CACHE_REQUESTS:
            self.counts["requests"] += 1
        elif event == _CACHE_HITS:
            self.counts["hits"] += 1

    @contextlib.contextmanager
    def stage(self, name, out):
        """Time one stage into `out[name]`: compile_s from the listener,
        run_s = the rest of the wall time (device execution plus host work:
        tracing, batch generation, export I/O, HTTP)."""
        log(f"stage {name} ...")
        compile0, cnt0 = self.compile_s, dict(self.counts)
        t0 = time.perf_counter()
        result = out[name] = {}
        yield result
        wall = time.perf_counter() - t0
        compile_s = self.compile_s - compile0
        requests = self.counts["requests"] - cnt0["requests"]
        hits = self.counts["hits"] - cnt0["hits"]
        result.update(compile_s=round(compile_s, 3),
                      run_s=round(wall - compile_s, 3),
                      programs=requests, cache_hits=hits,
                      compiled=requests - hits)
        log(f"stage {name} OK: {json.dumps(result)}")


def check(cond, msg):
    """`assert` that survives `python -O`."""
    if not cond:
        raise AssertionError(msg)


def make_batches(batch_size, vocabulary, scan_steps):
    """-> (the fixed batch, `scan_steps` fresh batches stacked for the scan)."""
    import jax
    from openembedding_tpu.data import synthetic_criteo
    # int32 ids keep x64 off (vocabulary < 2^31)
    first, *rest = synthetic_criteo(batch_size, id_space=vocabulary,
                                    steps=1 + scan_steps, seed=7,
                                    ids_dtype=np.int32)
    return first, jax.tree_util.tree_map(lambda *xs: np.stack(xs), *rest)


def fresh_hlo_text(jitted, *args):
    """The compiled program's HLO text with THIS build's `op_name` metadata.
    The persistent cache's key leaves metadata out
    (`jax_compilation_cache_include_metadata_in_key`), so an executable
    loaded from it carries the stage names of whichever build wrote the
    entry: compile once past the cache (and past the in-memory one, which
    would hand back an executable this process already loaded). The fresh
    executable is what the next call of `jitted` runs."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        return jitted.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def drive(state, step, many, first, stacked, profile=None):
    """The step/scan sequence both trainers run: FIXED_STEPS steps on the
    fixed batch, then ONE scan window over the stacked fresh batches.
    `profile`: a directory that gets the scan window's profiler trace and
    the scan's HLO text (the names `trace.scope_map` joins the trace's ops to
    their stages by; one program a trace, since two programs' instruction
    names collide). -> (state, losses)."""
    import jax
    fixed = []
    for _ in range(FIXED_STEPS):
        state, metrics = step(state, first)
        fixed.append(float(metrics["loss"]))
    session = contextlib.nullcontext()
    if profile:
        os.makedirs(profile, exist_ok=True)
        with open(os.path.join(profile, "train_many.hlo.txt"), "w") as f:
            f.write(fresh_hlo_text(many, state, stacked))
        session = jax.profiler.trace(profile)
    with session:
        state, window = many(state, stacked)
        jax.block_until_ready(state)
    check(np.isfinite(fixed).all(), f"non-finite step loss: {fixed}")
    check(fixed[-1] < fixed[0], f"loss did not fall on a fixed batch: {fixed}")
    scan = np.asarray(window["loss"], np.float64)
    check(scan.shape == stacked["label"].shape[:1], f"scan losses {scan.shape}")
    check(np.isfinite(scan).all(), f"non-finite scan loss: {scan.tolist()}")
    check(int(window["overflow"]) == 0,
          f"exchange dropped {int(window['overflow'])} ids")
    return state, fixed + scan.tolist()


def stage_train(args, result):
    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm

    model = make_deepfm(vocabulary=args.vocabulary, dim=DIM)
    trainer = Trainer(model, embed.Adagrad(learning_rate=LEARNING_RATE))
    first, stacked = make_batches(args.batch, args.vocabulary, args.scan_steps)
    state = trainer.init(first)
    packed = trainer._packed_layouts(state)
    state, losses = drive(state, trainer.jit_train_step(),
                          trainer.jit_train_many(), first, stacked,
                          profile=args.profile
                          and os.path.join(args.profile, "train"))
    result.update(losses=[round(x, 6) for x in losses],
                  packed={k: list(map(list, v)) for k, v in packed.items()})
    return model, trainer, state, first, losses


def stage_serve(model, trainer, state, batch, result):
    import jax.numpy as jnp
    from openembedding_tpu.export import export_standalone
    from openembedding_tpu.serving import (ServingClient, make_server,
                                           resolve_sign)

    (name, spec), = model.ps_specs().items()
    ids = np.asarray(batch["sparse"][spec.feature_name])
    want_logits = np.asarray(trainer.jit_eval_step()(state, batch)["logits"],
                             np.float32)
    # single device: global row order == id order, so the live rows for the
    # pulled ids are a plain gather
    pull_ids = [ids[0, :8], ids[1], ids[:4].reshape(-1)]
    want_rows = [np.asarray(state.tables[name].weights[jnp.asarray(p)])
                 for p in pull_ids]

    with tempfile.TemporaryDirectory(prefix="oetpu_chip_smoke_") as tmp:
        export_dir = os.path.join(tmp, "export")
        sign = resolve_sign("chip-smoke", float(state.model_version))
        t0 = time.perf_counter()
        export_standalone(state, model, export_dir, model_sign=sign)
        result["export_s"] = round(time.perf_counter() - t0, 3)

        httpd = make_server(os.path.join(tmp, "registry"), port=0)
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        try:
            client = ServingClient(
                [f"http://127.0.0.1:{httpd.server_address[1]}"], timeout=300)
            t0 = time.perf_counter()
            entry = client.create_model(sign, export_dir)
            result["load_s"] = round(time.perf_counter() - t0, 3)
            check(entry["model_sign"] == sign, f"registered {entry}")

            for p, want in zip(pull_ids, want_rows):
                rows = client.pull(sign, name, p)
                check(rows.shape == (len(p), spec.output_dim),
                      f"pull shape {rows.shape}")
                check(np.isfinite(rows).all(), "non-finite pulled rows")
                np.testing.assert_allclose(rows, want, rtol=1e-6, atol=0)

            worst = 0.0
            for n in (1, 8, 64):
                logits = client.predict(
                    sign, {spec.feature_name: ids[:n]},
                    dense=np.asarray(batch["dense"])[:n])
                check(logits.shape == (n,), f"predict shape {logits.shape}")
                check(np.isfinite(logits).all(), "non-finite logits")
                np.testing.assert_allclose(logits, want_logits[:n],
                                           rtol=PREDICT_TOL, atol=PREDICT_TOL)
                worst = max(worst,
                            float(np.abs(logits - want_logits[:n]).max()))
            result.update(pulls=len(pull_ids), predicts=3,
                          predict_max_abs_diff=round(worst, 6))
        finally:
            httpd.shutdown()
            httpd.server_close()
            thread.join(timeout=30)
        check(not thread.is_alive(), "serving thread did not stop")
        del httpd, client  # the registry holds the loaded table on device


def stage_mesh(args, train_losses, result):
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    mesh = make_mesh()
    devices = list(mesh.devices.flat)
    S = len(devices)
    check(S == len(jax.devices()), "mesh must span every device")
    model = make_deepfm(vocabulary=args.vocabulary, dim=DIM)
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=LEARNING_RATE),
                          mesh=mesh)
    first, stacked = make_batches(args.batch * S, args.vocabulary,
                                  args.scan_steps)
    state = trainer.init(first)
    state, losses = drive(state, trainer.jit_train_step(first, state),
                          trainer.jit_train_many(stacked, state), first,
                          stacked, profile=args.profile
                          and os.path.join(args.profile, "mesh"))

    shard_rows = {}
    for name, spec in model.ps_specs().items():
        shards = state.tables[name].weights.addressable_shards
        rows = state.tables[name].weights.shape[0]
        check(len(shards) == S, f"{name}: {len(shards)} shards, want {S}")
        check(len({s.device for s in shards}) == S,
              f"{name}: shards share a device")
        check(all(s.data.shape[0] * S == rows for s in shards),
              f"{name}: uneven shards {[s.data.shape for s in shards]}")
        shard_rows[name] = rows // S
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if devices[0].platform == "tpu":
        check(all(in_use), f"no memory_stats on {devices}")
    if all(in_use):
        check(max(in_use) / min(in_use) < 1.5,
              f"device memory is unbalanced: bytes_in_use={in_use}")
    cost = trainer.last_wire_cost
    if S > 1:
        check(cost["collectives_per_step"] > 0 and cost["bytes_per_step"] > 0,
              f"no exchange traced at S={S}: {cost}")
    else:
        np.testing.assert_allclose(losses, train_losses, rtol=0,
                                   atol=S1_LOSS_TOL)
    result.update(shards=S, rows_per_shard=shard_rows, bytes_in_use=in_use,
                  wire_cost=cost, losses=[round(x, 6) for x in losses])


def package_version(name):
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--vocabulary", type=int, default=None,
                    help=f"table rows (default {FULL_VOCABULARY}; must be "
                         "given explicitly for a CPU rehearsal)")
    ap.add_argument("--batch", type=int, default=None,
                    help=f"examples per chip per step (default {FULL_BATCH}; "
                         "must be given explicitly for a CPU rehearsal)")
    ap.add_argument("--scan-steps", type=int, default=16)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="record the train and mesh scan windows' profiler "
                         "trace and HLO text under DIR (read with "
                         "tools/trace_report.py --xplane DIR/train)")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        rehearsal = (platform == "cpu"
                     and os.environ.get("JAX_PLATFORMS") == "cpu"
                     and args.vocabulary is not None
                     and args.batch is not None)
        if not rehearsal:
            log(f"no TPU: jax.devices()[0].platform == {platform!r}. A CPU "
                "rehearsal needs JAX_PLATFORMS=cpu AND explicit --vocabulary "
                "and --batch (see `make chip-smoke-cpu`).")
            return 2
    args.vocabulary = args.vocabulary or FULL_VOCABULARY
    args.batch = args.batch or FULL_BATCH

    from openembedding_tpu.ops.sparse import PACKED_MAX_BYTES
    from openembedding_tpu.utils import compile_cache

    cache_path = compile_cache.enable()
    entries_before = compile_cache.entry_count(cache_path)
    clock = CompileClock()
    log(f"devices={devices} cache={cache_path} ({entries_before} entries) "
        f"vocabulary={args.vocabulary} batch={args.batch}/chip")

    stages = {}
    with clock.stage("train", stages) as result:
        model, trainer, state, first, losses = stage_train(args, result)
    with clock.stage("serve", stages) as result:
        stage_serve(model, trainer, state, first, result)
    # states are donated step to step, so these names hold the only live
    # copy: drop them (and the served table) before the mesh allocates
    del model, trainer, state
    gc.collect()
    with clock.stage("mesh", stages) as result:
        stage_mesh(args, losses, result)

    stats = [d.memory_stats() or {} for d in devices]
    print(json.dumps({"report": {
        "versions": {"jax": jax.__version__,
                     "jaxlib": package_version("jaxlib"),
                     "libtpu": package_version("libtpu"),
                     "flax": package_version("flax")},
        "config": {"model": "deepfm", "vocabulary": args.vocabulary,
                   "dim": DIM, "batch_per_chip": args.batch,
                   "scan_steps": args.scan_steps},
        "compile_cache": {"dir": cache_path, "entries_before": entries_before,
                          "entries_after":
                              compile_cache.entry_count(cache_path)},
        "stages": stages,
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_limit": stats[0].get("bytes_limit"),
        "packed_max_bytes": PACKED_MAX_BYTES,
    }}), flush=True)
    # the verdict line: these keys and no others (the driver checks)
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": devices[0].device_kind,
                   "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
