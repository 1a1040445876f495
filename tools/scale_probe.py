"""Measure incremental persist and host-offload at a scale that hurts.

Round-4 review weak #3/#4: the 27x/4.5x delta-persist numbers came from a 320 MB
state and offload had no performance datum at all. This probe produces the
missing curve points:

  persist:  full-vs-delta wall time + bytes at --vocab-log2 {22..27}
            (dim-9 DeepFM state = 80 B/row: 2^22 = 336 MB ... 2^27 = 10.7 GB)
  offload:  offload_train_many examples/s at a hashed table whose id space
            is ~2x the device cache, vs the SAME workload on a plain in-HBM
            (in-RAM on CPU) table — the price of the two-tier path when the
            table does not fit

Honest-labeling note: on CPU the "device cache" and "host store" live in the
same RAM, so the offload number isolates the admission/eviction/bookkeeping
COMPUTE cost — there is no PCIe transfer in it. On a host-attached TPU the
same path pays real DMA; the round-3 chip number (458 ex/s, PERF.md "Earlier
chip numbers") was taken through a remote runtime whose per-transfer latency
dominated it and is not representative of either.

Usage:
  python tools/scale_probe.py persist --vocab-log2 24 [--steps 8]
  python tools/scale_probe.py offload [--cache-log2 20] [--steps 32]
Writes one JSON line per case to stdout; run under JAX_PLATFORMS=cpu for the
scale cases (the v5e cannot hold 2^27 x 20 f32 anyway).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(case, payload):
    print(json.dumps({"case": case, **payload}), flush=True)


def probe_persist(vocab_log2: int, steps: int, batch: int):
    import jax

    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.persist import (AsyncPersister, IncrementalPersister,
                                           PersistPolicy, list_deltas,
                                           list_persists)

    V = 1 << vocab_log2
    model = make_deepfm(vocabulary=V, dim=9)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    batches = list(synthetic_criteo(batch, id_space=V, steps=steps, seed=1,
                                    ids_dtype=np.int32))
    t0 = time.perf_counter()
    state = trainer.init(batches[0])
    step = trainer.jit_train_step()
    for b in batches:
        state, m = step(state, b)
    float(m["loss"])
    train_s = time.perf_counter() - t0
    state_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for ts in state.tables.values()
        for a in ([ts.weights] + list(ts.slots.values())))

    def du(path):
        total = 0
        for root, _, files in os.walk(path):
            for f in files:
                total += os.path.getsize(os.path.join(root, f))
        return total

    tmp = tempfile.mkdtemp(prefix="persist_probe_")
    out = {"vocab_log2": vocab_log2, "state_gib": round(state_bytes / 2**30, 3),
           "train_warm_s": round(train_s, 1), "batch": batch, "steps": steps}
    try:
        # FULL persist: snapshot + write, measured to COMMIT (wait drains)
        with AsyncPersister(trainer, model, os.path.join(tmp, "full"),
                            policy=PersistPolicy(every_steps=1)) as p:
            t0 = time.perf_counter()
            p.persist(state)
            p.wait()
            out["full_persist_s"] = round(time.perf_counter() - t0, 2)
        out["full_bytes"] = du(os.path.join(tmp, "full"))

        # DELTA: base once, then observe one batch window and persist deltas
        with IncrementalPersister(trainer, model, os.path.join(tmp, "incr"),
                                  policy=PersistPolicy(every_steps=1),
                                  full_every=1000) as p:
            p.observe(batches[0])
            p.persist(state)  # base (full)
            p.wait()
            base_bytes = du(os.path.join(tmp, "incr"))
            ts = []
            st = state
            for b in batches[:3]:
                p.observe(b)
                st = st.replace(step=st.step + 1)
                t0 = time.perf_counter()
                p.persist(st)
                p.wait()
                ts.append(time.perf_counter() - t0)
            out["delta_persist_s"] = round(float(np.median(ts)), 3)
            out["delta_bytes"] = (du(os.path.join(tmp, "incr")) - base_bytes
                                  ) // max(1, len(ts))
            out["touched_rows_per_window"] = int(np.unique(
                batches[0]["sparse"]["categorical"]).size)
        out["speedup_time"] = round(
            out["full_persist_s"] / max(1e-9, out["delta_persist_s"]), 1)
        out["ratio_bytes"] = round(
            out["full_bytes"] / max(1, out["delta_bytes"]), 1)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    _emit("persist", out)


def probe_offload(cache_log2: int, steps: int, batch: int, scan: int):
    import dataclasses

    import jax

    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm

    cache = 1 << cache_log2
    id_space = 1 << (cache_log2 + 1)  # ~2x the cache (Zipf uniques less, see out)

    def run(offload: bool):
        model = make_deepfm(vocabulary=-1 if offload else id_space, dim=9,
                            hashed=offload, capacity=(cache if offload
                                                      else 0))
        if offload:
            model.specs["categorical"] = dataclasses.replace(
                model.specs["categorical"], storage="host_cached")
        trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
        batches = list(synthetic_criteo(batch, id_space=id_space, steps=steps,
                                        seed=1, ids_dtype=np.int32))
        state = trainer.init(batches[0])
        windows = [batches[i:i + scan] for i in range(0, steps, scan)]
        stacked = [jax.tree_util.tree_map(lambda *xs: np.stack(xs), *w)
                   for w in windows]
        # warm (compile + first admissions)
        state, m = trainer.offload_train_many(state, stacked[0])
        float(np.asarray(m["loss"])[-1])
        t0 = time.perf_counter()
        done = 0
        for w in stacked[1:]:
            state, m = trainer.offload_train_many(state, w)
            done += scan
        float(np.asarray(m["loss"])[-1])
        dt = time.perf_counter() - t0
        uniq = int(np.unique(np.concatenate(
            [b["sparse"]["categorical"].reshape(-1) for b in batches])).size)
        return done * batch / dt, uniq

    eps_off, uniq = run(True)
    eps_plain, _ = run(False)
    _emit("offload", {
        "cache_rows": cache, "id_space": id_space, "unique_ids_seen": uniq,
        "batch": batch, "scan": scan, "steps": steps,
        "offload_examples_per_s": round(eps_off, 1),
        "plain_examples_per_s": round(eps_plain, 1),
        "offload_cost_factor": round(eps_plain / max(1e-9, eps_off), 2),
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["persist", "offload"])
    ap.add_argument("--vocab-log2", type=int, default=24)
    ap.add_argument("--cache-log2", type=int, default=20)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--scan", type=int, default=8)
    args = ap.parse_args()
    if args.mode == "persist":
        probe_persist(args.vocab_log2, args.steps, args.batch)
    else:
        probe_offload(args.cache_log2, args.steps, args.batch, args.scan)


if __name__ == "__main__":
    main()
