"""Micro-benchmark: Pallas kernels vs the XLA fallback on the current backend.

The Pallas kernels only engage for 128-lane-aligned row widths (Mosaic DMA slice
constraint, see `ops/pallas_sparse.py::_require_lane_aligned`), so this measures:
- dim 64 (reference benchmark shape): XLA path only (what production uses there);
- dim 128 (aligned): XLA vs Pallas gather and fused-apply head to head;
- a full single-chip DeepFM train step at the reference dims, Pallas auto vs off.

Run on the real TPU:  python tools/pallas_microbench.py
On CPU (interpreter): JAX_PLATFORMS=cpu python tools/pallas_microbench.py --interpret
"""

import argparse
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, warmup=2, iters=20):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_dim(dim, vocab, n, opt, interp, try_pallas):
    import jax
    import jax.numpy as jnp
    from openembedding_tpu.ops import pallas_sparse
    from openembedding_tpu.ops.sparse import lookup_rows, sparse_apply_dense_table

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((vocab, dim)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, vocab, size=n), jnp.int32)
    slots = opt.init_slots(vocab, dim)
    grads = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)

    pallas_sparse.set_mode("off")
    xla_gather = jax.jit(lookup_rows)
    t = timeit(xla_gather, w, rows)
    print(f"[dim {dim:4d}] gather XLA:    {t*1e3:8.3f} ms ({n/t/1e6:7.1f} M rows/s)")

    xla_apply = jax.jit(lambda w, s, r, g: sparse_apply_dense_table(opt, w, s, r, g))
    t = timeit(xla_apply, w, slots, rows, grads)
    print(f"[dim {dim:4d}] apply  XLA:    {t*1e3:8.3f} ms ({n/t/1e6:7.1f} M grads/s)")

    if not try_pallas:
        return
    try:
        pgather = jax.jit(
            lambda w, r: pallas_sparse.gather_rows(w, r, interpret=interp))
        np.testing.assert_array_equal(np.asarray(xla_gather(w, rows)),
                                      np.asarray(pgather(w, rows)))
        t = timeit(pgather, w, rows)
        print(f"[dim {dim:4d}] gather Pallas: {t*1e3:8.3f} ms "
              f"({n/t/1e6:7.1f} M rows/s)")
    except Exception:
        print(f"[dim {dim:4d}] gather Pallas: FAILED")
        traceback.print_exc(limit=2)
    # window-batched gather (PERF lever #1): sorted rows, two densities —
    # uniform (worst case, sigma~1) and frequency-clustered (the reference's
    # relabel-by-frequency data shape, where windows amortize)
    for label, rows_w in (
        ("uniform", jnp.sort(rows)),
        ("hot10%", jnp.sort(jnp.asarray(
            rng.integers(0, max(vocab // 10, 1), size=n), jnp.int32))),
    ):
        for window in (16, 64):
            try:
                pwin = jax.jit(lambda w, r, win=window:
                               pallas_sparse.gather_rows_windows(
                                   w, r, window=win, interpret=interp))
                np.testing.assert_array_equal(
                    np.asarray(xla_gather(w, rows_w)),
                    np.asarray(pwin(w, rows_w)))
                t = timeit(pwin, w, rows_w)
                print(f"[dim {dim:4d}] gather win{window:3d} {label}: "
                      f"{t*1e3:8.3f} ms ({n/t/1e6:7.1f} M rows/s)")
            except Exception:
                print(f"[dim {dim:4d}] gather win{window} {label}: FAILED")
                traceback.print_exc(limit=2)
    try:
        pallas_sparse.set_mode("interpret" if interp else "on")
        papply = jax.jit(
            lambda w, s, r, g: sparse_apply_dense_table(opt, w, s, r, g))
        rw, _ = xla_apply(w, slots, rows, grads)
        gw, _ = papply(w, slots, rows, grads)
        np.testing.assert_allclose(np.asarray(rw), np.asarray(gw),
                                   rtol=2e-6, atol=1e-6)
        t = timeit(papply, w, slots, rows, grads)
        print(f"[dim {dim:4d}] apply  Pallas: {t*1e3:8.3f} ms "
              f"({n/t/1e6:7.1f} M grads/s)")
    except Exception:
        print(f"[dim {dim:4d}] apply  Pallas: FAILED")
        traceback.print_exc(limit=2)
    finally:
        pallas_sparse.set_mode("off")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--n", type=int, default=26 * 4096)
    args = ap.parse_args()

    import jax
    from openembedding_tpu.ops import pallas_sparse
    from openembedding_tpu import optimizers

    print(f"backend={jax.default_backend()} devices={jax.devices()}")
    opt = optimizers.Adagrad(learning_rate=0.05)
    small = args.interpret  # interpreter is slow; shrink shapes
    n = 2048 if small else args.n
    bench_dim(64, 1 << (14 if small else 22), n, opt, args.interpret, small)
    bench_dim(128, 1 << (14 if small else 21), n, opt, args.interpret, True)

    # full single-chip train step at the reference benchmark shape
    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.data import synthetic_criteo

    # "auto" resolves to the XLA path (kernels stay off until they win)
    for mode in ("off", "interpret" if args.interpret else "auto"):
        pallas_sparse.set_mode(mode)
        model = make_deepfm(vocabulary=1 << (14 if small else 22), dim=9)
        trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
        bs = 256 if small else 4096
        batch = jax.device_put(next(synthetic_criteo(
            bs, id_space=1 << 14, steps=1, seed=7, ids_dtype=np.int32)))
        state = trainer.init(batch)
        step = trainer.jit_train_step()
        state, m = step(state, batch)
        float(m["loss"])
        t0 = time.perf_counter()
        iters = 5 if small else 30
        for _ in range(iters):
            state, m = step(state, batch)
        float(m["loss"])
        dt = (time.perf_counter() - t0) / iters
        print(f"train step [{mode:9s}]: {dt*1e3:8.3f} ms ({bs/dt:,.0f} examples/s)")
    pallas_sparse.set_mode("off")


if __name__ == "__main__":
    main()
