"""Input-path headroom: reader -> batcher -> prefetch_to_device, NO train step.

Round-4 review item 8: at the 1M-examples/s north star each of 16 hosts must
parse ~62.5k rows/s; the native readers were measured in isolation (169k
rows/s TFRecord @4 threads) but the end-to-end feed — parse + batch +
device placement + the prefetch queue — was never pinned. This probe:

  1. generates a synthetic Criteo TSV (and .gz) once,
  2. streams it through `read_criteo_tsv(native=...)` + `prefetch_to_device`,
  3. reports rows/s for a thread-count curve, and
  4. reports the STALL FRACTION against a simulated device consuming at the
     chip step rate (--device-ms per batch; default 23.4 ms = 4096 rows at
     the measured 175k ex/s/chip): the fraction of wall time the "device"
     loop spends blocked on the feed. 0 = input fully off the critical path.

Usage:  python tools/feed_probe.py [--rows 400000] [--batch 4096]
                                   [--threads 1,2,4,8] [--device-ms 23.4]
One JSON line per configuration on stdout.
"""

import argparse
import gzip
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_DENSE, NUM_SPARSE = 13, 26


def synth_tsv(path: str, rows: int, seed: int = 0) -> str:
    rng = np.random.default_rng(seed)
    label = rng.integers(0, 2, rows)
    dense = rng.integers(-5, 1000, (rows, NUM_DENSE))
    dense_miss = rng.random((rows, NUM_DENSE)) < 0.1
    cats = rng.integers(0, 1 << 32, (rows, NUM_SPARSE), dtype=np.int64)
    cat_miss = rng.random((rows, NUM_SPARSE)) < 0.1
    with open(path, "w") as f:
        for r in range(rows):
            cols = [str(label[r])]
            cols += ["" if dense_miss[r, i] else str(dense[r, i])
                     for i in range(NUM_DENSE)]
            cols += ["" if cat_miss[r, i] else f"{cats[r, i]:08x}"
                     for i in range(NUM_SPARSE)]
            f.write("\t".join(cols) + "\n")
    return path


def run_one(paths, batch, threads, device_ms, native, repeat_rows):
    from openembedding_tpu.data import prefetch_to_device, read_criteo_tsv

    it = read_criteo_tsv(paths, batch, id_space=1 << 25, native=native,
                         native_threads=threads, repeat=True)
    it = prefetch_to_device(it, size=4)
    target_batches = max(1, repeat_rows // batch)
    # warm: first batch pays reader spin-up + device transfer compile
    next(it)
    t_start = time.perf_counter()
    stalled = 0.0
    n = 0
    for _ in range(target_batches):
        t0 = time.perf_counter()
        b = next(it)
        stalled += time.perf_counter() - t0
        n += int(b["label"].shape[0])
        if device_ms > 0:
            time.sleep(device_ms / 1e3)  # the simulated device step
    total = time.perf_counter() - t_start
    feed_only_rows_s = n / max(1e-9, stalled) if device_ms == 0 else None
    return {"threads": threads, "native": native, "rows": n,
            "rows_per_s": round(n / total, 1),
            "stall_fraction": round(stalled / total, 4),
            "device_ms": device_ms,
            **({"feed_only_rows_per_s": round(feed_only_rows_s, 1)}
               if feed_only_rows_s is not None else {})}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--threads", default="1,2,4,8")
    ap.add_argument("--device-ms", type=float, default=23.4)
    ap.add_argument("--measure-rows", type=int, default=400_000)
    ap.add_argument("--gz", action="store_true", help="also probe .gz input")
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="feed_probe_")
    base = synth_tsv(os.path.join(tmp, "a.tsv"), args.rows)
    paths = [base]
    if args.gz:
        gz = os.path.join(tmp, "a.tsv.gz")
        with open(base, "rb") as fin, gzip.open(gz, "wb", 1) as fout:
            fout.write(fin.read())

    for threads in [int(t) for t in args.threads.split(",")]:
        # pure feed rate (no device consumer)
        out = run_one(paths, args.batch, threads, 0.0, "on",
                      args.measure_rows)
        print(json.dumps({"case": "feed", **out}), flush=True)
        # behind a simulated chip-rate consumer
        out = run_one(paths, args.batch, threads, args.device_ms, "on",
                      args.measure_rows)
        print(json.dumps({"case": "feed+device", **out}), flush=True)
    # the Python fallback parser, for the curve's floor
    out = run_one(paths, args.batch, 1, 0.0, "off",
                  min(args.measure_rows, 100_000))
    print(json.dumps({"case": "feed-python", **out}), flush=True)
    if args.gz:
        out = run_one([gz], args.batch, 4, 0.0, "on", args.measure_rows)
        print(json.dumps({"case": "feed-gz", **out}), flush=True)


if __name__ == "__main__":
    main()
