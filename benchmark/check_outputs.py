"""The builder's checker: for one cell, many seeds in ONE process (the scan is
compiled once; the seed is a traced key). For every seed it reads the numbers
the run compares (program against the plain reference), then the same numbers
for each control (the reference in a lower precision, put in the program's
place) and each planted fault. Limits are set from these readings.

    python3 -m benchmark.check_outputs --workload <w> --seeds 1,2,3 \
        [--controls table_bf16,tower_fp8] [--faults half_batch,no_exchange] [--out file.json]

The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import jax

from benchmark import compare, run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="table_bf16,tower_fp8")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--control-seeds", type=int, default=3, help="controls and faults on the first N seeds")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = run.resolve(args.workload)
    device = run.device_block(cell["chips"], os.environ.get("JAX_PLATFORMS", "") == "cpu")
    from openembedding_tpu.utils import compile_cache
    compile_cache.enable()
    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        session = driver.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=seed)
        session.setup()
        session.free()
        with jax.default_matmul_precision("highest"):
            ref = session.reference_summary()
            row = {"seed": seed, "program": compare.numbers(session.prog, ref),
                   "loss0": float(ref["losses"][0]), "controls": {}, "faults": {},
                   "by_leaf": {k: compare.by_leaf(session.prog[k], ref[k]) for k in ("grad", "delta")},
                   "grad_floor": ref["grad_floor"]}
            if i < args.control_seeds:
                for c in filter(None, args.controls.split(",")):
                    row["controls"][c] = compare.numbers(session.reference_summary(precision=c), ref)
                for f in filter(None, args.faults.split(",")):
                    row["faults"][f] = compare.numbers(session.reference_summary(fault=f), ref)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    out = {"workload": args.workload, "device": device, "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
