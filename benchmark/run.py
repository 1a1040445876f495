"""Entry point of the benchmark:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found BY NAME from `BENCHMARK.json`:
`configs/<config>.json`, `traffic/<traffic>.json`, `drivers/<kind>.py` (the
traffic file's `kind`), `reference/<family>.py` (the configuration's `family`),
`limits/<workload>.json`, and for `--trace 1` each per-layer metric's
`layer_metrics/<metric>.json`, which names its `readers/<reader>.py`. No cell,
configuration or metric is named in code.

One process, which holds the chip; nothing outlives it. The last line of
standard output is the result; the numbers compared and their limits are the
last lines of standard error and the last key of the result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can see it

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(rel: str):
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def resolve(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg = load(f"configs/{cell['config']}.json")
    traffic = load(f"traffic/{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def metrics_of(bench, group: str, workload: str, reports: set):
    """The metrics of `group` this cell reports: those that list it, and those
    that list nothing and (per-layer) move an end-to-end metric it reports."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reports:
            out.append(m)
    return out


def device_block(chips: int, caller_asked_cpu: bool):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not (platform == "cpu" and caller_asked_cpu):
        print(f"no accelerator: JAX's first device is {platform!r}", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"cell needs {chips} chips, JAX sees {len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": platform, "kind": devs[0].device_kind, "count": chips}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = resolve(args.workload)
    caller_asked_cpu = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    # The TPU runtime pins a staging buffer of host memory when it starts: 4 GiB
    # by default, 7 to 11 s of a one-chip start, and seconds apart from one
    # machine to the next. The mix states the size its transfers need; it has to
    # be in the environment before the runtime loads. A caller's own value stands.
    if "host_transfer_buffer_bytes" in traffic:
        os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(int(traffic["host_transfer_buffer_bytes"])))

    phases, mark = {}, _T0

    def phase(name):
        nonlocal mark
        now = time.perf_counter()
        phases[name], mark = round(now - mark, 3), now

    import jax
    phase("import_jax")
    device = device_block(cell["chips"], caller_asked_cpu)
    phase("runtime_start")  # jax.devices(): the TPU runtime comes up
    from openembedding_tpu.utils import compile_cache
    compile_cache.enable()  # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache/<platform>

    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])
    session = driver.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=args.seed)
    phase("import_program")
    session.setup()
    phases.update(session.phases)
    print("setup phases (s): " + json.dumps(phases), file=sys.stderr)

    trace_dir = None
    seconds = args.seconds
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        seconds = min(seconds, float(traffic["trace_seconds"]))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win = session.window(seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    setup_s = win["t0"] - _T0
    device["memory_peak_bytes"] = session.peak_bytes()
    run_ctx = session.context()
    run_ctx["memory_peak_bytes"] = device["memory_peak_bytes"] or None  # a CPU reports none
    session.free()

    verdict = session.check(load(f"limits/{args.workload}.json"))

    result = {"correct": verdict["correct"], "attempted": session.attempted,
              "failed": session.failed, "metrics": {}, "device": device}
    e2e = metrics_of(bench, "end_to_end", args.workload, set())
    values = {"setup_s": setup_s, **win["end_to_end"]}
    if args.trace:
        from benchmark import trace_reduce
        summary = None
        if device["platform"] == "tpu":
            with open(os.path.join(HERE, "peaks.json")) as f:
                peaks = json.load(f)
            if device["kind"] not in peaks:
                raise SystemExit(f"device_kind {device['kind']!r} is not in peaks.json")
            summary = trace_reduce.reduce_dir(trace_dir, chips=cell["chips"], window_s=win["seconds"])
            run_ctx["peaks"] = peaks[device["kind"]]
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
            result["breakdown"] = {"device_ops": summary["top_ops"][:10],
                                   "idle_gaps": summary["idle_gaps"][:10]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        run_ctx.update(win)
        reports = {m["name"] for m in e2e}
        for m in metrics_of(bench, "per_layer", args.workload, reports):
            spec = load(f"layer_metrics/{m['name']}.json")
            reader = importlib.import_module("benchmark.readers." + spec["reader"])
            if m["source"] == "device_trace" and summary is None:
                continue  # a CPU rehearsal has no device trace: nothing to read
            value = reader.read(summary, run_ctx, spec.get("params", {}))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["compared"] = verdict["compared"]
    sys.stdout.flush()
    for name, c in verdict["compared"].items():
        print(f"compared {name} = {c['value']:.6g} (limit {c['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
