"""Line-rate ingest soak: find the compute ceiling, feed the pipelined
train loop through the depth-D device feed ring at that rate, and prove the
input-wait attribution lane points the right way.

One process, three measured phases over the same compiled window program
(the CI-sized version of the v5e-64 line-rate question):

  ceiling    — pre-staged windows, min ms/step: what the device mesh can
               absorb with input off the books (examples/s/chip);
  line rate  — `MeshTrainer.train_stream` fed by `data.ingest.feed`
               (per-host sharded synthetic "days" -> parse pool -> depth-D
               ring): the measured `ingest.input_wait_share` must stay under
               the tools/ingest_slo.json gate (< 5% — compute-bound);
  throttled  — the SAME loop behind a producer deliberately paced at 2x the
               measured ceiling: the share must now read input-bound (the
               control that proves the lane attributes, not flatters).

Asserted at exit: the line-rate SLO verdict (adopted as the process exit
code unless --no-slo-gate), the throttled control's inverted attribution,
and a pooled-vs-inline reader bit-identity spot check (the determinism the
reorder stage promises). The short configuration rides tier-1 via
tests/test_ingest.py; `python tools/ingest_soak.py` runs the longer
standalone battery. `--weave` explores the feed ring's and parse pool's
interleavings under tools/oeweave instead.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SLO_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ingest_slo.json")


def run(*, windows=6, window=8, batch=512, vocab=1 << 13, depth=3,
        workers=2, devices=8, throttle_factor=2.0, quiet=False):
    """-> report dict (see asserts at the bottom). Raises AssertionError when
    the soak's invariants break. The report carries the line-rate SLO
    verdicts (tools/ingest_slo.json judged over the line-rate phase only —
    the throttled phase deliberately breaches, that's its job) and
    `slo_exit_code`, which `main()` adopts as the process exit status."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    import openembedding_tpu as embed
    from openembedding_tpu.data import ingest
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.utils import metrics, slo

    def log(msg):
        if not quiet:
            print(f"[ingest_soak] {msg}", flush=True)

    devs = jax.devices()
    S = min(devices, len(devs))
    mesh = make_mesh(devs[:S])
    files = [f"synthetic://steps={windows * window // 2}&seed={7 + s}"
             f"&id_space={vocab}" for s in range(2)]

    def ring(label, throttle_s=0.0, d=depth):
        return ingest.feed(files, batch, mesh=mesh, source="synthetic",
                           depth=d, window=window, workers=workers,
                           label=label, throttle_s=throttle_s)

    # determinism spot check: the parse pool's reorder stage must be
    # bit-identical to the inline reader over the same sharded file list
    inline = list(ingest.sharded_reader(files, batch, source="synthetic",
                                        host_id=0, num_hosts=1))
    pooled = list(ingest.sharded_reader(files, batch, source="synthetic",
                                        host_id=0, num_hosts=1,
                                        workers=workers))
    reader_identical = len(inline) == len(pooled) and all(
        np.array_equal(a["sparse"]["categorical"],
                       b["sparse"]["categorical"])
        and np.array_equal(a["dense"], b["dense"])
        for a, b in zip(inline, pooled))

    model = make_deepfm(vocabulary=vocab, dim=9)
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh,
                     capacity_factor=0.0, wire="fp32", pipeline_steps=True)

    # phase 1: the compute ceiling (input off the books)
    metrics._REGISTRY.clear()
    staged = list(ring("stage"))
    first = jax.tree_util.tree_map(lambda x: np.asarray(x[0]), staged[0])
    state = tr.init(first)
    many = tr.jit_train_many(staged[0], state)
    times = []
    for i, w in enumerate(staged):
        t0 = time.perf_counter()
        state, m = many(state, w)
        jax.block_until_ready((state, m))
        if i:
            times.append((time.perf_counter() - t0) / window)
    ceiling_s = min(times)
    log(f"compute ceiling: {ceiling_s * 1e3:.2f} ms/step "
        f"({batch / ceiling_s / S:.0f} examples/s/chip on {S} devices)")

    # phase 2: ring-fed at line rate — the SLO-gated run
    metrics._REGISTRY.clear()
    t0 = time.perf_counter()
    state, rep = tr.train_stream(state, ring("line"))
    elapsed = time.perf_counter() - t0
    share = ingest.input_wait_share()
    evaluator = slo.SLOEvaluator(specs=slo.load_specs(SLO_PATH))
    verdicts = evaluator.evaluate_now()
    slo_exit = evaluator.exit_code()
    log("line-rate SLOs:\n" + evaluator.render_text())

    # phase 3: the throttled control — same loop, producer paced at
    # throttle_factor x the measured ceiling, must read input-bound
    metrics._REGISTRY.clear()
    state, trep = tr.train_stream(
        state, ring("slow", throttle_s=throttle_factor * ceiling_s, d=1))
    tshare = ingest.input_wait_share()

    report = {
        "num_shards": S,
        "batch": batch,
        "window": window,
        "windows": rep["windows"],
        "compute_ms_per_step": round(ceiling_s * 1e3, 3),
        "compute_ceiling_eps_per_chip": round(batch / ceiling_s / S, 1),
        "line_rate": {
            "examples_per_sec_per_chip": round(
                rep["windows"] * window * batch / elapsed / S, 1),
            "input_wait_share": share,
            "loss": rep["loss"],
        },
        "throttled": {
            "windows": trep["windows"],
            "input_wait_share": tshare,
        },
        "reader_pool_bit_identical": reader_identical,
        "slo": {v["name"]: v["verdict"] for v in verdicts},
        "slo_exit_code": slo_exit,
    }
    log(json.dumps(report, indent=2))
    assert reader_identical, report
    assert share is not None and tshare is not None, report
    assert tshare > 0.25, (
        f"throttled producer not attributed input-bound: {tshare}", report)
    assert tshare > share, report
    return report


#: the feed path's actors, as oeweave scenarios: ring producer/consumer/
#: close interleavings and the parse pool's reorder stage
WEAVE_SCENARIOS = ("feed_ring", "parse_pool")


def run_weave(*, schedules=8, sweep=12, seed=0, quiet=False):
    """Deterministic-interleaving variant: explore seeded-random +
    preemption-bounded schedules of the ring and pool under tools/oeweave
    and fail on ANY schedule that breaks an invariant (out-of-order
    delivery, lost fault, leaked thread). Returns a report dict; raises
    AssertionError listing replay tokens on failure."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from openembedding_tpu.utils import metrics
    from tools.oeweave import explore as weave_explore
    from tools.oeweave import scenarios as weave_scenarios

    def log(msg):
        if not quiet:
            print(f"[ingest_soak --weave] {msg}", flush=True)

    weave_scenarios.warm()
    report = {"scenarios": {}, "schedules_explored": 0, "failures": 0}
    for name in WEAVE_SCENARIOS:
        res = weave_explore.explore(
            weave_scenarios.SCENARIOS[name],
            random_schedules=schedules, seed=seed,
            preemption_schedules=sweep)
        report["scenarios"][name] = {
            "explored": res.schedules_explored,
            "truncated": res.truncated,
            "failures": [{"kind": f.kind, "error": f.error,
                          "token": f.token} for f in res.failures],
        }
        report["schedules_explored"] += res.schedules_explored
        report["failures"] += len(res.failures)
        log(f"{name}: {res.schedules_explored} schedules, "
            f"{len(res.failures)} failures")
    metrics.observe("weave.schedules_explored",
                    float(report["schedules_explored"]))
    metrics.observe("weave.failures", float(report["failures"]))
    assert report["failures"] == 0, (
        "weave found failing interleavings — replay with "
        "`python -m tools.oeweave <scenario> --replay <scenario>:<token>`: "
        + json.dumps(report["scenarios"]))
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--window", type=int, default=8,
                    help="steps per compiled window (the scan K)")
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=1 << 13)
    ap.add_argument("--depth", type=int, default=3,
                    help="feed-ring depth (resident windows staged ahead)")
    ap.add_argument("--workers", type=int, default=2,
                    help="parse-pool workers")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--throttle-factor", type=float, default=2.0,
                    help="throttled-control producer pace as a multiple of "
                         "the measured per-step compute ceiling")
    ap.add_argument("--no-slo-gate", action="store_true",
                    help="report SLO verdicts but exit 0 regardless "
                         "(default: exit with the line-rate SLO verdict — "
                         "0 OK, 1 breached, 2 unknown)")
    ap.add_argument("--weave", action="store_true",
                    help="explore the ring/pool interleavings under "
                         "tools/oeweave instead of the wall-clock soak")
    ap.add_argument("--weave-schedules", type=int, default=8)
    ap.add_argument("--weave-sweep", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.weave:
        try:
            report = run_weave(schedules=args.weave_schedules,
                               sweep=args.weave_sweep, seed=args.seed)
        except AssertionError as e:
            print(e)
            return 1
        print(json.dumps(report))
        return 0
    report = run(windows=args.windows, window=args.window, batch=args.batch,
                 vocab=args.vocab, depth=args.depth, workers=args.workers,
                 devices=args.devices, throttle_factor=args.throttle_factor)
    print(json.dumps(report))
    return 0 if args.no_slo_gate else report["slo_exit_code"]


if __name__ == "__main__":
    sys.exit(main())
