"""Online-sync soak: train-and-persist in a thread while a subscriber-backed
serving node answers predicts; assert freshness and zero failed predicts.

One process, three actors (the CI-sized version of the production topology):

  trainer thread   — Trainer + IncrementalPersister: full base at the first
                     persist, then one committed delta every `persist_every`
                     steps into the persist root;
  publisher node   — serving HTTP server whose SyncPublisher feeds that root
                     (`GET /models/<sign>:versions`, `/delta/<step>/...`);
  serving node     — a second HTTP server that loaded the base export, with a
                     SyncSubscriber polling the feed and RCU-swapping the
                     servable, while `predict_threads` hammer /predict.

Asserted at exit: zero failed predicts across every swap, the subscriber
ended IDLE at the trainer's final committed step (version lag 0), and at
least K swaps actually happened (the soak is vacuous without them). The
short configuration rides tier-1 via tests/test_sync.py::test_sync_soak_short;
`python tools/sync_soak.py` runs the longer standalone battery.
"""

import argparse
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _check_timeline(urls, *, version, log):
    """Scrape the soak nodes' /timelinez through tools/fleet_timeline and
    assert delta `version`'s commit->publish->fetch->apply->swap->
    first-predict chain merges contiguous and correctly ordered. Retries the
    scrape briefly: first-predict lands on the hammer's first post-swap hit,
    a few ms after the drain loop saw the version flip."""
    from tools import fleet_timeline as ftl
    want_full = ("birth", "commit", "publish", "fetch", "apply", "swap",
                 "first_predict")
    labels, ts, items = [], [], []
    deadline = time.monotonic() + 10
    while True:
        nodes_data = []
        for u in urls:
            doc, offset = ftl.probe(u, probes=3)
            nodes_data.append((doc.get("node") or u, doc, offset))
        items = ftl.merge(nodes_data)
        # both soak nodes live in ONE process and share the lineage book
        # (same node id, two scrape offsets), so the merged view carries the
        # chain twice: judge ONE node's copy of it
        chain = [it for it in ftl.merge(nodes_data[-1:])
                 if it["kind"] == "DELTA" and it.get("step") == version]
        labels = [it["what"].split()[1] for it in chain]
        ts = [it["ts"] for it in chain]
        if "first_predict" in labels or time.monotonic() >= deadline:
            break
        time.sleep(0.1)
    want = [l for l in want_full if l in labels]
    ok = (labels == want
          and {"commit", "publish", "fetch", "apply", "swap",
               "first_predict"} <= set(labels)
          and all(a <= b for a, b in zip(ts, ts[1:])))
    log(f"timeline chain for delta {version}: {labels} ok={ok}")
    assert ok, {"timeline_chain": labels, "version": version}
    return {"merged_items": len(items), "chain": labels, "chain_ok": ok}


def run(*, steps=24, persist_every=2, interval_s=0.05, workdir="/tmp/oetpu_sync_soak",
        predict_threads=4, wire="fp32", vocab=1 << 10, batch=16, dim=4,
        lag_bound_steps=None, step_delay_s=0.0, quiet=False,
        metrics_log=None, sentinel=True, measure_every=8,
        stall_s=0.0, stall_after_frac=0.4, freshness_threshold_ms=None,
        timeline=False):
    """-> report dict (see asserts at the bottom). Raises AssertionError when
    the soak's invariants break. The report carries the SLO verdicts
    (`utils/slo.DEFAULT_SLOS` judged once at exit over everything the soak
    observed — predict latency, sync freshness, sentinel numerics) and
    `slo_exit_code`, which `main()` adopts as the process exit status.

    `stall_s > 0` runs the CAUSALITY acceptance scenario: once the trainer
    passes `stall_after_frac` of its steps, the publisher's delta PAYLOADS
    are withheld for `stall_s` seconds (the feed keeps advancing, so the
    subscriber sees an ever-older head birth and `sync.freshness_ms` grows)
    — the `serving_freshness` SLO (threshold `freshness_threshold_ms`,
    default stall_s/2) must flip to BREACHED mid-run with the stalled hop
    dominating `sync.hop_ms{hop="fetch"}`, then recover to OK once the
    stall lifts and a post-stall delta lands. `timeline=True` additionally
    scrapes both nodes' /timelinez pre-shutdown and asserts the last
    delta's commit->publish->fetch->apply->swap->first-predict chain merges
    contiguous and correctly ordered (`tools/fleet_timeline.py`)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.export import export_standalone
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.persist import IncrementalPersister, PersistPolicy
    from openembedding_tpu.serving import make_server
    from openembedding_tpu.sync import SyncSubscriber

    def log(msg):
        if not quiet:
            print(f"[sync_soak] {msg}", flush=True)

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    root = os.path.join(workdir, "persist")
    sign = "soak-0"

    model = make_deepfm(vocabulary=vocab, dim=dim, hidden=(8,))
    # sentinel + sampled measurement on by default: the soak IS the
    # production-day rehearsal, so it trains with the health rails it gates on
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                      sentinel=sentinel, measure_every=measure_every)
    batches = list(synthetic_criteo(batch, id_space=vocab, steps=steps,
                                    seed=1))
    reporter = None
    if metrics_log:
        from openembedding_tpu.utils.metrics import PeriodicReporter
        # reset=False: the soak judges the DEFAULT_SLOS over the whole run at
        # exit, and a resetting reporter would zero counter windows (e.g.
        # health.nonfinite_total) back to never-observed -> verdict UNKNOWN
        reporter = PeriodicReporter(max(interval_s, 0.5),
                                    sink=lambda _s: None, reset=False,
                                    jsonl_path=metrics_log).start()
    state = trainer.init(batches[0])
    # the soak's paced trainer must never re-jit across the run: identical
    # batch shapes -> one compiled program, asserted at every step
    # (utils/guards — the executable half of the never-re-jit rule), and
    # the traced collective SEQUENCE is pinned at start and re-asserted at
    # the end (the SPMD-contract half: no refresh/sync path may change
    # which collectives run, in what order)
    from openembedding_tpu.utils.guards import (assert_collective_fingerprint,
                                                assert_no_recompile,
                                                collective_fingerprint)
    raw_step = trainer.jit_train_step()
    step_fn = assert_no_recompile(raw_step, label="soak_train_step")
    collective_pin = collective_fingerprint(raw_step, state, batches[0])

    persister = IncrementalPersister(
        trainer, model, root, window=2,
        policy=PersistPolicy(every_steps=persist_every), full_every=10_000)
    # base: FORCE the first persist (the full anchor) at step 1 — serving
    # starts from an export of this exact chain step, whatever the policy says
    state, _ = step_fn(state, batches[0])
    persister.observe(batches[0])
    persister.persist(state)
    persister.wait()
    export_dir = os.path.join(workdir, "export")
    export_standalone(state, model, export_dir, model_sign=sign)

    pub_srv = make_server(os.path.join(workdir, "reg_pub"),
                          publish={sign: root}, publish_wire=wire)
    threading.Thread(target=pub_srv.serve_forever, daemon=True).start()
    pub_url = f"http://127.0.0.1:{pub_srv.server_address[1]}"
    srv = make_server(os.path.join(workdir, "reg_srv"))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    srv_url = f"http://127.0.0.1:{srv.server_address[1]}"
    srv.manager.load_model(sign, export_dir)
    log(f"publisher {pub_url} feeds {root}; serving node {srv_url}")

    # tight backoff cap when a stall is planned: the DEGRADED retry loop
    # must re-probe fast enough to recover within the post-stall drain
    sub = SyncSubscriber(srv.manager, sign, pub_url, wire=wire,
                         interval_s=interval_s,
                         max_backoff_s=max(4 * interval_s, 0.25)
                         if stall_s > 0 else 30.0)

    from openembedding_tpu.utils import slo
    prior_specs = slo.EVALUATOR.specs
    if stall_s > 0:
        # re-anchor serving_freshness to the soak's scale: the stock 30s
        # threshold would never trip on a CI-sized stall
        thr = float(freshness_threshold_ms
                    if freshness_threshold_ms is not None
                    else stall_s * 500.0)
        specs = [s for s in prior_specs if s.name != "serving_freshness"]
        specs.append(slo.SLOSpec(
            name="serving_freshness", metric="sync.freshness_ms",
            selector="value", op="<=", threshold=thr, fast_window_s=0.0,
            slow_window_s=300.0, burn_threshold=1e-9,
            description=f"soak-scaled freshness bound ({thr:.0f}ms)"))
        slo.configure(specs)

    # predict hammer: live traffic across every swap
    stop = threading.Event()
    counts = {"ok": 0, "fail": 0}
    lock = threading.Lock()
    body = json.dumps({
        "sparse": {"categorical":
                   np.asarray(batches[0]["sparse"]["categorical"]).tolist()},
        "dense": np.asarray(batches[0]["dense"]).tolist()}).encode()

    def hammer():
        url = f"{srv_url}/models/{sign}/predict"
        while not stop.is_set():
            req = urllib.request.Request(
                url, data=body, method="POST",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    ok = r.status == 200
            except Exception:  # noqa: BLE001 — any failure counts
                ok = False
            with lock:
                counts["ok" if ok else "fail"] += 1

    # warm the predict program before the clock starts (compile != failure)
    srv.manager.find_model(sign).predict(batches[0])
    hammers = [threading.Thread(target=hammer, daemon=True)
               for _ in range(predict_threads)]
    for t in hammers:
        t.start()

    trained = {"step": 1}
    train_done = threading.Event()

    def train():
        s = state
        for b in batches[1:]:
            s, mets = step_fn(s, b)
            trainer.record_step_stats(mets)
            persister.maybe_persist(s, batch=b)
            trained["step"] = int(s.step)
            if step_delay_s > 0:  # emulate a real per-step training cadence
                time.sleep(step_delay_s)
        persister.wait()
        train_done.set()

    max_lag = 0
    stall = {"on": False, "done": stall_s <= 0, "orig": None,
             "denied": 0, "first_deny": None}
    stall_after_step = max(2, int(steps * stall_after_frac))
    slo_track = {"breached": False, "recovered": False}

    def _slo_tick():
        v = {x["name"]: x["verdict"]
             for x in slo.EVALUATOR.evaluate_now()}.get("serving_freshness")
        if v == "BREACHED":
            slo_track["breached"] = True
        elif v == "OK" and slo_track["breached"]:
            slo_track["recovered"] = True

    def _stall_tick():
        # withhold delta PAYLOADS, not the feed: the head keeps advancing,
        # so the subscriber sees an ever-older unapplied birth (freshness
        # grows) while its payload fetches 404 into DEGRADED retries —
        # which is exactly the time the `fetch` hop is defined to absorb
        pub = pub_srv.publishers[sign]
        if (not stall["done"] and not stall["on"]
                and trained["step"] >= stall_after_step):
            stall["orig"] = pub.delta_meta

            def _withheld(step):
                # the stall window is anchored to the FIRST fetch actually
                # denied — a wall-clock window could race the training pace
                # and cover no delta at all
                if stall["first_deny"] is None:
                    stall["first_deny"] = time.monotonic()
                stall["denied"] += 1
                raise KeyError(f"soak stall: delta {step} payload withheld")

            pub.delta_meta = _withheld
            stall["on"] = True
            log(f"stall ON at step {trained['step']}: withholding payloads "
                f"for {stall_s}s past the first denied fetch")
        elif stall["on"] and (train_done.is_set()
                              or (stall["first_deny"] is not None
                                  and time.monotonic()
                                  >= stall["first_deny"] + stall_s)):
            pub.delta_meta = stall["orig"]
            stall["on"], stall["done"] = False, True
            log(f"stall OFF after {stall['denied']} denied fetches")

    t0 = time.monotonic()
    trainer_thread = threading.Thread(target=train, daemon=True)
    trainer_thread.start()
    sub.start()
    timeline_report = None
    try:
        while not train_done.is_set():
            time.sleep(interval_s)
            max_lag = max(max_lag, trained["step"] - (sub.version or 1))
            if stall_s > 0:
                _stall_tick()
                _slo_tick()
        if stall["on"]:
            _stall_tick()  # training ended first: force the stall off
        # drain: let the subscriber reach the final committed step
        deadline = time.monotonic() + 60
        final = trained["step"] - (trained["step"] - 1) % persist_every
        while (sub.version or 0) < final and time.monotonic() < deadline:
            time.sleep(interval_s)
            if stall_s > 0:
                _slo_tick()
        if stall_s > 0:
            # settle: a post-stall delta's fresh sample must re-judge OK
            settle = time.monotonic() + 10
            while not slo_track["recovered"] and time.monotonic() < settle:
                _slo_tick()
                time.sleep(interval_s)
        if timeline:
            timeline_report = _check_timeline([pub_url, srv_url],
                                              version=sub.version, log=log)
    finally:
        sub.stop()
        stop.set()
        for t in hammers:
            t.join(timeout=10)
        trainer_thread.join(timeout=60)
        persister.close()
        pub_srv.shutdown()
        srv.shutdown()
        if reporter is not None:
            reporter.stop()  # flushes the final JSONL record

    # the collective program must be exactly what we pinned before the run
    # (same shapes, same axes, same order) — raises CollectiveMismatchError
    assert_collective_fingerprint(raw_step, collective_pin, state,
                                  batches[0], label="soak_train_step")

    report = {
        "collective_fingerprint": collective_pin,
        "steps": trained["step"],
        "persist_every": persist_every,
        "wire": wire,
        "swaps": sub.applied,
        "final_version": sub.version,
        "final_committed": final,
        "final_lag_steps": final - (sub.version or 0),
        "max_observed_lag_steps": max_lag,
        "predicts": counts["ok"] + counts["fail"],
        "failed_predicts": counts["fail"],
        "subscriber_state": sub.state,
        "last_error": sub.last_error,
        "wall_s": round(time.monotonic() - t0, 2),
    }
    # the SLO gate: judge everything the soak observed (predict latency
    # hists, sync freshness gauges, sentinel numerics) against the stock
    # objectives — the process-exit verdict main() adopts
    verdicts = slo.EVALUATOR.evaluate_now()
    report["slo"] = {v["name"]: v["verdict"] for v in verdicts}
    report["slo_exit_code"] = slo.EVALUATOR.exit_code()
    log("SLOs:\n" + slo.EVALUATOR.render_text())
    if stall_s > 0:
        slo.configure(prior_specs)  # un-shadow the stock serving_freshness
        # stalled-hop attribution: the max over each sync.hop_ms{hop=} hist —
        # the withheld-payload window is DEGRADED retry time, which the
        # `fetch` hop is defined to absorb, so fetch must dominate
        from openembedding_tpu.utils import metrics as metrics_mod
        with metrics_mod._LOCK:
            hop_max = {a.labels.get("hop", "?"): a.hist_snapshot()[4]
                       for a in metrics_mod._REGISTRY.values()
                       if a.name == "sync.hop_ms" and a.count}
        stalled_hop = max(hop_max, key=hop_max.get) if hop_max else None
        report["freshness_breached"] = slo_track["breached"]
        report["freshness_recovered"] = slo_track["recovered"]
        report["hop_max_ms"] = {k: round(v, 3) for k, v in hop_max.items()}
        report["stalled_hop"] = stalled_hop
    if timeline_report is not None:
        report["timeline"] = timeline_report
    log(json.dumps(report, indent=2))
    assert report["failed_predicts"] == 0, report
    assert report["final_lag_steps"] == 0, report
    assert report["swaps"] >= 1, report
    if lag_bound_steps is not None:
        assert max_lag <= lag_bound_steps, report
    if stall_s > 0:
        assert report["freshness_breached"], report
        assert report["freshness_recovered"], report
        assert report["stalled_hop"] == "fetch", report
    return report


#: the soak topology's actors, as oeweave scenarios: subscriber state
#: machine + its lineage bookkeeping, serving batcher, persister, reporter
WEAVE_SCENARIOS = ("sync_subscriber", "sync_lineage", "micro_batcher",
                   "async_persister", "periodic_reporter")


def run_weave(*, schedules=8, sweep=12, seed=0, quiet=False):
    """Deterministic-interleaving variant of the soak: instead of racing the
    real actors against the OS scheduler for wall-clock seconds, explore
    seeded-random + preemption-bounded schedules of the same components
    under tools/oeweave and fail on ANY schedule that breaks an invariant
    (torn status, lost wakeup, double apply, leaked thread). Returns a
    report dict; raises AssertionError listing replay tokens on failure."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from openembedding_tpu.utils import metrics
    from tools.oeweave import explore as weave_explore
    from tools.oeweave import scenarios as weave_scenarios

    def log(msg):
        if not quiet:
            print(f"[sync_soak --weave] {msg}", flush=True)

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
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--persist-every", type=int, default=2)
    ap.add_argument("--interval-s", type=float, default=0.05)
    ap.add_argument("--predict-threads", type=int, default=4)
    ap.add_argument("--wire", default="fp32")
    ap.add_argument("--workdir", default="/tmp/oetpu_sync_soak")
    ap.add_argument("--lag-bound-steps", type=int, default=None,
                    help="fail if observed version lag ever exceeds this "
                         "(only meaningful with --step-delay-s pacing the "
                         "trainer slower than the subscriber poll)")
    ap.add_argument("--step-delay-s", type=float, default=0.0,
                    help="sleep per train step, emulating a real step time "
                         "so version lag is measurable")
    ap.add_argument("--metrics-log", default=None, metavar="PATH",
                    help="append periodic accumulator reports (and a final "
                         "snapshot) as timestamped JSONL records to PATH")
    ap.add_argument("--stall-s", type=float, default=0.0,
                    help="withhold publisher delta payloads for this many "
                         "seconds mid-run (the causality acceptance "
                         "scenario: serving_freshness must flip BREACHED "
                         "with the fetch hop dominating, then recover)")
    ap.add_argument("--stall-after-frac", type=float, default=0.4,
                    help="engage the stall once the trainer passes this "
                         "fraction of its steps")
    ap.add_argument("--freshness-threshold-ms", type=float, default=None,
                    help="soak-scaled serving_freshness threshold while "
                         "stalling (default stall_s/2 in ms)")
    ap.add_argument("--timeline", action="store_true",
                    help="scrape both nodes' /timelinez pre-shutdown and "
                         "assert the last delta's lineage chain merges "
                         "contiguous and ordered (tools/fleet_timeline)")
    ap.add_argument("--no-slo-gate", action="store_true",
                    help="report SLO verdicts but exit 0 regardless "
                         "(default: exit with the SLO verdict — 0 all OK, "
                         "1 breached, 2 unknown)")
    ap.add_argument("--weave", action="store_true",
                    help="run the deterministic-interleaving variant "
                         "(tools/oeweave over the soak's actors) instead "
                         "of the wall-clock soak")
    ap.add_argument("--weave-schedules", type=int, default=8,
                    help="random schedules per scenario with --weave")
    ap.add_argument("--weave-sweep", type=int, default=12,
                    help="preemption-sweep schedules per scenario")
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
    report = run(steps=args.steps, persist_every=args.persist_every,
                 interval_s=args.interval_s,
                 predict_threads=args.predict_threads, wire=args.wire,
                 workdir=args.workdir, lag_bound_steps=args.lag_bound_steps,
                 step_delay_s=args.step_delay_s,
                 metrics_log=args.metrics_log, stall_s=args.stall_s,
                 stall_after_frac=args.stall_after_frac,
                 freshness_threshold_ms=args.freshness_threshold_ms,
                 timeline=args.timeline)
    print(json.dumps(report))
    return 0 if args.no_slo_gate else report["slo_exit_code"]


if __name__ == "__main__":
    sys.exit(main())
