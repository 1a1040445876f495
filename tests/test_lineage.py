"""Fleet-causality tests: cross-process trace stitching over two live nodes,
delta lineage hop decomposition under injected delays, skew-corrected fleet
timeline ordering on deliberately skewed fake clocks, the freshness-SLO
breach/recover soak end to end, and the capsule lineage round-trip."""

import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.export import export_standalone
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.persist import IncrementalPersister, PersistPolicy
from openembedding_tpu.serving import make_server
from openembedding_tpu.sync import SyncPublisher, SyncSubscriber, lineage
from openembedding_tpu.utils import metrics, trace

VOCAB = 512

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh():
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()
    lineage.BOOK.clear()
    yield
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()
    lineage.BOOK.clear()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def serving_node(tmp_path):
    srv = make_server(str(tmp_path / "reg_srv"))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", srv
    for sub in srv.subscribers.values():
        sub.stop()
    srv.shutdown()


@pytest.fixture()
def publisher_node(tmp_path):
    srv = make_server(str(tmp_path / "reg_pub"))
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", srv
    srv.shutdown()


def _req(url, method="GET", payload=None, headers=None):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json",
                                          **(headers or {})})
    try:
        with urllib.request.urlopen(req) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        body = e.read()
        return e.code, (json.loads(body) if body else {}), dict(e.headers)


# -- trace context + cross-process stitching ----------------------------------


def test_trace_context_header_roundtrip():
    """TraceContext serializes to the X-OETPU-Trace header value and back,
    with and without a parent span; extract falls back to the bare
    request-id header for pre-upgrade callers."""
    ctx = trace.TraceContext("rid-1", f"{trace.PROCESS_ID}:abc123")
    back = trace.TraceContext.from_header(ctx.to_header())
    assert (back.trace_id, back.parent_span) == (ctx.trace_id,
                                                 ctx.parent_span)
    bare = trace.TraceContext.from_header("rid-2")
    assert bare.trace_id == "rid-2" and bare.parent_span is None
    legacy = trace.extract_context({trace.REQUEST_ID_HEADER: "rid-3"})
    assert legacy.trace_id == "rid-3" and legacy.parent_span is None
    assert trace.extract_context({}) is None

    with trace.request("rid-4"):
        with trace.span("sync", "caller") as sp:
            cur = trace.TraceContext.current()
            assert cur.trace_id == "rid-4"
            assert cur.parent_span == f"{trace.PROCESS_ID}:{sp.span_id}"
            hdrs = trace.inject_headers()
    assert hdrs[trace.REQUEST_ID_HEADER] == "rid-4"
    assert hdrs[trace.TRACE_HEADER] == cur.to_header()


def test_cross_process_stitching_over_live_node(serving_node, tmp_path,
                                                capsys):
    """A caller span's injected X-OETPU-Trace header makes the serving
    node's http span a REMOTE child of the caller: same trace id, the
    caller's qualified span uid recorded as remote_parent, and
    tools/trace_report --trace renders the stitched tree with the hop
    marked."""
    base, srv = serving_node
    with trace.request("stitch-1"):
        with trace.span("sync", "caller") as caller:
            req = urllib.request.Request(f"{base}/healthz",
                                         headers=trace.inject_headers())
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
                # the serving node adopted the caller's trace id as its rid
                assert resp.headers["X-OETPU-Request-Id"] == "stitch-1"

    # the http span closes (and records) just AFTER the response body is
    # written, so reading the recorder immediately can race it — poll briefly
    deadline = time.time() + 5.0
    while True:
        http = next((s for s in trace.RECORDER.spans()
                     if s.name == "http" and s.trace_id == "stitch-1"), None)
        if http is not None:
            break
        assert time.time() < deadline, trace.RECORDER.spans()
        time.sleep(0.01)
    assert http.remote_parent == f"{trace.PROCESS_ID}:{caller.span_id}"
    assert http.parent_id is None  # root locally, child across the wire

    path = str(tmp_path / "stitched.json")
    trace.dump_chrome(path)
    tr = _load_tool("trace_report")
    assert tr.main([path, "--trace", "stitch-1"]) == 0
    out = capsys.readouterr().out
    assert "sync.caller" in out and "serving.http" in out
    assert "<-remote" in out
    # the http line is indented under the caller line
    lines = out.splitlines()
    caller_i = next(i for i, l in enumerate(lines) if "sync.caller" in l)
    http_l = next(l for l in lines if "serving.http" in l)
    assert http_l.startswith("  ") and not lines[caller_i].startswith(" ")


# -- hop decomposition --------------------------------------------------------


def test_hop_decomposition_with_injected_fetch_delay(tmp_path, publisher_node,
                                                     serving_node):
    """An artificially slow delta-payload serve lands on the FETCH hop of
    the applied delta's lineage record (not apply/swap), the record carries
    every hop of the chain, and the first predict at the version closes it
    with a serve hop."""
    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=4, seed=1))
    state = trainer.init(batches[0])
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    pub_url, pub_srv = publisher_node
    srv_url, srv = serving_node

    with IncrementalPersister(trainer, model, root, window=2,
                              policy=PersistPolicy(every_steps=1),
                              full_every=100) as p:
        state, _ = step(state, batches[0])
        p.maybe_persist(state, batch=batches[0])
        p.wait()
        export_dir = str(tmp_path / "export")
        export_standalone(state, model, export_dir, model_sign="lin-0")
        pub_srv.publishers["lin-0"] = SyncPublisher(root)
        srv.manager.load_model("lin-0", export_dir)

        sub = SyncSubscriber(srv.manager, "lin-0", pub_url)
        assert sub.poll() == 0 and sub.version == 1

        pub = pub_srv.publishers["lin-0"]
        orig = pub.delta_table
        slept = []

        def slow_table(*a, **kw):
            t0 = time.time()
            time.sleep(0.25)
            slept.append((t0, time.time()))
            return orig(*a, **kw)

        pub.delta_table = slow_table
        state, _ = step(state, batches[1])
        p.maybe_persist(state, batch=batches[1])
        p.wait()
        assert sub.poll() == 1, sub.last_error

    st = sub.status()
    lh = st["last_hops"]
    assert lh is not None and lh["step"] == 2
    hops = lh["hops"]
    assert {"commit", "publish", "fetch", "apply", "swap"} <= set(hops)
    assert hops["fetch"] >= 200.0, hops  # the injected delay lands here
    # end-to-end freshness covers at least the stalled fetch
    assert st["freshness_ms"] is not None and st["freshness_ms"] >= 200.0

    rec = lineage.BOOK.get("lin-0", 2)
    assert rec is not None
    for stamp in ("birth", "commit", "seen", "fetched", "applied", "swapped"):
        assert rec.get(stamp) is not None, (stamp, rec)
    # birth -> ... -> swapped is non-decreasing within one clock domain pair
    assert rec["seen"] <= rec["fetched"] <= rec["applied"] <= rec["swapped"]
    # ... and on NO other hop: every injected sleep lies inside the fetch
    # hop's own window (stamps of one clock; how long apply and swap take on
    # a loaded machine says nothing about where the delay went)
    assert slept
    for t0, t1 in slept:
        assert rec["seen"] <= t0 and t1 <= rec["fetched"], (slept, rec)

    body = {"sparse": {"categorical": np.asarray(
        batches[0]["sparse"]["categorical"]).tolist()},
        "dense": np.asarray(batches[0]["dense"]).tolist()}
    status, _, _ = _req(f"{srv_url}/models/lin-0/predict", "POST", body)
    assert status == 200
    rec = lineage.BOOK.get("lin-0", 2)
    assert rec.get("first_serve") is not None
    assert rec["hops"].get("serve") is not None
    # idempotent: a second predict must not move first_serve
    first = rec["first_serve"]
    _req(f"{srv_url}/models/lin-0/predict", "POST", body)
    assert lineage.BOOK.get("lin-0", 2)["first_serve"] == first
    # the hop histogram carries the decomposition with the hop= label
    acc = metrics.Accumulator.get("sync.hop_ms", "hist",
                                  labels={"hop": "fetch"})
    assert acc.count >= 1 and acc.hist_snapshot()[4] >= 200.0


def test_note_clock_ewma():
    sub = SyncSubscriber(manager=None, model_sign="m", feed="http://feed")
    # Cristian: offset = server - (t0+t2)/2; first sample lands directly
    sub._note_clock(100.5, 99.9, 100.1)
    assert abs(sub._clock_offset_s - 0.5) < 1e-9
    # EWMA (alpha 0.3) moves toward a new estimate without jumping
    sub._note_clock(101.5, 99.9, 100.1)  # sample: +1.5
    assert 0.5 < sub._clock_offset_s < 1.5
    assert abs(sub._clock_offset_s - (0.5 + 0.3 * 1.0)) < 1e-9
    assert sub.status()["clock_offset_ms"] == sub._clock_offset_s * 1e3


# -- skew-corrected fleet timeline (pure merge over fake docs) ---------------


def test_fleet_timeline_merge_corrects_deliberate_skew():
    """Two fake nodes, one with a +5s clock: after per-node offset
    correction the merged timeline interleaves causally (the skewed node's
    event does NOT sort 5s late), and a lineage record's publisher-domain
    stamps translate through its own offset_s so the chain stays
    contiguous and non-decreasing."""
    ftl = _load_tool("fleet_timeline")
    t = 1_000_000.0
    skew = 5.0
    # node A's clock reads +5s: every stamp it reports is wall+5, its
    # probe-estimated offset to the scraper is -5
    doc_a = {"events": [
        {"group": "sync", "name": "a_first", "ts": t + 0.10 + skew},
        {"group": "sync", "name": "a_last", "ts": t + 0.40 + skew}],
        "spans": [], "lineage": []}
    # node B is in the scraper's domain; its subscriber estimated the
    # publisher (A) clock offset at +5 (offset_s), so birth/commit below are
    # publisher-domain stamps
    doc_b = {"events": [
        {"group": "sync", "name": "b_mid", "ts": t + 0.25}],
        "spans": [],
        "lineage": [{"sign": "m", "step": 7, "offset_s": skew,
                     "birth": t + 0.05 + skew, "commit": t + 0.12 + skew,
                     "seen": t + 0.20, "fetched": t + 0.28,
                     "applied": t + 0.30, "swapped": t + 0.31,
                     "first_serve": t + 0.33,
                     "hops": {"fetch": 80.0, "apply": 20.0}}]}
    items = ftl.merge([("A", doc_a, -skew), ("B", doc_b, 0.0)])
    whats = [it["what"] for it in items]
    # causal order, not raw-clock order: A's stamps came back by 5s
    assert whats.index("sync.a_first") < whats.index("sync.b_mid")
    assert whats.index("sync.b_mid") < whats.index("sync.a_last")
    chain = [it for it in items if it["kind"] == "DELTA"]
    labels = [it["what"].split()[1] for it in chain]
    assert labels == ["birth", "commit", "publish", "fetch", "apply",
                      "swap", "first_predict"]
    ts = [it["ts"] for it in chain]
    assert all(a <= b for a, b in zip(ts, ts[1:]))
    # corrected birth sits on the scraper axis (skew removed), before seen
    assert abs(chain[0]["ts"] - (t + 0.05)) < 1e-6
    # version filter keeps the chain, drops unrelated events
    only = ftl.filter_items(items, version=7)
    assert {it["kind"] for it in only} == {"DELTA"} and len(only) == 7
    assert "m#7 fetch (80.0ms)" in [it["what"] for it in only]


def test_fleet_timeline_causal_clamp():
    """Residual skew that would reorder a chain (fetch before publish) is
    clamped non-decreasing instead of rendering causal nonsense."""
    ftl = _load_tool("fleet_timeline")
    t = 2_000_000.0
    doc = {"events": [], "spans": [],
           "lineage": [{"sign": "m", "step": 3, "offset_s": -0.050,
                        # commit translates to t+0.060 local — AFTER seen
                        "commit": t + 0.010, "seen": t + 0.040,
                        "fetched": t + 0.045, "swapped": t + 0.047}]}
    items = ftl.merge([("n", doc, 0.0)])
    ts = [it["ts"] for it in items]
    assert all(a <= b for a, b in zip(ts, ts[1:]))
    labels = [it["what"].split()[1] for it in items]
    assert labels == ["commit", "publish", "fetch", "swap"]


# -- the acceptance scenario: stall -> BREACHED -> recover -> OK --------------


def test_freshness_slo_breach_and_recover_e2e(tmp_path):
    """tools/sync_soak.py with an injected publisher stall: the
    serving_freshness SLO flips to BREACHED while delta payloads are
    withheld, the stalled hop is attributed to `fetch` in sync.hop_ms, the
    SLO recovers to OK once a post-stall delta lands, and the merged
    /timelinez timeline renders the last delta's full chain contiguous and
    ordered."""
    from openembedding_tpu.utils import slo
    spec = importlib.util.spec_from_file_location(
        "sync_soak", os.path.join(REPO, "tools", "sync_soak.py"))
    soak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(soak)
    try:
        # a delta is born every 8 steps x 0.3 s = 2.4 s, and while payloads
        # are withheld the served freshness is the age of the feed's NEWEST
        # birth, so it climbs to one such period and no further: the
        # threshold sits between that and what birth -> swap takes on a
        # loaded machine (1.1 s against a period of 1.2 s left no room).
        # The stall lifts 4 s past the first denied fetch (delta 9), about
        # step 22; deltas 25 and 33 are born after it, so the exit verdict
        # reads a delta that never waited
        report = soak.run(steps=34, persist_every=8, interval_s=0.05,
                          step_delay_s=0.3, stall_s=4.0,
                          stall_after_frac=0.15,
                          freshness_threshold_ms=1700.0, timeline=True,
                          workdir=str(tmp_path / "soak"), predict_threads=2,
                          quiet=True)
    finally:
        slo.configure(list(slo.DEFAULT_SLOS))
    assert report["freshness_breached"] is True
    assert report["freshness_recovered"] is True
    assert report["stalled_hop"] == "fetch", report["hop_max_ms"]
    assert report["hop_max_ms"]["fetch"] >= 1000.0, report["hop_max_ms"]
    assert report["slo"]["serving_freshness"] == "OK"  # recovered at exit
    assert report["timeline"]["chain_ok"] is True
    assert report["timeline"]["chain"] == [
        "birth", "commit", "publish", "fetch", "apply", "swap",
        "first_predict"]
    assert report["failed_predicts"] == 0


# -- capsules bundle lineage --------------------------------------------------


def test_capsule_lineage_roundtrip(tmp_path):
    from openembedding_tpu.utils import capsule
    lineage.BOOK.record("cap-0", 9, birth=1.0, swapped=2.0,
                        hops={"fetch": 40.0})
    capsule.configure(str(tmp_path / "caps"))
    try:
        path = capsule.trigger("lineage_test", origin="test_lineage")
    finally:
        capsule.configure(None)
    assert path and os.path.exists(path)
    doc = capsule.load(path)
    recs = doc["lineage"]
    assert any(r.get("sign") == "cap-0" and r.get("step") == 9
               and r.get("hops", {}).get("fetch") == 40.0 for r in recs)
