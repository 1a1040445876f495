"""Trace core tests (`utils/trace.py`): span nesting (same-thread and across
threads), ring-buffer eviction, histogram quantile accuracy, Chrome-trace
export, request-id propagation through a live serving request, /statusz and
/tracez, and the tools/trace_report.py smoke."""

import contextvars
import functools
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from openembedding_tpu.utils import metrics, trace


@pytest.fixture(autouse=True)
def _fresh():
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()
    yield
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()


# -- span core ----------------------------------------------------------------


def test_span_nesting_and_request_id():
    with trace.request("req-1"):
        with trace.span("g", "outer", foo=1) as outer:
            with trace.span("g", "inner") as inner:
                assert trace.current_span() is inner
            assert trace.current_span() is outer
    assert trace.current_span() is None
    spans = trace.RECORDER.spans()
    # completion order: inner lands before outer
    assert [(s.name, s.trace_id) for s in spans] == [("inner", "req-1"),
                                                     ("outer", "req-1")]
    inner, outer = spans
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert outer.attrs == {"foo": 1}
    assert outer.duration_ms >= inner.duration_ms >= 0
    # every span doubles as a latency histogram observation
    assert metrics.Accumulator.get("g.outer.ms", "hist").count == 1


def test_span_nesting_across_threads():
    """A thread launched with copy_context() nests under the launching span;
    a bare thread starts a fresh trace (no parent, no inherited id)."""
    results = {}

    def child():
        with trace.span("g", "child"):
            pass
        results["rid"] = trace.get_request_id()

    with trace.request("req-t"):
        with trace.span("g", "parent") as parent:
            ctx = contextvars.copy_context()
            t = threading.Thread(target=ctx.run, args=(child,))
            t.start()
            t.join()
    child_span = next(s for s in trace.RECORDER.spans() if s.name == "child")
    assert child_span.parent_id == parent.span_id
    assert child_span.trace_id == "req-t"
    assert results["rid"] == "req-t"

    trace.RECORDER.clear()
    t = threading.Thread(target=child)  # no context handoff
    t.start()
    t.join()
    bare = trace.RECORDER.spans()[0]
    assert bare.parent_id is None and bare.trace_id is None
    assert results["rid"] is None


def test_span_records_error_and_reraises():
    with pytest.raises(ValueError):
        with trace.span("g", "boom"):
            raise ValueError("no")
    s = trace.RECORDER.spans()[0]
    assert s.attrs["error"] == "ValueError: no"
    # error exits are greppable: status attr + a flight-recorder event that
    # survives span-ring eviction
    assert s.attrs["status"] == "error"
    assert s.duration_ms is not None
    evs = [e for e in trace.RECORDER.tail() if e.name == "span_error"]
    assert len(evs) == 1
    assert evs[0].group == "g"
    assert evs[0].attrs == {"span": "boom", "error": "ValueError: no"}
    # clean exits don't get the status attr or the event
    with trace.span("g", "fine"):
        pass
    ok = next(s for s in trace.RECORDER.spans() if s.name == "fine")
    assert "status" not in ok.attrs
    assert len([e for e in trace.RECORDER.tail()
                if e.name == "span_error"]) == 1


def test_flight_recorder_eviction_order():
    rec = trace.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(trace.Event("g", f"e{i}", {}))
    names = [e.name for e in rec.tail()]
    assert names == ["e6", "e7", "e8", "e9"]  # oldest evicted, order kept
    rec.configure(2)
    assert [e.name for e in rec.tail()] == ["e8", "e9"]  # newest survive
    assert rec.capacity == 2


def test_events_and_render_text():
    trace.event("sync", "state", frm="IDLE", to="DEGRADED", reason="torn")
    with trace.span("g", "s"):
        pass
    text = trace.RECORDER.render_text()
    assert "EVT  sync.state" in text and "reason=torn" in text
    assert "SPAN g.s" in text


# -- histogram quantiles ------------------------------------------------------


def test_histogram_quantiles_match_numpy():
    """Log-spaced buckets + in-bucket interpolation: p50/p95/p99 within a
    bucket-width (sqrt2) relative tolerance of exact numpy percentiles on a
    known heavy-tailed latency distribution."""
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=1.0, sigma=1.2, size=8000)
    acc = metrics.Accumulator.get("q.lat.ms", "hist")
    for v in vals:
        acc.observe(v)
    for q in (0.5, 0.95, 0.99):
        exact = float(np.percentile(vals, q * 100))
        got = acc.quantile(q)
        assert abs(got - exact) <= 0.25 * exact, (q, got, exact)
    # degenerate cases: empty -> 0, single value -> that value (clamping)
    empty = metrics.Accumulator.get("q.none.ms", "hist")
    assert empty.quantile(0.5) == 0.0
    one = metrics.Accumulator.get("q.one.ms", "hist")
    one.observe(3.25)
    assert one.quantile(0.5) == pytest.approx(3.25)


# -- chrome export + report tool ----------------------------------------------


def _load_tool(name):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dump_chrome_and_trace_report(tmp_path, capsys):
    with trace.request("req-d"):
        with trace.span("serving", "http"):
            with trace.span("serving", "predict", model="m"):
                pass
    trace.event("persist", "commit", step=3)
    path = trace.dump_chrome(str(tmp_path / "dump.json"))

    with open(path) as f:
        doc = json.load(f)  # valid Chrome-trace JSON
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert {e["name"] for e in xs} == {"serving.http", "serving.predict"}
    assert instants[0]["name"] == "persist.commit"
    for e in xs:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["args"]["request_id"] == "req-d"
        assert {"pid", "tid", "cat"} <= set(e)
    child = next(e for e in xs if e["name"] == "serving.predict")
    parent = next(e for e in xs if e["name"] == "serving.http")
    assert child["args"]["parent_id"] == parent["args"]["span_id"]

    # tier-1-riding smoke for tools/trace_report.py on the same dump
    tr = _load_tool("trace_report")
    rows = tr.report(tr.load_events(path))
    assert {r["key"] for r in rows} == {"serving.http", "serving.predict"}
    for r in rows:
        assert r["count"] == 1
        assert r["p99_ms"] >= r["p50_ms"] >= 0
    table = tr.format_table(rows)
    assert "serving.http" in table and "p99_ms" in table
    assert tr.main([path, "--by", "group", "--sort", "mean"]) == 0
    assert "serving" in capsys.readouterr().out


# -- live serving: request-id propagation + /statusz + /tracez ----------------


@pytest.fixture()
def served_model(tmp_path):
    """A serving node with micro-batching ON and a tiny deepfm loaded."""
    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.export import export_standalone
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.serving import make_server

    model = make_deepfm(vocabulary=256, dim=4, hidden=(8,))
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batch = next(iter(synthetic_criteo(8, id_space=256, steps=1, seed=0)))
    state = trainer.init(batch)
    export_dir = str(tmp_path / "export")
    export_standalone(state, model, export_dir, model_sign="t-0")
    srv = make_server(str(tmp_path / "reg"), port=0, batch_window_ms=2.0)
    srv.manager.load_model("t-0", export_dir)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", srv, batch
    srv.shutdown()


def test_request_id_propagates_through_live_predict(served_model):
    """ONE predict request yields >= 4 nested spans (http -> predict ->
    batch exec -> model call, plus queue wait) all correlated by the
    caller's X-OETPU-Request-Id, which the response echoes; /metrics gains
    the predict-latency histogram."""
    base, srv, batch = served_model
    body = json.dumps({
        "sparse": {"categorical":
                   np.asarray(batch["sparse"]["categorical"]).tolist()},
        "dense": np.asarray(batch["dense"]).tolist()}).encode()
    req = urllib.request.Request(
        f"{base}/models/t-0/predict", data=body, method="POST",
        headers={"Content-Type": "application/json",
                 "X-OETPU-Request-Id": "req-e2e"})
    with urllib.request.urlopen(req) as resp:
        assert resp.status == 200
        assert resp.headers["X-OETPU-Request-Id"] == "req-e2e"
        json.loads(resp.read())

    # the root span closes AFTER the handler has written the response: the
    # server's thread may still be on its way out of it when the client asks
    for _ in range(40):
        with urllib.request.urlopen(f"{base}/tracez") as resp:
            tz = json.loads(resp.read())
        spans = {s["span_id"]: s for s in tz["spans"]
                 if s["request_id"] == "req-e2e"}
        names = {s["name"] for s in spans.values()}
        if "http" in names:
            break
        time.sleep(0.05)
    assert {"http", "predict", "queue_wait", "batch_exec",
            "model_call"} <= names
    assert len(spans) >= 4

    # parent chain: model_call -> batch_exec -> predict -> http (depth 4)
    def chain(s):
        out = [s["name"]]
        while s["parent_id"] in spans:
            s = spans[s["parent_id"]]
            out.append(s["name"])
        return out

    mc = next(s for s in spans.values() if s["name"] == "model_call")
    assert chain(mc) == ["model_call", "batch_exec", "predict", "http"]
    qw = next(s for s in spans.values() if s["name"] == "queue_wait")
    assert chain(qw) == ["queue_wait", "predict", "http"]
    assert all(s["attrs"].get("status") == 200 for s in spans.values()
               if s["name"] == "http")

    with urllib.request.urlopen(f"{base}/metrics") as resp:
        text = resp.read().decode()
    assert 'oetpu_serving_predict_ms_bucket{model="t-0",le="+Inf"} 1' in text
    assert 'oetpu_serving_predict_ms_count{model="t-0"} 1' in text
    assert "oetpu_serving_http_ms_bucket" in text


def test_statusz_and_tracez_surfaces(served_model):
    base, srv, batch = served_model
    with urllib.request.urlopen(f"{base}/statusz") as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    assert "== openembedding_tpu serving /statusz ==" in text
    assert "t-0: step=0 kind=StandaloneModel status=NORMAL" in text
    assert "-- sync subscribers --" in text
    assert "-- workload skew (hot ids) --" in text
    assert "-- flight recorder" in text
    # a request id was generated for the statusz request itself. The http
    # span closes (and records) just AFTER the response body is written, so
    # an immediate /tracez can race it by ~1 ms — poll briefly.
    deadline = time.time() + 5.0
    while True:
        with urllib.request.urlopen(f"{base}/tracez?n=8") as resp:
            tz = json.loads(resp.read())
        if any(s["name"] == "http" and s["request_id"]
               for s in tz["spans"]):
            break
        assert time.time() < deadline, tz["spans"]
        time.sleep(0.01)


def test_trainer_stages_are_scopes_not_metrics_series(served_model):
    """The train step's stages are `trace.scope`s (HLO metadata, read from a
    device profile): neither an eager nor a jitted step leaves a
    `trainer.{pull,compute,apply}.ms` series — those timed Python tracing —
    while `trainer.traces{fn=}` counts each TRACE of the entry point: 1 after
    the first jitted call and still 1 after the second."""
    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm

    base, srv, _ = served_model
    model = make_deepfm(vocabulary=128, dim=4, hidden=(8,))
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batch = next(iter(synthetic_criteo(4, id_space=128, steps=1, seed=2)))
    state = trainer.init(batch)
    step = trainer.jit_train_step()
    state, _ = step(state, batch)
    key = 'trainer.traces{fn="train_step"}'
    assert metrics.report()[key] == 1
    state, _ = step(state, batch)
    rep = metrics.report()
    assert rep[key] == 1
    assert not [k for k in rep if k.startswith(
        ("trainer.pull.", "trainer.compute.", "trainer.apply."))]
    with urllib.request.urlopen(f"{base}/metrics") as resp:
        text = resp.read().decode()
    assert 'oetpu_trainer_traces_total{fn="train_step"} 1' in text
    for phase in ("pull", "compute", "apply"):
        assert f"oetpu_trainer_{phase}_ms" not in text


# -- stage scopes in the compiled scan, the profiler's clock, the reducer -----

_HEAVY = ("gather", "scatter", "sort", "dot", "convolution", "all-to-all",
          "all-reduce", "all-gather", "reduce-scatter", "collective-permute")
_SINGLE = {"sparse.dedup", "sparse.pull", "sparse.apply", "sparse.pack",
           "sparse.unpack", "dense.tower", "dense.update"}
_MESH = _SINGLE | {"exchange.route", "exchange.a2a_ids", "exchange.a2a_rows",
                   "exchange.a2a_grads", "exchange.owner_serve",
                   "exchange.owner_apply", "exchange.reassemble",
                   "dense.reduce"}


@functools.lru_cache(maxsize=None)  # two tests read the same two texts
def _compiled_scan_text(kind):
    """`train_many` of a tiny DeepFM, compiled: Trainer, or MeshTrainer on 4
    of the suite's virtual devices."""
    import jax

    import openembedding_tpu as embed
    from openembedding_tpu.models import make_deepfm

    rng = np.random.default_rng(0)
    K, B, V = 2, 32, 512
    stacked = {
        "sparse": {"categorical":
                   rng.integers(0, V, (K, B, 26)).astype(np.int32)},
        "dense": rng.normal(size=(K, B, 13)).astype(np.float32),
        "label": rng.integers(0, 2, (K, B)).astype(np.float32)}
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    model = make_deepfm(vocabulary=V, dim=4, hidden=(8,))
    opt = embed.Adagrad(learning_rate=0.05)
    if kind == "mesh":
        from openembedding_tpu.parallel import MeshTrainer, make_mesh
        trainer = MeshTrainer(model, opt, mesh=make_mesh(jax.devices()[:4]))
        state = trainer.init(one)
        many = trainer.jit_train_many(stacked, state)
    else:
        trainer = embed.Trainer(model, opt)
        state = trainer.init(one)
        many = trainer.jit_train_many()
    return many.lower(state, stacked).compile().as_text()


@pytest.mark.parametrize("kind", ["single", "mesh"])
def test_compiled_scan_carries_every_stage_scope(kind):
    from openembedding_tpu.utils import devtrace

    text = _compiled_scan_text(kind)
    scopes = trace.scope_map(text)
    inner = {path.rsplit("/", 1)[-1] for path in scopes.values() if path}
    want = _MESH if kind == "mesh" else _SINGLE
    assert want <= inner, sorted(want - inner)
    opcode = {}
    for line in text.splitlines():
        m = devtrace._INSTR.match(line)
        if m:
            opcode[m.group(1)] = devtrace.opcode_of(line)
    heavy = {n: op for n, op in opcode.items()
             if op.removesuffix("-start").removesuffix("-done") in _HEAVY}
    assert heavy
    assert not {n: op for n, op in heavy.items() if not scopes[n]}
    a2a = [scopes[n].rsplit("/", 1)[-1] for n, op in heavy.items()
           if op.startswith("all-to-all")]
    if kind == "mesh":
        # ids, rows, grads: each all-to-all under its own name
        assert sorted(a2a) == ["exchange.a2a_grads", "exchange.a2a_ids",
                               "exchange.a2a_rows"]
    else:
        assert a2a == []


@pytest.mark.parametrize("kind", ["single", "mesh"])
def test_scopes_add_no_instruction(kind, monkeypatch, no_compile_cache):
    """The compiled scan with scopes == the one without, metadata stripped,
    byte for byte: a scope is a name and nothing else. (The compile cache's
    key leaves the names out: the scan without them is compiled, not
    loaded.)"""
    import contextlib

    from hlo_hash import strip

    with_scopes = _compiled_scan_text(kind)
    assert "sparse.apply" in with_scopes
    monkeypatch.setattr(trace, "scope",
                        lambda group, name: contextlib.nullcontext())
    without = _compiled_scan_text.__wrapped__(kind)
    assert "sparse.apply" not in without
    assert strip(with_scopes) == strip(without)


def test_span_is_a_profiler_annotation(tmp_path):
    """A host span lands in an open profiler session as
    `oetpu.<group>.<name>` (the clock the device ops are on) and still in the
    flight recorder and its histogram."""
    import jax

    from openembedding_tpu.utils import devtrace

    with jax.profiler.trace(str(tmp_path)):
        with trace.span("ingest", "parse_block", rows=3):
            time.sleep(0.002)
    events = devtrace.load_events(devtrace.find_xplane(str(tmp_path)))
    mine = [e for e in events["host"] if e[0] == "oetpu.ingest.parse_block"]
    assert len(mine) == 1 and mine[0][2] >= 2e6  # ns
    (s,) = trace.RECORDER.spans()
    assert (s.group, s.name, s.attrs) == ("ingest", "parse_block",
                                          {"rows": 3})
    assert metrics.Accumulator.get("ingest.parse_block.ms", "hist").count == 1


def test_scope_map_reads_nested_and_wrapped_names():
    text = "\n".join([
        '  %copy.9 = f32[8,4]{0,1} copy(%param.1), metadata={op_name="w"}',
        '  %fusion.7 = f32[8,4]{1,0} fusion(%copy.9), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(train_many)/while/body/exchange.owner_apply/'
        'sparse.apply/sparse.apply/scatter-add" stack_frame_id=3}',
        '  ROOT %dot.2 = f32[8,8]{1,0} dot(%a, %b), metadata={op_name='
        '"jit(f)/transpose(jvp(dense.tower))/Dense_0/dot_general"}',
        "  %copy.1 = f32[8]{0} copy(%x)",
        '  %add.3 = f32[] add(%x, %y), metadata={op_name="jit(f)/my.dense.x"}',
    ])
    assert trace.scope_map(text) == {
        "fusion.7": "exchange.owner_apply/sparse.apply",
        # a layout copy the compiler put in takes its consumer's scope
        "copy.9": "exchange.owner_apply/sparse.apply",
        "dot.2": "dense.tower", "copy.1": "", "add.3": ""}


# A program in the TPU compiler's text form, cut to what `scope_map`'s two
# rules read (names and forms from `solar-open2.train_8k`'s scan, my chip
# run, PR 36; layouts and backend_config left out): computations, who calls
# them, and `op_name`s.
_CALL_GRAPH_TEXT = """HloModule jit_train_many, entry_computation_layout={()->f32[4]}

%fused_computation.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4] parameter(0)
  ROOT %negate.1 = f32[4] negate(%p.1)
}

%wide.while_body.778.sunk (wide.param.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %wide.param.1 = (s32[], f32[4]) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%wide.param.1), index=0
  %get-tuple-element.2 = f32[4] get-tuple-element(%wide.param.1), index=1
  %fusion.4767 = f32[4] fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.1
  %add.12958 = s32[] add(%get-tuple-element.1, %get-tuple-element.1)
  ROOT %tuple.12478 = (s32[], f32[4]) tuple(%add.12958, %fusion.4767)
}

%wide.while_cond.778 (wide.param.2: (s32[], f32[4])) -> pred[] {
  %wide.param.2 = (s32[], f32[4]) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element(%wide.param.2), index=0
  ROOT %compare.1 = pred[] compare(%get-tuple-element.3, %get-tuple-element.3), direction=LT
}

%region_compact.1 (b.1: f32[4]) -> f32[4] {
  %b.1 = f32[4] parameter(0)
  %fusion.20 = f32[4] fusion(%b.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_many)/while/body/exchange.owner_apply/cond/branch_0_fun/sparse.apply/scatter-add" stack_frame_id=7}
  ROOT %broadcast.21 = f32[4] broadcast(%fusion.20), dimensions={0}, metadata={op_name="jit(train_many)/while/body/closed_call"}
}

%region_full.2 (b.2: f32[4]) -> f32[4] {
  %b.2 = f32[4] parameter(0)
  ROOT %fusion.30 = f32[4] fusion(%b.2), kind=kLoop, calls=%fused_computation.1
}

%region_sum.3 (x.3: f32[], y.3: f32[]) -> f32[] {
  %x.3 = f32[] parameter(0)
  %y.3 = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%x.3, %y.3)
}

%body.9 (param.9: (s32[], f32[4])) -> (s32[], f32[4]) {
  %param.9 = (s32[], f32[4]) parameter(0)
  %get-tuple-element.91 = f32[4] get-tuple-element(%param.9), index=1
  %fusion.92 = f32[4] fusion(%get-tuple-element.91), kind=kLoop, calls=%fused_computation.1
  %get-tuple-element.93 = s32[] get-tuple-element(%param.9), index=0
  ROOT %tuple.94 = (s32[], f32[4]) tuple(%get-tuple-element.93, %fusion.92)
}

%body.8 (param.8: f32[4]) -> f32[4] {
  %param.8 = f32[4] parameter(0)
  ROOT %fusion.82 = f32[4] fusion(%param.8), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main.655 (state.1: f32[4], pred.1: pred[]) -> f32[4] {
  %state.1 = f32[4] parameter(0)
  %pred.1 = pred[] parameter(1)
  %constant.1 = s32[] constant(0)
  %tuple.15410 = (s32[], f32[4]) tuple(%constant.1, %state.1)
  %while.1789 = (s32[], f32[4]) while(%tuple.15410), condition=%wide.while_cond.778, body=%wide.while_body.778.sunk, metadata={op_name="jit(train_many)/while/body/closed_call/dense.tower/transpose(jvp(SolarOpen2))/checkpoint/layers_2/moe/cond/branch_1_fun/moe.experts/scatter-add" stack_frame_id=274}
  %get-tuple-element.10 = f32[4] get-tuple-element(%while.1789), index=1
  %cond.3837 = f32[4] conditional(%pred.1, %get-tuple-element.10, %get-tuple-element.10), true_computation=%region_compact.1, false_computation=%region_full.2, metadata={op_name="jit(train_many)/while/body/exchange.owner_apply/cond"}
  %constant.2 = f32[] constant(0)
  %reduce.1 = f32[] reduce(%cond.3837, %constant.2), dimensions={0}, to_apply=%region_sum.3, metadata={op_name="jit(train_many)/while/body/dense.tower/moe.experts/reduce_sum"}
  %reduce.2 = f32[] reduce(%cond.3837, %constant.2), dimensions={0}, to_apply=%region_sum.3, metadata={op_name="jit(train_many)/while/body/dense.tower/lm.head/reduce_sum"}
  %multiply.9 = f32[4] multiply(%state.1, %state.1), metadata={op_name="jit(train_many)/while/body/dense.tower/kda.scan/mul"}
  %tuple.9 = (s32[], f32[4]) tuple(%constant.1, %multiply.9)
  %while.9 = (s32[], f32[4]) while(%tuple.9), condition=%wide.while_cond.778, body=%body.9
  %get-tuple-element.99 = f32[4] get-tuple-element(%while.9), index=1
  %add.9 = f32[4] add(%get-tuple-element.99, %get-tuple-element.99), metadata={op_name="jit(train_many)/while/body/dense.tower/kda.scan/add"}
  %while.8 = f32[4] while(%multiply.9), condition=%wide.while_cond.778, body=%body.8
  ROOT %add.8 = f32[4] add(%while.8, %add.9), metadata={op_name="jit(train_many)/while/body/dense.tower/lm.head/add"}
}
"""


def test_scope_map_follows_the_call_graph():
    scopes = trace.scope_map(_CALL_GRAPH_TEXT)
    experts = "dense.tower/moe.experts"
    # a `while` whose body's instructions carry no op_name: they take the
    # while's path, through the fusion the body calls
    for name in ("fusion.4767", "add.12958", "get-tuple-element.2",
                 "tuple.12478", "wide.param.1"):
        assert scopes[name] == experts, name
    # ... and what the loop hands on is consumed under the conditional's name
    assert scopes["get-tuple-element.10"] == "exchange.owner_apply"
    # a conditional with a scoped branch: an instruction that HAS a scope
    # keeps it, one with an op_name and no scope takes the caller's, and so
    # does the whole scopeless branch
    assert scopes["fusion.20"] == "exchange.owner_apply/sparse.apply"
    assert scopes["broadcast.21"] == "exchange.owner_apply"
    assert scopes["fusion.30"] == scopes["b.2"] == "exchange.owner_apply"
    # a computation called from two scopes gives its instructions none; the
    # condition both scopeless loops share is called from three
    assert scopes["add.3"] == scopes["x.3"] == ""
    assert scopes["compare.1"] == ""
    # ... and `fused_computation.1`, called from everywhere, none either
    assert scopes["negate.1"] == ""
    # a scopeless `while` between agreeing producers and consumers: the tuple
    # in front of it carries values and is no producer, its consumers agree
    scan = "dense.tower/kda.scan"
    for name in ("while.9", "get-tuple-element.99", "fusion.92", "tuple.94",
                 "tuple.9"):
        assert scopes[name] == scan, name
    # ... and one whose producer and consumer disagree: none, nor its body
    assert scopes["while.8"] == scopes["fusion.82"] == ""
    # the entry computation's own made instructions: a constant two scopes
    # consume stays unscoped
    assert scopes["constant.2"] == ""


def test_call_graph_scopes_partition_busy_time():
    """Scope sums + unscoped = busy with the call graph's names, exactly."""
    from openembedding_tpu.utils import devtrace

    scopes = trace.scope_map(_CALL_GRAPH_TEXT)
    timed = ["fusion.4767", "add.12958", "fusion.20", "fusion.30",
             "reduce.1", "fusion.92", "fusion.82", "add.8", "compare.1"]
    ops = [[f"%{n} = f32[4] fusion(%x)", 1000.0 * i, 600.0 + 10 * i]
           for i, n in enumerate(timed)]
    ops.append(["%while.1789 = (s32[], f32[4]) while(%t)", 0.0, 9000.0])
    rep = devtrace.reduce_events(
        {"devices": {"/device:TPU:0": {"ops": ops, "async": []}},
         "host": [], "steps": []}, scopes=scopes, steps=1)
    (dev,) = rep["devices"].values()
    assert sum(dev["scope_s"].values()) + dev["unscoped_s"] == \
        pytest.approx(dev["busy_s"], abs=1e-15)
    assert dev["scope_s"]["moe.experts"] == pytest.approx(
        (600 + 610 + 640) * 1e-9)   # the body's two ops and reduce.1
    assert dev["scope_s"]["sparse.apply"] == pytest.approx(620e-9)
    assert dev["scope_s"]["exchange.owner_apply"] == pytest.approx(630e-9)
    assert dev["scope_s"]["kda.scan"] == pytest.approx(650e-9)
    assert dev["unscoped_s"] == pytest.approx((660 + 680) * 1e-9)


@pytest.fixture(scope="module")
def recorded():
    """A v5e trace cut to a few hundred events (see its `recorded` key)."""
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "devtrace_small.json")
    with open(path) as f:
        return json.load(f)


def test_device_report_partitions_busy_time(recorded):
    from openembedding_tpu.utils import devtrace

    rep = devtrace.reduce_events(recorded, scopes=recorded["scopes"],
                                 steps=recorded["steps_in_cut"])
    (dev,) = rep["devices"].values()
    total = sum(dev["scope_s"].values()) + dev["unscoped_s"]
    assert abs(total - dev["busy_s"]) < 1e-9
    assert abs(sum(dev["path_s"].values()) - sum(dev["scope_s"].values())) \
        < 1e-9
    assert abs(sum(dev["rollup_s"].values())
               - sum(dev["scope_s"].values())) < 1e-9
    assert 0.5 < dev["scoped_share"] <= 1.0
    assert {"sparse.apply", "sparse.pull", "sparse.dedup",
            "dense.tower"} <= set(dev["scope_s"])
    assert dev["idle_s"] == pytest.approx(dev["span_s"] - dev["busy_s"])
    per_step = dev["scope_ms_per_step"]["sparse.apply"]
    assert per_step == pytest.approx(
        dev["scope_s"]["sparse.apply"] / recorded["steps_in_cut"] * 1e3)


def test_device_report_leaves_containers_out(recorded):
    from openembedding_tpu.utils import devtrace

    (name,) = recorded["devices"]
    ops = recorded["devices"][name]["ops"]
    loops = [e for e in ops if devtrace.opcode_of(e[0]) in
             devtrace.CONTAINERS]
    assert loops, "the cut keeps the scan's while op"
    with_loops = devtrace.reduce_device(recorded["devices"][name],
                                        recorded["scopes"])
    without = devtrace.reduce_device(
        {"ops": [e for e in ops if e not in loops],
         "async": recorded["devices"][name]["async"]}, recorded["scopes"])
    assert with_loops["busy_ns"] == without["busy_ns"]
    assert with_loops["path_ns"] == without["path_ns"]
    # a container spans its body: counted, each scan would be charged twice
    assert sum(e[2] for e in loops) > with_loops["busy_ns"] / 2


def test_device_report_names_idle_gaps(recorded):
    from openembedding_tpu.utils import devtrace

    rep = devtrace.reduce_events(recorded, scopes=recorded["scopes"])
    (dev,) = rep["devices"].values()
    gaps = dev["idle_gaps"]
    assert gaps and gaps == sorted(gaps, key=lambda g: -g[1])
    assert gaps[0][0] == recorded["longest_gap_under"]
    # with no host plane the same gap is there, unattributed
    bare = dict(recorded, host=[])
    (dev2,) = devtrace.reduce_events(
        bare, scopes=recorded["scopes"])["devices"].values()
    assert dev2["idle_gaps"][0] == ["unattributed", gaps[0][1]]


def test_trace_report_xplane_smoke(tmp_path, capsys):
    """tools/trace_report.py --xplane on a CPU profile of a jitted fn: no
    device plane there, and the tool says so instead of failing."""
    import jax
    import jax.numpy as jnp

    trace_report = _load_tool("trace_report")
    with jax.profiler.trace(str(tmp_path)):
        jax.jit(lambda x: x * 2)(jnp.ones(4)).block_until_ready()
    assert trace_report.main(["--xplane", str(tmp_path)]) == 0
    assert "no device ops" in capsys.readouterr().out
