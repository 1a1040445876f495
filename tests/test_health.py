"""Round-16 training-health E2E (`ISSUE 12` acceptance): the in-jit numerics
sentinel (clean run -> health.* gauges populated, nonfinite_total == 0;
planted NaN -> `NonFiniteError` naming the table + the `health/nonfinite`
flight-recorder event + the numerics SLO flipping to BREACHED on a live
`GET /sloz`), the sampled step-time watch (`trainer.step_ms`,
`exchange.cost_drift`), sentinel-off stat hygiene, the mesh
additive-stats path, and the PeriodicReporter JSONL sink."""

import json
import threading
import urllib.request

import numpy as np
import pytest

import openembedding_tpu as oe
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.utils import metrics, slo, trace


@pytest.fixture(autouse=True)
def _fresh():
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()
    yield
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()


def _make(vocab=64, **kw):
    model = make_deepfm(vocabulary=vocab, dim=4, hidden=(8,))
    trainer = Trainer(model, oe.Adagrad(learning_rate=0.05), **kw)
    batch = next(iter(synthetic_criteo(8, id_space=vocab, steps=1, seed=0)))
    state = trainer.init(batch)
    return trainer, state, batch


# -- clean run: gauges populated, step_ms measured, SLOs OK -------------------


def test_clean_run_health_gauges_step_ms_and_numerics_ok():
    trainer, state, batch = _make(sentinel=True, measure_every=1)
    step = trainer.jit_train_step()
    for _ in range(3):
        state, mets = step(state, batch)
        health = trainer.record_step_stats(mets)
    (name,) = trainer.model.ps_specs().keys()
    assert health["sentinel"] is True
    assert health["nonfinite"] == {}
    for src in (name, "dense"):
        assert np.isfinite(health["grad_norm"][src])
        assert health["grad_norm"][src] > 0.0
    # the gauges the /metrics surface serves
    assert metrics.Accumulator.get(
        "health.grad_norm", "gauge", labels={"table": name}).value() > 0.0
    assert metrics.Accumulator.get("health.dense_grad_norm",
                                   "gauge").value() > 0.0
    # observed (as zero) EVERY step, so the numerics SLO is judged, not
    # UNKNOWN, on a clean run
    nt = metrics.Accumulator.get("health.nonfinite_total")
    assert nt.count == 3 and nt.value() == 0.0
    # measure_every=1 brackets every call into the step-time histogram
    assert metrics.Accumulator.get("trainer.step_ms", "hist").count == 3
    ev = slo.SLOEvaluator([s for s in slo.DEFAULT_SLOS
                           if s.name == "numerics"])
    (v,) = ev.evaluate_now()
    assert v["verdict"] == slo.OK


def test_sentinel_off_leaves_stats_and_registry_clean():
    trainer, state, batch = _make()  # sentinel defaults off
    assert trainer.sentinel is False
    state, mets = trainer.jit_train_step()(state, batch)
    assert not any("grad_sumsq" in k or k.startswith("health/")
                   for k in mets["stats"])
    health = trainer.record_step_stats(mets)
    assert health["sentinel"] is False and health["nonfinite"] == {}
    with metrics._LOCK:
        names = {a.name for a in metrics._REGISTRY.values()}
    assert not any(n.startswith("health.") for n in names)
    assert "trainer.step_ms" not in names  # measure_every defaults off


# -- planted non-finite: the acceptance E2E -----------------------------------


@pytest.fixture()
def sloz_server(tmp_path):
    """A serving node exposing /sloz, with the global evaluator pinned to
    the numerics SLO for the test (restored after)."""
    from openembedding_tpu.serving import make_server
    slo.configure([s for s in slo.DEFAULT_SLOS if s.name == "numerics"])
    srv = make_server(str(tmp_path / "reg"), port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    slo.configure(list(slo.DEFAULT_SLOS))


def test_nonfinite_grad_trips_error_event_and_sloz_breach(sloz_server):
    trainer, state, batch = _make(halt_on_nonfinite=True)
    assert trainer.sentinel is True  # halt implies the sentinel
    (name,) = trainer.model.ps_specs().keys()
    ts = state.tables[name]
    state = state.replace(tables={
        **state.tables,
        name: ts.replace(weights=ts.weights.at[:].set(np.nan))})
    state, mets = trainer.jit_train_step()(state, batch)
    with pytest.raises(oe.NonFiniteError) as ei:
        trainer.record_step_stats(mets)
    # the error names the offending table (and the loss it poisoned)
    assert name in str(ei.value) and "loss" in str(ei.value)
    assert ei.value.sources[name] > 0

    # the flight recorder kept the breadcrumb
    evs = [e for e in trace.RECORDER.tail()
           if e.group == "health" and e.name == "nonfinite"]
    assert len(evs) == 1 and evs[0].attrs[name] > 0

    # and the numerics SLO flips to BREACHED on the live node
    with urllib.request.urlopen(f"{sloz_server}/sloz") as resp:
        doc = json.loads(resp.read())
    (v,) = doc["verdicts"]
    assert v["name"] == "numerics" and v["verdict"] == slo.BREACHED
    assert doc["exit_code"] == 1
    with urllib.request.urlopen(f"{sloz_server}/sloz?format=text") as resp:
        assert b"BREACHED" in resp.read()
    with urllib.request.urlopen(f"{sloz_server}/statusz") as resp:
        assert b"-- SLOs (GET /sloz for JSON) --" in resp.read()


def test_halt_off_records_but_does_not_raise():
    trainer, state, batch = _make(sentinel=True)
    (name,) = trainer.model.ps_specs().keys()
    ts = state.tables[name]
    state = state.replace(tables={
        **state.tables,
        name: ts.replace(weights=ts.weights.at[:].set(np.inf))})
    state, mets = trainer.jit_train_step()(state, batch)
    health = trainer.record_step_stats(mets)  # no raise: observe-only mode
    assert health["nonfinite"]
    assert metrics.Accumulator.get("health.nonfinite_total").value() > 0


# -- mesh path: additive stats psum to global figures -------------------------


def test_mesh_sentinel_grad_norms_and_quant_err():
    import jax
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    model = make_deepfm(vocabulary=64, dim=4, hidden=(8,))
    trainer = MeshTrainer(model, oe.Adagrad(learning_rate=0.05),
                          mesh=make_mesh(), wire="int8", sentinel=True)
    batch = next(iter(synthetic_criteo(8, id_space=64, steps=1, seed=0)))
    state = trainer.init(batch)
    state, mets = trainer.jit_train_step(batch, state)(state, batch)
    health = trainer.record_step_stats(mets)
    (name,) = trainer.model.ps_specs().keys()
    assert health["nonfinite"] == {}
    assert np.isfinite(health["grad_norm"][name])
    assert np.isfinite(health["grad_norm"]["dense"])
    if len(jax.devices()) > 1:
        # int8 wire + a real exchange: the quantization-error gauge derives
        assert metrics.Accumulator.get(
            "health.quant_err_rel", "gauge",
            labels={"table": name}).value() >= 0.0


# -- step watch: sampling cadence, attribution, cost drift --------------------


def test_stepwatch_cadence_attribution_and_cost_drift():
    from openembedding_tpu.utils.stepwatch import StepWatch

    watch = StepWatch(every=2, wire_cost=lambda: {"bytes_per_step": 1024})
    wrapped = watch.wrap(lambda x: x)  # any callable: nothing is lowered
    for i in range(8):
        assert wrapped(i) == i
    assert watch.calls == 8 and watch.samples == 4
    assert metrics.Accumulator.get("trainer.step_ms", "hist").count == 4
    # baseline = first 3 samples; drift gauged from sample 1 on, finite
    drift = metrics.Accumulator.get("exchange.cost_drift", "gauge").value()
    assert np.isfinite(drift)
    assert metrics.Accumulator.get("exchange.us_per_byte",
                                   "gauge").value() > 0.0


def test_stepwatch_jit_proxy_and_no_modelled_series():
    import jax
    import jax.numpy as jnp

    from openembedding_tpu.utils.stepwatch import StepWatch

    fn = jax.jit(lambda x: jnp.sum(x * 2.0))
    watch = StepWatch(every=1)
    wrapped = watch.wrap(fn)
    x = jnp.ones((4,))
    assert float(wrapped(x)) == 8.0
    # proxied attributes still reach the jit fn (recompile guards use this)
    assert hasattr(wrapped, "lower")
    assert watch.samples == 1
    # step_ms is measured; the byte-model series (time split over collective
    # kinds by HLO bytes, modelled overlap) are gone: per-stage time comes
    # from a device profile (tools/trace_report.py --xplane)
    assert metrics.Accumulator.get("trainer.step_ms", "hist").count == 1
    with metrics._LOCK:
        names = {a.name for a in metrics._REGISTRY.values()}
    assert not names & {"trainer.attrib_ms", "trainer.overlap_ms",
                        "trainer.hlo_bytes", "trainer.hlo_extract_errors"}


def test_stepwatch_rejects_bad_every():
    from openembedding_tpu.utils.stepwatch import StepWatch
    with pytest.raises(ValueError):
        StepWatch(every=0)


# -- PeriodicReporter JSONL sink ----------------------------------------------


def test_periodic_reporter_jsonl(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    metrics.observe("train.examples", 128.0)
    rep = metrics.PeriodicReporter(60.0, sink=lambda s: None,
                                   jsonl_path=path).start()
    rep.stop()  # final flush writes one record even before the first tick
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert len(lines) == 1
    assert lines[0]["ts"] > 0
    assert lines[0]["metrics"]["train.examples"] == 128.0
