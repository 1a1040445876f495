"""Metrics subsystem tests (reference §5: accumulators, VTIMER, periodic report,
Prometheus exposition)."""

import time

import pytest

from openembedding_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _fresh():
    metrics._REGISTRY.clear()
    yield
    metrics._REGISTRY.clear()


def test_accumulator_kinds():
    metrics.observe("a.sum", 2)
    metrics.observe("a.sum", 3)
    metrics.Accumulator.get("a.avg", "avg").observe(2)
    metrics.Accumulator.get("a.avg", "avg").observe(4)
    metrics.Accumulator.get("a.max", "max").observe(5)
    metrics.Accumulator.get("a.max", "max").observe(1)
    metrics.Accumulator.get("a.g", "gauge").observe(7)
    metrics.Accumulator.get("a.g", "gauge").observe(9)
    rep = metrics.report()
    assert rep["a.sum"] == 5
    assert rep["a.avg"] == 3
    assert rep["a.max"] == 5
    assert rep["a.g"] == 9


def test_accumulator_kind_conflict_raises():
    """Round-1 advisor: re-registering a name with a different kind must not
    silently aggregate with whichever kind ran first."""
    metrics.Accumulator.get("k", "sum").observe(1)
    with pytest.raises(ValueError, match="kind"):
        metrics.Accumulator.get("k", "gauge")
    metrics.Accumulator.get("k", "sum").observe(1)  # same kind still fine
    assert metrics.report()["k"] == 2


def test_vtimer_records():
    with metrics.vtimer("pull", "exchange"):
        time.sleep(0.01)
    rep = metrics.report()
    assert rep["pull.exchange.ms"] >= 10
    assert rep["pull.exchange.max_ms"] >= rep["pull.exchange.ms"]


def test_record_step_stats_from_device_dict():
    import jax.numpy as jnp
    metrics.record_step_stats({"categorical/pull_indices": jnp.asarray(128),
                               "categorical/pull_unique": jnp.asarray(50),
                               "categorical/pull_overflow": jnp.asarray(0)})
    rep = metrics.report()
    assert rep["categorical.pull_indices"] == 128
    assert rep["categorical.pull_unique"] == 50
    # per-table stats double as LABELED counters (per-table skew on /metrics)
    assert rep['trainer.pull_indices{table="categorical"}'] == 128


@pytest.mark.parametrize("shape", ["scalar", "per_shard"])
def test_record_step_stats_folds_the_apply_load(shape):
    """`{table}/apply_fill` / `apply_full_steps` (`ops/sparse.py` "WHAT THE
    APPLY WORKS OVER"): a scalar from one device, a per-shard vector from a
    mesh; either way ONE series a table: the fullest shard's fill as a gauge,
    and a counter of steps in which some shard ran its last rung."""
    import numpy as np
    fill = {"scalar": np.float32(0.675),
            "per_shard": np.asarray([0.61, 0.675, 0.64, 0.6], np.float32)}[shape]
    full = {"scalar": np.int32(0), "per_shard": np.zeros(4, np.int32)}[shape]
    for step in range(3):
        last = step == 2
        metrics.record_step_stats({
            "user/apply_fill": fill * (1.2 if last else 1.0),
            "user/apply_full_steps": full + (1 if last else 0),
            "item/apply_fill": np.float32(0.25),
            "item/apply_full_steps": np.int32(0)})
    rep = metrics.report()
    assert rep['sparse.apply_fill{table="user"}'] == pytest.approx(0.675 * 1.2)
    assert rep['sparse.apply_full_steps{table="user"}'] == 1
    assert rep['sparse.apply_fill{table="item"}'] == pytest.approx(0.25)
    assert rep['sparse.apply_full_steps{table="item"}'] == 0
    # one series a table: no per-shard gauges, no generic `trainer.*` counter
    assert not [k for k in rep if "shard=" in k or k.startswith("trainer.apply")
                or k.startswith("user.") or k.startswith("item.")]
    # a gauge survives a windowed reset, the counter starts over
    metrics.report(reset=True)
    rep = metrics.report()
    assert rep['sparse.apply_fill{table="user"}'] == pytest.approx(0.675 * 1.2)
    assert rep['sparse.apply_full_steps{table="user"}'] == 0


def test_record_step_stats_single_host_sync_and_mixed_types(monkeypatch):
    """The hot-path contract: ONE jax.device_get for the whole stats dict
    (per-key float() on device arrays = one host sync per stat), accepting
    jax arrays, numpy scalars, and plain floats interchangeably."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    metrics.record_step_stats({"t/pull_indices": jnp.asarray(7),
                               "t/pull_unique": np.float32(3.5),
                               "t/pull_overflow": 0.25,
                               "t/not_numeric": "skipped"})
    assert calls["n"] == 1
    rep = metrics.report()
    assert rep["t.pull_indices"] == 7
    assert rep["t.pull_unique"] == 3.5
    assert rep["t.pull_overflow"] == 0.25
    assert "t.not_numeric" not in rep


def test_report_reset():
    metrics.observe("x", 1)
    assert metrics.report(reset=True)["x"] == 1
    assert metrics.report()["x"] == 0


def test_reset_skips_gauges():
    """Regression: one-shot gauges (`exchange.*` wire costs,
    `sync.wire_bytes_per_delta`) must survive `report(reset=True)` — the
    PeriodicReporter wiped them from /metrics after its first report."""
    metrics.observe("exchange.wire_bytes_per_step", 4096, "gauge")
    metrics.observe("win.count", 2)
    rep = metrics.report(reset=True)
    assert rep["exchange.wire_bytes_per_step"] == 4096
    rep = metrics.report()
    assert rep["exchange.wire_bytes_per_step"] == 4096  # gauge survives
    assert rep["win.count"] == 0                        # counter windowed
    # the PeriodicReporter path (report_table(reset=True)) behaves the same
    metrics.PeriodicReporter(0).interval  # (construction only; no thread)
    metrics.report_table(reset=True)
    assert metrics.report()["exchange.wire_bytes_per_step"] == 4096


def test_hist_survives_reset_and_reports_quantiles():
    for v in (1.0, 2.0, 3.0, 4.0):
        metrics.observe("lat.ms", v, "hist")
    rep = metrics.report(reset=True)
    assert rep["lat.ms"] == 2.5  # mean under the bare key
    assert set(k for k in rep if k.startswith("lat.ms.")) == {
        "lat.ms.p50", "lat.ms.p95", "lat.ms.p99"}
    # histogram series are cumulative (Prometheus contract): not windowed
    assert metrics.Accumulator.get("lat.ms", "hist").count == 4


def test_prometheus_text():
    metrics.observe("pull.indices", 10)
    metrics.Accumulator.get("step.ms", "avg", help="step time").observe(5.0)
    text = metrics.prometheus_text()
    # counters carry the _total suffix (Prometheus conformance)
    assert "# TYPE oetpu_pull_indices_total counter" in text
    assert "oetpu_pull_indices_total 10.0" in text
    # avg/max kinds stay a single well-typed gauge series
    assert "# HELP oetpu_step_ms step time" in text
    assert "# TYPE oetpu_step_ms gauge" in text
    assert "oetpu_step_ms 5.0" in text


def test_prometheus_histogram_series():
    for v in (0.5, 1.0, 2.0, 400.0):
        metrics.observe("serving.predict.ms", v, "hist",
                        labels={"model": "m-0"})
    text = metrics.prometheus_text()
    assert "# TYPE oetpu_serving_predict_ms histogram" in text
    assert 'oetpu_serving_predict_ms_bucket{model="m-0",le="+Inf"} 4' in text
    assert 'oetpu_serving_predict_ms_count{model="m-0"} 4' in text
    assert 'oetpu_serving_predict_ms_sum{model="m-0"} 403.5' in text
    # cumulative bucket counts, monotone le boundaries
    import re
    pairs = re.findall(
        r'oetpu_serving_predict_ms_bucket\{model="m-0",le="([^"]+)"\} (\d+)',
        text)
    counts = [int(c) for _le, c in pairs]
    assert counts == sorted(counts) and counts[-1] == 4


def test_prometheus_label_escaping():
    metrics.observe("pull.rows", 1, "gauge",
                    labels={"table": 'we"ird\\na\nme'})
    text = metrics.prometheus_text()
    assert r'oetpu_pull_rows{table="we\"ird\\na\nme"} 1.0' in text


def test_label_series_are_distinct_and_kinds_consistent():
    metrics.observe("pull.rows_total", 3, labels={"table": "user"})
    metrics.observe("pull.rows_total", 5, labels={"table": "item"})
    metrics.observe("pull.rows_total", 1, labels={"table": "user"})
    rep = metrics.report()
    assert rep['pull.rows_total{table="user"}'] == 4
    assert rep['pull.rows_total{table="item"}'] == 5
    # one name aggregates ONE way across all its label sets
    with pytest.raises(ValueError, match="kind"):
        metrics.Accumulator.get("pull.rows_total", "gauge",
                                labels={"table": "other"})


def test_periodic_reporter():
    metrics.observe("tick", 1)
    seen = []
    rep = metrics.PeriodicReporter(0.05, sink=seen.append)
    with rep:
        time.sleep(0.2)
    assert seen and "tick" in seen[0]


def test_serving_metrics_endpoint(tmp_path):
    import json
    import threading
    import urllib.request
    from openembedding_tpu.serving import make_server

    metrics.observe("serving.requests", 3)
    httpd = make_server(str(tmp_path / "reg"), port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}/metrics"
        with urllib.request.urlopen(url) as resp:
            body = resp.read().decode()
        assert "oetpu_serving_requests_total 3.0" in body
    finally:
        httpd.shutdown()


def test_auc():
    from openembedding_tpu.utils.metrics import auc
    import numpy as np
    # perfect separation
    assert auc([0, 0, 1, 1], [0.1, 0.2, 0.8, 0.9]) == 1.0
    # perfect inversion
    assert auc([1, 1, 0, 0], [0.1, 0.2, 0.8, 0.9]) == 0.0
    # random-ish mid value
    v = auc([0, 1, 0, 1], [0.4, 0.3, 0.6, 0.7])
    assert 0.0 < v < 1.0
    # one-class degenerate -> nan
    assert np.isnan(auc([1, 1], [0.5, 0.6]))
    # matches sklearn on random data when available
    try:
        from sklearn.metrics import roc_auc_score
    except Exception:
        return
    rng = np.random.default_rng(0)
    y = rng.integers(0, 2, 500)
    s = rng.random(500)
    np.testing.assert_allclose(auc(y, s), roc_auc_score(y, s), atol=1e-12)
