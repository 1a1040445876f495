"""`utils/compile_cache.py`'s account of traces, compiles and cache loads:
JAX's own monitoring events folded into `compile.*{fn=}` under the program's
entry point on whose call they fired, JAX's `fun_name` where none is running,
and every event in the flight recorder."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.utils import compile_cache, metrics, trace


@pytest.fixture(autouse=True)
def _fresh():
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()
    compile_cache.listen()
    yield
    metrics._REGISTRY.clear()
    trace.RECORDER.clear()


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent cache of this test's own, every entry written (no size or
    time threshold); the process's setting is put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield str(tmp_path)
    for k, v in keep.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _series(fn):
    rep = metrics.report()
    return {name: rep.get(f'compile.{name}{{fn="{fn}"}}')
            for name in compile_cache.SERIES}


def _fresh_probe():
    """A function object no earlier test has jitted (the same program)."""
    def _probe(x):
        return jnp.tanh(x) * 3.0 + jnp.arange(5, dtype=x.dtype)
    return _probe


def test_a_first_compile_is_a_miss_with_backend_seconds(cache_dir):
    x = jnp.ones(5, jnp.float32)  # made outside: its own compiles are not the entry's
    with compile_cache.entry("probe_miss"):
        jax.jit(_fresh_probe())(x)
    got = _series("probe_miss")
    assert got["executables"] == 1 and got["cache_misses"] == 1
    assert got["cache_hits"] == 0 and got["cache_load_s"] == 0
    assert got["backend_s"] > 0 and got["trace_s"] > 0
    assert compile_cache.entry_count(cache_dir) >= 1


def test_the_same_program_after_clear_caches_is_a_hit_with_load_seconds(
        cache_dir):
    x = jnp.ones(5, jnp.float32)
    with compile_cache.entry("probe_hit"):
        jax.jit(_fresh_probe())(x)
    first = _series("probe_hit")
    jax.clear_caches()  # the process forgets; the directory does not
    with compile_cache.entry("probe_hit"):
        jax.jit(_fresh_probe())(x)
    got = _series("probe_hit")
    assert got["executables"] == 2
    assert got["cache_hits"] == 1 and got["cache_load_s"] > 0
    assert got["cache_misses"] == 1
    assert got["backend_s"] == first["backend_s"]  # a load compiles nothing
    assert got["trace_s"] > first["trace_s"]       # but it is traced again


def test_a_second_signature_is_a_second_executable_of_one_trace(
        no_compile_cache):
    """An uncommitted first state and a committed later one (PERF.md section
    7 c): `trainer.traces` cannot see the second compile, the account can."""
    rng = np.random.default_rng(0)
    K, B = 2, 8
    stacked = {"sparse": {"categorical": rng.integers(0, 64, (K, B, 26))
                          .astype(np.int32)},
               "dense": rng.normal(size=(K, B, 13)).astype(np.float32),
               "label": rng.integers(0, 2, (K, B)).astype(np.float32)}
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    tr = embed.Trainer(make_deepfm(vocabulary=64, dim=4, hidden=(8,)),
                       embed.Adagrad(learning_rate=0.05))
    state = tr.init(one)
    many = tr.jit_train_many()
    state, _ = many(state, stacked)
    state, _ = many(state, stacked)
    assert _series("train_many")["executables"] == 1
    state, _ = many(jax.device_put(state, jax.devices()[0]), stacked)
    rep = metrics.report()
    assert rep['trainer.traces{fn="train_many"}'] == 1
    got = _series("train_many")
    assert got["executables"] == 2
    assert got["cache_hits"] == got["cache_misses"] == 0  # no cache directory
    # `init` ran inside its own entry point, with its own span
    assert _series("init")["executables"] > 0
    assert rep["trainer.init.ms"] > 0
    assert rep["trainer.dispatch.ms"] > 0 and rep["trainer.dispatch.ms.p50"] > 0


@pytest.mark.parametrize("dim", [9, 64])
def test_init_is_a_program_a_part_and_the_op_by_op_inits_state(dim):
    """`Trainer.init` runs the tower's init, each table's init and the dense
    slots as ONE jitted program each (PR 42: a DeepFM's op-by-op init was 66
    dispatches, 2.1 s of set-up on the chip's host), so few executables; the
    state is the op-by-op init's (`jax.disable_jit`): every integer leaf
    equal, every float within a few roundings of the leaf's largest value (the
    compiler may contract an initializer's multiply-adds inside a program:
    on the CPU about half of a kernel's weights differ by 1e-8..1e-7)."""
    B = 8
    one = {"sparse": {"categorical": np.zeros((B, 26), np.int32)},
           "dense": np.zeros((B, 13), np.float32),
           "label": np.zeros((B,), np.float32)}
    tr = embed.Trainer(make_deepfm(vocabulary=512, dim=dim, hidden=(8, 8)),
                       embed.Adagrad(learning_rate=0.05))
    state = tr.init(one)
    # the tower, each table, the dense slots and a few conversions around them
    # (8 and 9 here; op by op the same init compiles 60 and more)
    assert 0 < _series("init")["executables"] <= 12, _series("init")
    with jax.disable_jit():
        eager = tr.init(one)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(eager)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        if a.dtype.kind == "f" and a.size:
            np.testing.assert_allclose(
                a, b, rtol=0, atol=4 * np.finfo(a.dtype).eps * np.abs(b).max())
        else:
            np.testing.assert_array_equal(a, b)


def test_an_event_outside_any_entry_point_is_jaxs_function_name_or_other():
    def lonely_function(x):
        return x * 5 - 1

    jax.jit(lonely_function)(jnp.ones(3))
    got = _series("lonely_function")  # trace says `f`, lowering `jit(f)`: one fn
    assert got["executables"] == 1 and got["trace_s"] > 0
    assert got["backend_s"] > 0
    # an event that names no function
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.25)
    assert _series("other")["executables"] == 1
    assert _series("other")["backend_s"] == 0.25
    # ... and one that is no trace, compile or load is not counted
    compile_cache._on_duration("/jax/checkpoint/write/durations_sec", 9.0,
                               fun_name="lonely_function")
    assert _series("lonely_function") == got


def test_nested_traces_count_every_second_once():
    """A function traced inside another's trace reports its own seconds and
    the outer one's hold them (events fire at their end, the seconds are
    injected here): the sum is the outer trace's, plus what came before it."""
    trace_event = "/jax/core/compile/jaxpr_trace_duration"
    with compile_cache.entry("probe_nested"):
        compile_cache._on_duration(trace_event, 0.002, fun_name="before")
        compile_cache._on_duration(trace_event, 0.001, fun_name="inner_a")
        compile_cache._on_duration(trace_event, 0.0005, fun_name="inner_b")
        compile_cache._on_duration(trace_event, 0.0016, fun_name="middle")
        # `before` ended 2.5 ms ago at the least and is not inside `outer`,
        # which started 2 ms ago; the other three are
        compile_cache._on_duration(trace_event, 0.002, fun_name="outer")
    got = metrics.report()['compile.trace_s{fn="probe_nested"}']
    assert got == pytest.approx(0.004, abs=1e-9)
    assert 'compile.executables{fn="probe_nested"}' not in metrics.report()


def test_every_compile_is_in_the_flight_recorder(monkeypatch):
    def recorded_function(x):
        return x + 2

    with compile_cache.entry("probe_recorder"):
        jax.jit(recorded_function)(jnp.ones(3))
    events = [e for e in trace.RECORDER.events() if e.group == "compile"
              and e.attrs["fn"] == "probe_recorder"]
    stages = [e.name for e in events]
    assert {"lower", "backend"} <= set(stages)
    # a trace is recorded from `RECORDED_TRACE_S` on (a scan traces thousands
    # of small functions inside it): this one took no 10 ms, a long one does
    assert "trace" not in stages
    compile_cache._on_duration("/jax/core/compile/jaxpr_trace_duration",
                               compile_cache.RECORDED_TRACE_S * 2,
                               fun_name="a_long_trace")
    assert [e.attrs["fun_name"] for e in trace.RECORDER.events()
            if (e.group, e.name) == ("compile", "trace")] == ["a_long_trace"]
    backend = [e for e in events if e.name == "backend"
               and e.attrs["fun_name"] == "recorded_function"]
    assert len(backend) == 1 and backend[0].attrs["seconds"] > 0
    assert "compile.backend" in trace.RECORDER.render_text()
