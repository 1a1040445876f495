"""CPU tests of the benchmark's own code (run: `python -m pytest benchmark/tests -q`).

None describes a TPU topology; every run here is a rehearsal on the CPU at a
tiny size, and proves results and counts, never a time.
"""

import json
import os
import re

import numpy as np
import pytest

from benchmark import compare, run, trace_reduce, work
from benchmark.drivers import train_scan

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
BUFFER = "TPU_PREMAPPED_BUFFER_SIZE"  # run.main exports the mix's staging buffer under this name


@pytest.fixture(autouse=True)
def _environment_as_found():
    before = os.environ.get(BUFFER)
    yield
    os.environ.pop(BUFFER, None)
    if before is not None:
        os.environ[BUFFER] = before


# -- BENCHMARK.json is data that resolves -------------------------------------

@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_files(workload):
    bench, cell, cfg, traffic = run.resolve(workload)
    assert os.path.exists(os.path.join(run.HERE, "drivers", traffic["kind"] + ".py"))
    assert os.path.exists(os.path.join(run.HERE, "reference", cfg["family"] + ".py"))
    limits = run.load(f"limits/{workload}.json")
    assert limits and all(v > 0 for v in limits.values())
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
    assert set(entry["reduced"]) == set(cfg["reduced"])
    e2e = run.metrics_of(bench, "end_to_end", workload, set())
    assert {m["name"] for m in e2e} == {"train_examples_per_s_per_chip", "setup_s"}
    layers = run.metrics_of(bench, "per_layer", workload, {m["name"] for m in e2e})
    assert any("mfu" in m["name"] for m in layers)
    for m in layers:
        spec = run.load(f"layer_metrics/{m['name']}.json")
        assert os.path.exists(os.path.join(run.HERE, "readers", spec["reader"] + ".py"))
    exchange = [m["name"] for m in layers if m["name"].startswith("exchange.")]
    assert bool(exchange) == (cell["chips"] == 4)


def test_names_units_and_shape_of_benchmark_json():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert "peak_hbm_gib" not in [m["name"] for m in BENCH["end_to_end"]]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200


# -- work.py against hand-worked numbers ---------------------------------------

def test_work_flops_dim9_by_hand():
    cfg = run.load("configs/deepfm-criteo1tb-dim9.json")
    # matmuls per example: 13x1, 247x400, 400x400, 400x400, 400x1 = 419,213 MACs;
    # forward 2 FLOPs a MAC, backward twice that: 6 x 419,213 = 2,515,278
    assert work.tower_layers(cfg) == [[13, 1], [247, 400], [400, 400], [400, 400], [400, 1]]
    assert work.matmul_flops_per_example(cfg) == 2_515_278
    assert work.matmul_flops_per_example(cfg) * 4096 == 10_302_578_688  # 10.3 GFLOP a step
    assert work.packed_row_bytes(cfg) == 80  # (10 weights + 10 accumulators) x 4 B


def test_work_unique_rows_and_bytes_by_hand():
    cfg = run.load("configs/deepfm-criteo1tb-dim9.json")
    ids = np.array([[[1, 2, 2], [3, 3, 3]],      # step 1: rows {1, 2, 3}
                    [[4, 4, 4], [4, 8, 8]]])     # step 2: rows {4, 8}
    assert work.unique_rows_per_step(ids) == 2.5
    # pull reads the weights (40 B), apply reads and writes weights + accumulators (2 x 80 B)
    assert work.sparse_bytes_per_step(cfg, ids) == 2.5 * 200
    # four owners (id % 4): step 1 -> {1}, {2}, {3}: worst 1; step 2 -> owner 0 holds {4, 8}: worst 2
    assert work.unique_rows_per_step(ids, chips=4) == 1.5


# -- trace_reduce.py against the recorded trace --------------------------------

def test_parse_and_classify_real_instructions():
    scatter = trace_reduce.parse_op(
        "%fusion.147 = f32[33554432,20]{0,1:T(8,128)} fusion(f32[33554432,20]{0,1:T(8,128)} %get-tuple-element.1517, "
        "s32[106496]{0:T(1024)S(1)} %copy-done.9, f32[106496,20]{0,1:T(8,128)S(1)} %pad_maximum_fusion.8), "
        "kind=kCustom, calls=%fused_computation.8.clone.clone")
    assert (scatter["name"], scatter["opcode"], scatter["kind"]) == ("fusion.147", "fusion", "kCustom")
    assert trace_reduce.classify(scatter) == ("other", "scatter")
    assert trace_reduce.label(scatter, "scatter") == "fusion.147__scatter__f32_33554432_20_"
    conv = trace_reduce.parse_op(
        "%convolution_add_fusion.5 = bf16[4096,400]{0,1:T(8,128)(2,1)S(1)} fusion(bf16[247,400]{1,0:T(8,128)(2,1)S(1)} "
        "%copy-done.10, bf16[4096,234]{0,1:T(8,128)(2,1)S(1)} %reshape.1021), kind=kOutput, calls=%fused_computation.28")
    assert trace_reduce.classify(conv)[0] == "dot"
    a2a = trace_reduce.parse_op("%all-to-all.3 = bf16[4,26624,10]{2,1,0} all-to-all(bf16[4,26624,10]{2,1,0} %x), dimensions={0}")
    assert trace_reduce.classify(a2a)[0] == "collective"
    start = trace_reduce.parse_op(
        "%copy-start.3 = (s32[1,4096,26]{2,1,0:T(8,128)}, s32[1,4096,26]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
        "copy-start(s32[1,4096,26]{2,1,0:T(8,128)S(1)} %copy.40)")
    assert start["opcode"] == "copy-start" and trace_reduce.classify(start) == ("other", "data_movement")
    loop = trace_reduce.parse_op("%while.8 = (s32[]{:T(128)}, f32[1]{0:T(128)}) while((s32[]{:T(128)}, f32[1]{0:T(128)}) %t), condition=%c, body=%b")
    assert loop["opcode"] in trace_reduce.CONTAINERS


def test_trace_reduce_on_recorded_trace():
    events = json.load(open(os.path.join(run.HERE, "fixtures", "trace_small.json")))
    s = trace_reduce.reduce_events(events, chips=1)
    ops = list(events["devices"].values())[0]["ops"]
    # the ops of one line never overlap, so their plain sum (the while container
    # left out) has to come out again as the union with the async copies inside them
    plain = sum(d for t, _, d in ops if " while(" not in t) / 1e9
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(0.090184624, rel=1e-6)
    assert plain <= s["busy_s"] <= plain * 1.01
    assert s["class_s"]["dot"] == pytest.approx(0.000211573, rel=1e-5)
    assert s["class_s"]["other"] + s["class_s"]["dot"] == pytest.approx(plain, rel=1e-9)
    assert s["class_s"]["collective"] == 0.0 and s["exposed_collective_s"] == 0.0
    assert s["top_ops"][0][0] == "pad_maximum_fusion.4__other__f32_33554432_20_"
    assert s["top_ops"][1][0] == "fusion.147__scatter__f32_33554432_20_"
    # the longest gap is the cut in the recording; the next is the real one between
    # two dispatches, and the host was waiting on the fence in it
    assert s["idle_gaps"][1] == ["fence_loss", pytest.approx(0.002931691, rel=1e-6)]


def test_trace_reduce_collectives_and_exposed_part_by_hand():
    dev = {"ops": [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0.0, 100.0],
                   ["%all-to-all-done.1 = bf16[4,8]{1,0} all-to-all-done(bf16[4,8]{1,0} %s)", 100.0, 50.0],
                   ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b), kind=kOutput", 200.0, 40.0]],
           "async": [["%all-to-all-start.1 = bf16[4,8]{1,0} all-to-all-start(bf16[4,8]{1,0} %x)", 60.0, 90.0]]}
    events = {"devices": {"/device:TPU:0": dev, "/device:TPU:1": dev}, "host": [["fence_loss", 140.0, 70.0]]}
    s = trace_reduce.reduce_events(events, chips=2)
    assert s["class_s"]["collective"] == pytest.approx(90e-9)      # 60..150
    assert s["exposed_collective_s"] == pytest.approx(50e-9)      # 100..150: nothing else runs
    assert s["class_s"]["other"] == pytest.approx(100e-9) and s["class_s"]["dot"] == pytest.approx(40e-9)
    assert s["busy_s"] == pytest.approx(190e-9) and s["idle_gaps"][0] == ["fence_loss", pytest.approx(50e-9)]
    with pytest.raises(RuntimeError):
        trace_reduce.reduce_events(events, chips=4)


def test_reader_returns_nothing_where_nothing_ran():
    from benchmark.readers import class_ms_per_step, roofline
    trace = {"class_s": {"dot": 0.0, "other": 1.0, "collective": 0.0}}
    assert class_ms_per_step.read(trace, {"steps": 4}, {"class": "dot"}) is None
    assert roofline.read(trace, {"steps": 4}, {"class": "dot", "work": "tower_flops"}) is None
    assert roofline.read(None, {"steps": 4}, {"class": "dot", "work": "tower_flops"}) is None


# -- compare.py ------------------------------------------------------------------

def test_compare_measures_gaps_of_norms_by_worst_leaf():
    ref = {"losses": [0.8, 0.7, 0.6, 0.5], "grad": {"a": 1.0, "b": 1e-9, "c": 2.0}, "first_grad": {"a": 1.0},
           "delta": {"a": 1.0, "b": 1.0, "c": 1.0}, "early_delta": {"a": 2.0}}
    prog = {"losses": [0.8, 0.7007, 0.6, 0.55], "grad": {"a": 1.01, "b": 2e-9, "c": 2.0}, "first_grad": {"a": 1.0},
            "delta": {"a": 1.0, "b": 2.0, "c": 1.0}, "early_delta": {"a": 2.1}}
    n = compare.numbers(prog, ref)
    assert n["loss_gap"] == pytest.approx(0.1) and n["loss3_gap"] == pytest.approx(0.001)
    assert n["early_delta_gap"] == pytest.approx(0.05)
    assert n["grad_gap"] == pytest.approx(0.01)   # leaf b is all but zero: held against the median leaf
    assert n["delta_gap"] == pytest.approx(1.0)   # a leaf moved double reads 1
    v = compare.judge(n, {"loss_gap": 0.01, "delta_gap": 0.5})
    assert not v["correct"] and list(v["compared"]) == ["loss_gap", "delta_gap"]
    assert not compare.judge({"loss_gap": float("nan")}, {"loss_gap": 1.0})["correct"]


# -- a rehearsal of whole runs on the CPU ---------------------------------------

TINY = {"vocabulary": 1 << 16}
TINY_TRAFFIC = {"batch_per_chip": 256, "steps_per_dispatch": 4}
# Limits for THIS size on the CPU, where the tower's bf16 rounds otherwise than on the
# chip and 256 rows average less: between what the program reads here over four seeds
# and three cells (loss <= 7.6e-4, grad <= 6.4e-3, first_grad <= 3.9e-4, delta <= 7.7e-3)
# and what the controls read (table_bf16: grad >= 3.7, first_grad >= 12; tower_fp8:
# loss >= 2.8e-3). The cells' own limits come from chip readings (PERF.md section 2).
TEST_LIMITS = {"loss_gap": 2e-3, "grad_gap": 0.03, "first_grad_gap": 3e-3, "delta_gap": 0.03}


def _tiny(monkeypatch, workload):
    real = run.resolve

    def resolve(name):
        bench, cell, cfg, traffic = real(name)
        return bench, cell, dict(cfg, **TINY), dict(traffic, **TINY_TRAFFIC)

    load = run.load
    from openembedding_tpu.utils import compile_cache
    monkeypatch.setattr(run, "load", lambda rel: dict(TEST_LIMITS) if rel.startswith("limits/") else load(rel))
    monkeypatch.setattr(run, "resolve", resolve)
    monkeypatch.setattr(compile_cache, "enable", lambda: None)  # a test sets no process-wide cache
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def _last_line(capsys):
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_contract_line_and_is_correct(workload, monkeypatch, capsys):
    _tiny(monkeypatch, workload)
    monkeypatch.delenv(BUFFER, raising=False)
    assert run.main(["--workload", workload, "--seed", str(2**31 + 77), "--seconds", "0.3", "--trace", "0"]) == 0
    assert os.environ[BUFFER] == str(run.resolve(workload)[3]["host_transfer_buffer_bytes"])
    line, err = _last_line(capsys)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 12
    assert set(line["metrics"]) == {"train_examples_per_s_per_chip", "setup_s"}
    assert line["device"]["platform"] == "cpu" and set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_no_accelerator_and_no_cpu_by_name_is_refused(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        run.device_block(1, caller_asked_cpu=False)
    assert e.value.code not in (0, None)


def _session(workload, seed):
    bench, cell, cfg, traffic = run.resolve(workload)
    s = train_scan.open_session(cfg=dict(cfg, **TINY), traffic=dict(traffic, **TINY_TRAFFIC),
                                chips=cell["chips"], seed=seed)
    s.setup()
    s.free()
    return s


@pytest.mark.parametrize("workload", CELLS)
def test_program_passes_the_reference_and_each_control_fails(workload):
    import jax
    limits = TEST_LIMITS
    s = _session(workload, seed=11)
    with jax.default_matmul_precision("highest"):
        ref = s.reference_summary()
        assert compare.judge(compare.numbers(s.prog, ref), limits)["correct"]
        for control in ("table_bf16", "tower_fp8"):
            low = s.reference_summary(precision=control)
            assert not compare.judge(compare.numbers(low, ref), limits)["correct"], control
        for fault in ("half_batch",) + (("no_exchange",) if s.chips > 1 else ()):
            bad = s.reference_summary(fault=fault)
            assert not compare.judge(compare.numbers(bad, ref), limits)["correct"], fault


# -- the rest of a run with the timed path broken underneath --------------------

def _break(monkeypatch, fault):
    import jax
    import jax.numpy as jnp
    build = train_scan.Session._build_program

    def broken(self, sample):
        if fault == "no_exchange":  # every shard keeps its own buckets: the exchange left out
            monkeypatch.setattr(jax.lax, "all_to_all", lambda x, *a, **k: x)
        build(self, sample)
        many = self.many
        if fault == "unchanged_state":
            def same(state, stacked):
                _, metrics = many(jax.tree_util.tree_map(jnp.copy, state), stacked)
                return state, metrics
            self.many = same
        if fault == "half_batch":  # the second half of every worker's rows weighs nothing
            per = self.batch // self.chips
            w = (np.arange(self.batch) % per < per // 2).astype(np.float32)
            w = jnp.broadcast_to(w, (self.k_steps, self.batch))
            self.stacked = dict(self.stacked, weight=jax.device_put(w, self.stacked["label"].sharding))
            if self.mesh is not None:
                self.trainer._train_many_fn = None
                self.many = self.trainer.jit_train_many(self.stacked, self.state)

    monkeypatch.setattr(train_scan.Session, "_build_program", broken)


@pytest.mark.parametrize("workload,fault", [(w["name"], f) for w in BENCH["workloads"]
                                            for f in ("unchanged_state", "half_batch") +
                                            (("no_exchange",) if w["chips"] == 4 else ())])
def test_a_broken_timed_path_reads_not_correct(workload, fault, monkeypatch, capsys):
    _tiny(monkeypatch, workload)
    monkeypatch.setenv(BUFFER, "1048576")  # a caller's own value stands
    _break(monkeypatch, fault)
    run.main(["--workload", workload, "--seed", "5", "--seconds", "0.2", "--trace", "0"])
    line, _ = _last_line(capsys)
    assert line["correct"] is False, line["compared"]
    assert os.environ[BUFFER] == "1048576"
