"""CPU rehearsals of the Ouro cell at a tiny size (the cell's own widths are
for the chip): the contract line, the program against the plain reference,
every control and planted fault of `reference/ouro.py` reading not correct,
the driver's keyword map, `work_loop.py` against a hand count, and which
readers the cell selects.
"""

import inspect
import json
import math
import os

import pytest

from benchmark import compare, run, work_loop
from benchmark.drivers import train_scan_lm, train_scan_lm_keywords
from benchmark.reference import ouro as ref

CELL = "ouro.train_4k"
TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 16, "intermediate_size": 96, "vocab_size": 512, "attention_block": 16}
TINY_TRAFFIC = {"sequence_length": 64}
# The controls and faults are read against a program that computes in float32 here, so that the
# smallest fault stands clear of the program's own rounding. Limits for THIS size on the CPU (the
# cell's own come from chip readings, PERF.md section 2), read over two seeds: the f32 program
# loss <= 1.5e-7, grad <= 1.2e-7, early_delta <= 4.4e-7, delta <= 4.7e-7; the smallest readings of
# what must fail: last_use_only loss 1.1e-4 (the forward pass is the sound one) but grad 0.87,
# table_bf16 loss 1.2e-4 and early_delta 0.94, tower_fp8 loss 3.0e-3 / early_delta 8.5e-3,
# no_entropy grad 0.0101 / early_delta 5.2e-4, every other fault grad >= 0.05.
F32 = {"tower_dtype": "float32"}
TEST_LIMITS = {"loss_gap": 3e-5, "grad_gap": 1e-4, "early_delta_gap": 1e-4, "delta_gap": 1e-4}
# the same cell as the chip runs it, bf16: the program's own reading over two seeds is loss <= 2.8e-4,
# grad <= 2.5e-3, early_delta <= 6.8e-4, delta <= 2.4e-3
BF16_LIMITS = {"loss_gap": 1e-3, "grad_gap": 8e-3, "early_delta_gap": 2.5e-3, "delta_gap": 8e-3}


_RESOLVE = run.resolve


def _resolve_tiny(**more):
    bench, cell, cfg, traffic = _RESOLVE(CELL)
    return bench, cell, dict(cfg, **TINY, **more), dict(traffic, **TINY_TRAFFIC)


@pytest.fixture(scope="module")
def session():
    _, cell, cfg, traffic = _resolve_tiny(**F32)
    s = train_scan_lm_keywords.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=2**31 + 11)
    s.setup()
    s.context()
    s.free()
    return s


@pytest.fixture(scope="module")
def reference(session):
    import jax
    with jax.default_matmul_precision("highest"):
        return session.reference_summary()


def test_program_passes_the_reference(session, reference):
    verdict = compare.judge(compare.numbers(session.prog, reference), TEST_LIMITS)
    assert verdict["correct"], verdict["compared"]
    assert set(session.prog["grad"]) == set(reference["grad"])
    assert {"tables/token", "dense/head", "dense/gate", "dense/norm_f", "dense/L0.attn", "dense/L0.mlp",
            "dense/L1.attn", "dense/L1.mlp"} == set(reference["grad"])
    assert "ref_pairs_per_layer" not in session.ctx  # no routed layer: the work module is handed None


@pytest.mark.parametrize("kind,name", [("precision", c) for c in ref.CONTROLS] + [("fault", f) for f in ref.FAULTS])
def test_each_control_and_fault_reads_not_correct(session, reference, kind, name):
    import jax
    with jax.default_matmul_precision("highest"):
        low = session.reference_summary(**{kind: name})
    verdict = compare.judge(compare.numbers(low, reference), TEST_LIMITS)
    assert not verdict["correct"], verdict["compared"]


def test_rehearsal_prints_the_contract_line_and_is_correct(monkeypatch, capsys):
    from openembedding_tpu.utils import compile_cache
    load = run.load
    monkeypatch.setattr(run, "load", lambda rel: dict(BF16_LIMITS) if rel.startswith("limits/") else load(rel))
    monkeypatch.setattr(run, "resolve", lambda name: _resolve_tiny())
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU_PREMAPPED_BUFFER_SIZE", "1048576")
    from openembedding_tpu.utils import metrics
    metrics.reset_all()  # the registry is the process's: the module's session traced a scan too
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "0.3", "--trace", "1"]) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 12
    assert set(line["compared"]) == set(BF16_LIMITS)
    # a traced rehearsal has no device trace; the program's counters are read all the same
    # (`attn.fused_cores` is left out: a head of 16 is no shape the kernel takes)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"loop.traced_passes", "loop.exit_entropy", "loop.last_exit_mass", "loop.loss_last_over_first",
            "sparse.shared_pulls", "sparse.apply_fill", "sparse.apply_full_steps", "entry.compiles_in_window",
            "trainer.scan_traces", "trainer.windows"} <= set(got)
    assert got["trainer.scan_traces"] == 1 and got["entry.compiles_in_window"] == 0
    # ONE scanned walk a trace of the module, and two traces of it (`init` under `eval_shape`, the scan)
    assert got["loop.traced_passes"] == 2 and got["sparse.shared_pulls"] == 1
    # the gate starts near a half: p near (1/2, 1/4, 1/8, 1/8)
    assert 1.0 < got["loop.exit_entropy"] < math.log(4) and 0.05 < got["loop.last_exit_mass"] < 0.25
    assert 0.9 < got["loop.loss_last_over_first"] < 1.1
    assert not {"lm.ouro_step_mfu", "lm.zaya_step_mfu", "lm.solar_step_mfu", "lm.mla_step_mfu", "lm.step_mfu",
                "trainer.step_mfu", "sparse.token_rows_roofline"} & set(got)
    assert line["device"]["platform"] == "cpu"


def test_the_keyword_map_is_the_configurations_own():
    from openembedding_tpu import models
    cfg = run.load("configs/ouro-2.6b-ut4.json")
    train_scan_lm_keywords.with_row(cfg)
    assert train_scan_lm.KEYWORDS["ouro"] == ((), cfg["make_keywords"])
    assert set(cfg["make_keywords"].values()) <= set(inspect.signature(models.make_ouro).parameters)
    model = train_scan_lm_keywords.build_model(dict(cfg, **TINY))
    assert model.config["total_ut_steps"] == 4 and model.config["rope_theta"] == 1000000
    assert model.config["exit_entropy_weight"] == 0.1 and not model.specs["token"].sparse_as_dense


def test_traced_run_selects_this_familys_readers_alone():
    bench = run.resolve(CELL)[0]
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL, set())}
    mine = run.metrics_of(bench, "per_layer", CELL, e2e)
    readers = {run.load(f"layer_metrics/{m['name']}.json")["reader"] for m in mine}
    for reader in readers:
        text = open(os.path.join(run.HERE, "readers", reader + ".py")).read()
        assert "work_mla" not in text and "work_lm.train_flops" not in text and "pattern_of" not in text, reader
    names = {m["name"] for m in mine}
    # `<=`: a later PR may enter this cell in a metric of its own
    assert {"lm.ouro_step_mfu", "loop.traced_passes", "loop.exit_entropy", "loop.last_exit_mass",
            "loop.loss_last_over_first", "attn.fused_cores", "lm.nonmatmul_ms_per_step",
            "sparse.token_rows_roofline", "sparse.shared_pulls", "sparse.apply_fill", "sparse.apply_full_steps",
            "trainer.step_ms", "dense.matmul_ms_per_step", "device.idle_share", "device.peak_hbm_gib",
            "entry.compiles_in_window", "trainer.scan_traces", "trainer.windows", "trainer.init_s",
            "trainer.scan_trace_s", "trainer.scan_compile_s", "trainer.scan_cache_misses",
            "trainer.scan_executables"} <= names
    # no routed layer, no other family's share
    assert not {n for n in names if n.startswith(("moe.", "kda.", "cca.", "router.", "lm.zaya_", "lm.solar_",
                                                  "lm.mla_", "exchange."))}
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {n for n in theirs if n.startswith(("loop.", "lm.ouro_"))}


def test_work_loop_flops_by_hand():
    cfg = {"hidden_size": 8, "num_hidden_layers": 2, "total_ut_steps": 3, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 3, "intermediate_size": 5, "vocab_size": 10}
    # a layer application, MACs a token: q and o 2 x 8 x 12 = 192, k and v 2 x 8 x 6 = 96, SwiGLU 3 x 8 x 5 = 120
    assert work_loop.layer_macs_per_token(cfg) == 408
    # batch 2 x seq 6 = 12 tokens, 3 passes x 2 layers = 6 applications: 6 x 12 x 408 = 29376
    # cores: 6 applications x 2 sequences x 4 heads x 2 x 3 x 21 pairs = 6048
    # exits: 3 passes x 12 tokens x (head 80 + gate 8) = 3168
    assert work_loop.forward_flops_per_step(cfg, 2, 6) == 2 * (29376 + 6048 + 3168)
    assert work_loop.train_flops_per_step(cfg, 2, 6) == 3 * 2 * 38592
    assert work_loop.train_flops_per_step(cfg, 2, 6, pairs_per_layer=99.0) == 3 * 2 * 38592  # nothing is routed


def test_work_loop_at_the_cell_is_57_teraflops_a_step():
    cfg = run.load("configs/ouro-2.6b-ut4.json")
    assert 56.8e12 < work_loop.train_flops_per_step(cfg, 1, 4096) < 57.0e12
    # of the forward's 18.97 TFLOP: a layer application 0.4896 (products 0.4209, the causal core 0.0687),
    # an exit's head 0.8246: the four exits are 17.4% of the forward
    assert 0.4208e12 < 2 * 4096 * work_loop.layer_macs_per_token(cfg) < 0.4210e12
    assert 0.0687e12 < 2 * 16 * 2 * 128 * 4096 * 4097 / 2 < 0.0688e12
    assert 0.8246e12 < 2 * 4096 * 2048 * 49152 < 0.8247e12
    assert 0.173 < 4 * 0.8246 / 18.967 < 0.175
