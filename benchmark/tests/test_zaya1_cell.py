"""CPU rehearsals of the ZAYA1 cell at a tiny size (the cell's own widths are
for the chip): the contract line, the program against the plain reference,
every control and planted fault of `reference/zaya1.py` reading not correct,
the driver's keyword map, `work_cca.py` against a hand count, and which
readers the cell selects.
"""

import inspect
import json
import os

import pytest

from benchmark import compare, run, work_cca
from benchmark.drivers import train_scan_lm, train_scan_lm_keywords
from benchmark.reference import zaya1 as ref

CELL = "zaya1.train_8k"
TINY = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "router_hidden_size": 16, "router_width": 8, "num_experts": 4,
        "moe_intermediate_size": 48, "vocab_size": 512, "attention_block": 16, "table_init_stddev": 0.3}
TINY_TRAFFIC = {"sequence_length": 64}
# The controls, faults and calibrations are read against a program that computes in float32 here:
# a bf16 tower's own top-1 flips (one token's whole expert term) read 1e-3 to 1.4e-2 in every number
# at this size, above what a bf16 router or a bf16 attention score costs, and nothing tells them
# apart (on the chip neither: `reference/zaya1.CALIBRATIONS`, PERF.md section 2). Limits for THIS size on the CPU (the cell's own come from chip readings, PERF.md
# section 2), read over two seeds: the f32 program loss <= 1.1e-7, grad <= 7.1e-7, delta <= 4.2e-7;
# softmax_bf16 (the smallest fault) loss >= 8.4e-5, grad >= 1.7e-4 and delta >= 1.5e-4, router_bf16 grad >= 4e-3,
# untied_head grad >= 0.19 (its loss moves 3e-5 at most: the first step's logits are the tied ones),
# table_bf16 delta >= 0.57, tower_fp8 loss >= 8e-3; every other fault reads >= 0.015 in grad_gap.
F32 = {"tower_dtype": "float32"}
TEST_LIMITS = {"loss_gap": 3e-6, "grad_gap": 1.2e-5, "delta_gap": 1.2e-5}
# the same cell as the chip runs it, bf16: the program's own reading over two seeds is loss <= 2.5e-3,
# grad <= 0.0141, delta <= 0.0136
BF16_LIMITS = {"loss_gap": 8e-3, "grad_gap": 0.045, "delta_gap": 0.045}


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
    assert {"dense/table", "dense/head", "dense/L0.cca", "dense/L1.router", "dense/L2.experts",
            "dense/L0.ffn"} <= set(reference["grad"])
    assert not any(leaf.startswith("tables/") for leaf in reference["grad"])  # the table trains densely
    # the reference counted the pairs its own router sent the held experts: one expert a token
    assert 0 < session.ctx["ref_pairs_per_layer"] <= 64


@pytest.mark.parametrize("kind,name", [("precision", c) for c in ref.CONTROLS] + [("fault", f) for f in ref.FAULTS]
                         + list(ref.CALIBRATIONS))
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
    assert {"moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps", "moe.dropped", "router.gate_mean",
            "cca.key_temp_max", "entry.compiles_in_window", "trainer.scan_traces", "trainer.windows"} \
        <= set(line["metrics"])
    assert line["metrics"]["trainer.scan_traces"]["value"] == 1 and line["metrics"]["entry.compiles_in_window"]["value"] == 0
    assert line["metrics"]["moe.dropped"]["value"] == 0
    assert 1.0 / 8 < line["metrics"]["router.gate_mean"]["value"] < 1.0
    assert 1.0 <= line["metrics"]["cca.key_temp_max"]["value"] < 1.1
    assert not {"lm.zaya_step_mfu", "lm.solar_step_mfu", "lm.mla_step_mfu", "lm.step_mfu", "trainer.step_mfu",
                "sparse.token_rows_roofline", "sparse.shared_pulls", "sparse.apply_fill"} & set(line["metrics"])
    assert line["device"]["platform"] == "cpu"


def test_the_keyword_map_is_the_configurations_own():
    """The driver enters the family's row of `train_scan_lm.KEYWORDS` from the
    file's `make_keywords`; every keyword is one of `make_zaya1`'s, and a
    dotted path reads a nested group."""
    from openembedding_tpu import models
    cfg = run.load("configs/zaya1-8b-e8of16.json")
    flat = train_scan_lm_keywords.with_row(cfg)
    assert train_scan_lm.KEYWORDS["zaya1"] == ((), cfg["make_keywords"])
    assert flat["rope_parameters.hybrid.rope_theta"] == 5000000
    names = inspect.signature(models.make_zaya1).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)
    model = train_scan_lm_keywords.build_model(dict(cfg, **TINY))
    assert model.config["experts_held"] == 4 and model.config["num_experts"] == 8
    assert model.config["rope_theta"] == 5000000 and model.specs["token"].sparse_as_dense


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
    assert {"lm.zaya_step_mfu", "router.gate_mean", "cca.key_temp_max", "attn.fused_cores",
            "lm.nonmatmul_ms_per_step", "moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps",
            "moe.dropped", "trainer.step_ms", "dense.matmul_ms_per_step", "device.idle_share",
            "device.peak_hbm_gib", "entry.compiles_in_window", "trainer.scan_traces", "trainer.windows",
            "trainer.init_s", "trainer.scan_trace_s", "trainer.scan_compile_s", "trainer.scan_cache_misses",
            "trainer.scan_executables"} <= names
    # the table trains densely: nothing of the sparse path's is read here
    assert not {n for n in names if n.startswith(("sparse.", "kda.", "lm.solar_", "lm.mla_", "exchange."))}
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {n for n in theirs if n.startswith(("cca.", "router.", "lm.zaya_"))}


def test_work_cca_flops_by_hand():
    cfg = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 3, "cca_time0": 2, "cca_time1": 2, "router_hidden_size": 5, "router_width": 4,
           "num_experts": 2, "num_experts_per_tok": 1, "moe_intermediate_size": 6, "vocab_size": 10}
    # attention sub-layer, MACs a token: q~ and o 2 x 8 x 12 = 192, k~ and the two value halves 2 x 8 x 6 = 96,
    # Conv_B 2 taps x 6 heads x 3 x 3 = 108 -> 396 (Conv_A is depthwise: no product)
    assert work_cca.cca_macs_per_token(cfg) == 396
    # router: down 8 x 5 = 40, two square layers 2 x 25 = 50, output 5 x 4 = 20 -> 110
    assert work_cca.router_macs_per_token(cfg) == 110
    # batch 2 x seq 6 = 12 tokens: 12 x (2 x (396 + 110) + head 80) = 12 x 1092 = 13104
    # core: 2 layers x 2 sequences x 4 heads x 2 x 3 x 21 pairs = 2016
    # routed: 2 layers x balanced 12 x 1 x 2 / 4 = 6 pairs x 3 x 8 x 6 = 144 -> 1728
    assert work_cca.balanced_pairs_per_layer(cfg, 12) == 6
    assert work_cca.forward_flops_per_step(cfg, 2, 6) == 2 * (13104 + 2016 + 1728)
    assert work_cca.train_flops_per_step(cfg, 2, 6) == 3 * 2 * 16848
    assert work_cca.forward_flops_per_step(cfg, 2, 6, pairs_per_layer=8) == 2 * (13104 + 2016 + 2 * 8 * 144)


def test_work_cca_at_the_cell_is_9_teraflops_a_step():
    cfg = run.load("configs/zaya1-8b-e8of16.json")
    flops = work_cca.train_flops_per_step(cfg, 1, 8192)
    assert 9.3e12 < flops < 9.6e12
    # of the forward's 3.16 TFLOP: the tied head 1.10, six causal cores 0.82, six layers of 4096 balanced pairs 0.62
    assert 1.09e12 < 2 * 8192 * 2048 * 32784 < 1.11e12
    assert 0.82e12 < 6 * 2 * 8 * 2 * 128 * 8192 * 8193 / 2 < 0.83e12
    assert 0.61e12 < 6 * 2 * 4096 * 3 * 2048 * 2048 < 0.63e12
