"""CPU rehearsals of the Solar-Open2 cell at a tiny size (the cell's own
widths are for the chip): the contract line, the program against the plain
reference, every control and planted fault of `reference/solar_open2.py`
reading not correct, the driver's keyword map, `work_kda.py` against a hand
count, and which readers the cell selects.
"""

import inspect
import json
import os

import pytest

from benchmark import compare, run, work_kda
from benchmark.drivers import train_scan_lm, train_scan_lm_keywords
from benchmark.reference import solar_open2 as ref

CELL = "solar-open2.train_8k"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 2, "num_kv_heads": None},
        "gate_rank": 8, "chunk_size": 16, "router_width": 16, "n_routed_experts": 4, "num_experts_per_tok": 3,
        "moe_intermediate_size": 48, "vocab_size": 512, "attention_block": 16}
TINY_TRAFFIC = {"sequence_length": 64}
# Limits for THIS size on the CPU (the cell's own come from chip readings, PERF.md
# section 2): between what the program reads here and what each control and fault reads.
# Read over two seeds: the program loss <= 8.3e-4, grad <= 0.0105, early_delta <= 4.5e-3, delta <= 9.5e-3;
# tower_fp8 loss >= 2.2e-3 and grad >= 0.024, table_bf16 early_delta >= 0.86, half_batch grad >= 0.63,
# no_routed grad 1.0, drop_eighth grad >= 0.084, noncausal grad >= 0.28, chunk_reset grad >= 0.127,
# no_decay grad >= 0.125, no_delta grad >= 0.137, beta_unscaled grad >= 0.19, no_gate grad >= 1.2.
TEST_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 0.017, "early_delta_gap": 9e-3, "delta_gap": 0.017}


_RESOLVE = run.resolve


def _resolve_tiny():
    bench, cell, cfg, traffic = _RESOLVE(CELL)
    return bench, cell, dict(cfg, **TINY), dict(traffic, **TINY_TRAFFIC)


@pytest.fixture(scope="module")
def session():
    _, cell, cfg, traffic = _resolve_tiny()
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
    assert {"tables/token", "dense/head", "dense/L0.attn", "dense/L1.kda", "dense/L2.kda", "dense/L3.kda",
            "dense/L0.router", "dense/L1.experts", "dense/L3.shared"} <= set(reference["grad"])
    # the reference counted the pairs its own router sent the held experts
    assert 0 < session.ctx["ref_pairs_per_layer"] <= 64 * 3


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
    monkeypatch.setattr(run, "load", lambda rel: dict(TEST_LIMITS) if rel.startswith("limits/") else load(rel))
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
    # a traced rehearsal has no device trace; the program's counters are read all the same
    # (`attn.fused_cores` is left out: a head of 16 is no shape the kernel takes)
    assert {"moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps", "kda.scans", "kda.chunk_decay_floor",
            "entry.compiles_in_window", "trainer.scan_traces"} <= set(line["metrics"])
    assert line["metrics"]["trainer.scan_traces"]["value"] == 1 and line["metrics"]["entry.compiles_in_window"]["value"] == 0
    assert line["metrics"]["kda.scans"]["value"] % 3 == 0 and line["metrics"]["kda.scans"]["value"] > 0
    assert 0.0 < line["metrics"]["kda.chunk_decay_floor"]["value"] < 1.0
    assert not {"lm.solar_step_mfu", "lm.mla_step_mfu", "lm.step_mfu", "trainer.step_mfu"} & set(line["metrics"])
    assert line["device"]["platform"] == "cpu"
    assert metrics.report()["moe.dropped"] == 0


def test_the_keyword_map_is_the_configurations_own():
    """The driver enters the family's row of `train_scan_lm.KEYWORDS` from the
    file's `make_keywords`; every keyword is one of `make_solar_open2`'s, and a
    dotted path reads a nested group."""
    from openembedding_tpu import models
    cfg = run.load("configs/solar-open2-250b-l4-h8of64.json")
    flat = train_scan_lm_keywords.with_row(cfg)
    assert train_scan_lm.KEYWORDS["solar_open2"] == ((), cfg["make_keywords"])
    assert flat["linear_attn_config.num_heads"] == cfg["linear_attn_config"]["num_heads"] == 8
    names = inspect.signature(models.make_solar_open2).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)
    model = train_scan_lm_keywords.build_model(dict(cfg, **TINY))
    assert model.config["linear_num_heads"] == 2 and model.config["experts_held"] == 4
    assert model.config["n_routed_experts"] == 16 and model.config["gqa_layers"] == [0]


def test_traced_run_selects_this_familys_readers_alone():
    bench = run.resolve(CELL)[0]
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL, set())}
    mine = run.metrics_of(bench, "per_layer", CELL, e2e)
    readers = {run.load(f"layer_metrics/{m['name']}.json")["reader"] for m in mine}
    for reader in readers:
        text = open(os.path.join(run.HERE, "readers", reader + ".py")).read()
        assert "work_mla" not in text and "work_lm.train_flops" not in text and "pattern_of" not in text, reader
    names = {m["name"] for m in mine}
    assert {"lm.solar_step_mfu", "kda.scans", "kda.chunk_decay_floor", "attn.fused_cores", "lm.nonmatmul_ms_per_step",
            "sparse.token_rows_roofline", "moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps",
            "trainer.step_ms", "dense.matmul_ms_per_step", "device.idle_share", "device.peak_hbm_gib",
            "entry.compiles_in_window", "trainer.scan_traces"} == names
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {n for n in theirs if n.startswith(("kda.", "lm.solar_"))}


def test_work_kda_flops_by_hand():
    cfg = {"hidden_size": 8, "num_hidden_layers": 2, "gqa_layers": [0, 4], "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 3, "use_gqa_gate": True,
           "linear_attn_config": {"num_heads": 2, "head_dim": 4, "short_conv_kernel_size": 4}, "gate_rank": 3,
           "chunk_size": 4, "router_width": 8, "n_routed_experts": 2, "num_experts_per_tok": 2,
           "moe_intermediate_size": 5, "n_shared_experts": 1, "vocab_size": 10}
    # softmax layer, MACs a token: q, gate, o 3 x 8 x 6 = 144 + k, v 2 x 8 x 3 = 48 -> 192
    assert work_kda.softmax_macs_per_token(cfg) == 192
    # linear layer: q, k, v, o 4 x 8 x 8 = 256 + two low-rank pairs 2 x (8 x 3 + 3 x 8) = 96 + beta 8 x 2 = 16 -> 368
    assert work_kda.linear_macs_per_token(cfg) == 368
    # a chunk of 4 positions of one head, Dk = Dv = 4: A and P 4 x 4 x 4 = 64; the solve 6 rows x 8 = 48;
    # K_end^T [W_k | W_v] 4 x 4 x 8 = 128; the state's step 4 x 4 x 4 = 64; U and (Q e^G) S 2 x 64 = 128; P U 10 x 4 = 40
    assert work_kda.chunk_macs(4, 4, 4) == 64 + 48 + 128 + 64 + 128 + 40 == 472
    # batch 2 x seq 6 = 12 tokens; 2 chunks a sequence (the second half empty, counted whole)
    assert work_kda.scan_flops(cfg, 2, 6) == 2 * 2 * 2 * 2 * 472 == 7552
    # a layer's router 64 + shared 3 x 8 x 5 = 120 -> 184 a token; head 80
    # tokens: 12 x (2 x 184 + 192 + 368 + 80) = 12 x 1008 = 12096
    # core: 1 softmax layer x 2 sequences x 2 heads x 2 x 3 x 21 pairs = 504
    # routed: 2 layers x balanced 12 x 2 x 2 / 8 = 6 pairs x 3 x 8 x 5 = 120 -> 1440
    assert work_kda.linear_layers(cfg) == 1 and work_kda.balanced_pairs_per_layer(cfg, 12) == 6
    assert work_kda.forward_flops_per_step(cfg, 2, 6) == 2 * (12096 + 504 + 1440) + 7552
    assert work_kda.train_flops_per_step(cfg, 2, 6) == 3 * (2 * 14040 + 7552)
    assert work_kda.forward_flops_per_step(cfg, 2, 6, pairs_per_layer=8) == 2 * (14040 + 2 * 2 * 120) + 7552
    assert work_kda.scan_bytes(cfg, 2, 6) == 12 * (4 * 8 * 2 + 8 * 4 + 2 * 4)


def test_work_kda_at_the_cell_is_13_teraflops_a_step():
    cfg = run.load("configs/solar-open2-250b-l4-h8of64.json")
    flops = work_kda.train_flops_per_step(cfg, 1, 8192)
    assert 12.8e12 < flops < 13.1e12
    # the head is 1.65 of the forward's 4.3 TFLOP; three chunked scans 0.047
    assert 1.64e12 < 2 * 8192 * 4096 * 24576 < 1.66e12
    assert 0.045e12 < 3 * work_kda.scan_flops(cfg, 1, 8192) < 0.048e12
