"""CPU rehearsals of the NemotronH cell at a tiny size (the cell's own widths
are for the chip): the contract line, the program against the plain reference,
every control and planted fault of `reference/nemotron_h.py` reading not
correct, `work_lm.py` against a hand count, and which readers the cell selects.
"""

import json
import os

import numpy as np
import pytest

from benchmark import compare, run, work_lm
from benchmark.drivers import train_scan_tokens
from benchmark.reference import nemotron_h as ref

CELL = "nemotron3nano.train_4k"
TINY = {"hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
        "chunk_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "router_width": 16, "n_routed_experts": 4, "num_experts_per_tok": 3, "moe_intermediate_size": 48,
        "moe_shared_expert_intermediate_size": 96, "vocab_size": 512, "attention_block": 16}
TINY_TRAFFIC = {"sequence_length": 64}
# Limits for THIS size on the CPU (the cell's own come from chip readings, PERF.md
# section 2): between what the program reads here and what each control and fault reads.
# Read over two seeds: the program loss <= 2.9e-3, grad <= 0.013, first_grad <= 1.7e-3, delta <= 0.016;
# tower_fp8 first_grad >= 0.024, table_bf16 grad >= 1.6, chunk_reset first_grad >= 0.023 and grad >= 0.026,
# noncausal grad >= 0.074, drop_eighth first_grad >= 0.018, no_routed grad 1.0, half_batch grad >= 0.42.
TEST_LIMITS = {"loss_gap": 5e-3, "grad_gap": 0.02, "first_grad_gap": 6e-3, "early_delta_gap": 0.01,
               "delta_gap": 0.025}


_RESOLVE = run.resolve


def _resolve_tiny():
    bench, cell, cfg, traffic = _RESOLVE(CELL)
    return bench, cell, dict(cfg, **TINY), dict(traffic, **TINY_TRAFFIC)


@pytest.fixture(scope="module")
def session():
    _, cell, cfg, traffic = _resolve_tiny()
    s = train_scan_tokens.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=2**31 + 11)
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
    assert {"tables/token", "dense/head", "dense/L0.M", "dense/L1.router", "dense/L1.experts",
            "dense/L1.shared", "dense/L5.attn"} <= set(reference["grad"])
    # the reference counted the pairs its own router sent the held experts
    assert 0 < session.ctx["ref_pairs_per_layer"] <= 2 * 64 * 3


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
    assert {"moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps", "entry.compiles_in_window",
            "trainer.scan_traces"} <= set(line["metrics"])
    assert line["metrics"]["trainer.scan_traces"]["value"] == 1 and line["metrics"]["entry.compiles_in_window"]["value"] == 0
    assert not {"lm.step_mfu", "sparse.token_rows_roofline", "trainer.step_mfu"} & set(line["metrics"])
    assert line["device"]["platform"] == "cpu"
    assert metrics.report()["moe.dropped"] == 0


def test_traced_run_selects_no_reader_of_deepfm_work():
    """The cell's per-layer metrics name no reader that calls `work.tower_layers`
    (DeepFM's widths), and the DeepFM cells select none of the language model's."""
    bench = run.resolve(CELL)[0]
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL, set())}
    mine = run.metrics_of(bench, "per_layer", CELL, e2e)
    readers = {run.load(f"layer_metrics/{m['name']}.json")["reader"] for m in mine}
    for reader in readers:
        text = open(os.path.join(run.HERE, "readers", reader + ".py")).read()
        assert "work." not in text.replace("work_lm.", ""), reader
    names = {m["name"] for m in mine}
    assert {"lm.step_mfu", "lm.nonmatmul_ms_per_step", "sparse.token_rows_roofline", "trainer.step_ms",
            "dense.matmul_ms_per_step", "device.idle_share", "device.peak_hbm_gib"} <= names
    assert not {"trainer.step_mfu", "dense.matmul_roofline", "sparse.apply_roofline", "sparse.ms_per_step"} & names
    for cell in ("deepfm9.train_zipf", "deepfm64.train_zipf", "deepfm9x4.train_zipf"):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {n for n in theirs if n.startswith(("lm.", "moe.")) or n == "sparse.token_rows_roofline"}
        assert {"trainer.step_mfu", "dense.matmul_roofline", "sparse.apply_roofline", "sparse.ms_per_step"} <= theirs


def test_configuration_keeps_every_published_number():
    cfg = run.load("configs/nemotron3-nano-30b-a3b-l9-e8of128.json")
    assert set(cfg["reduced"]) == set(cfg["published"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert ref.pattern_of(cfg) == "MEMEM*EME"
    # 4 x 38.74M (M) + 23.40M (*) + 4 x 100.13M (E) + 2 x 44.04M (table, head) = 666.96M
    dense = sum(int(np.prod(shape)) for _, shape, _ in ref.dense_leaves(cfg))
    assert dense + cfg["vocab_size"] * cfg["hidden_size"] == 666_963_456


def test_work_lm_flops_by_hand():
    cfg = {"hybrid_override_pattern": "ME*", "num_hidden_layers": 3, "hidden_size": 8, "mamba_num_heads": 2,
           "mamba_head_dim": 4, "n_groups": 1, "ssm_state_size": 3, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "router_width": 8, "n_routed_experts": 2,
           "num_experts_per_tok": 2, "moe_intermediate_size": 5, "moe_shared_expert_intermediate_size": 6,
           "vocab_size": 10}
    # batch 2 x seq 4 = 8 tokens. MACs a token -- M: in_proj 8 x (8 + 14 + 2) = 192, out_proj 8 x 8 = 64,
    # recurrence 2 x 8 x 3 = 48: 304. *: q, k, v 8 x (8 + 4 + 4) = 128, o 64: 192. E: router 64, shared
    # 2 x 8 x 6 = 96: 160. head 80. (304 + 192 + 160 + 80) x 8 tokens = 5888.
    # attention: 2 sequences x 2 products x 8 (heads x dim) x 4 x 5 / 2 pairs = 320.
    # routed: balanced 8 x 2 x 2 / 8 = 4 pairs x 2 x 8 x 5 = 320. Forward FLOPs = 2 x 6528 = 13056.
    assert work_lm.balanced_pairs_per_layer(cfg, 8) == 4
    assert work_lm.forward_flops_per_step(cfg, 2, 4) == 13056
    assert work_lm.train_flops_per_step(cfg, 2, 4) == 3 * 13056
    assert work_lm.forward_flops_per_step(cfg, 2, 4, pairs_per_layer=6) == 13056 + 2 * 2 * 80
    ids = np.array([[[1, 2, 2, 3]], [[4, 4, 4, 8]]])
    assert work_lm.token_row_bytes_per_step(cfg, ids) == 2.5 * (32 + 2 * 64)  # pull 8 x 4 B, apply 2 x 64 B


def test_work_lm_at_the_cell_is_two_gigaflops_a_token():
    cfg = run.load("configs/nemotron3-nano-30b-a3b-l9-e8of128.json")
    flops = work_lm.train_flops_per_step(cfg, 2, 4096)
    assert 16.5e12 < flops < 17.0e12 and 2.0e9 < flops / 8192 < 2.08e9
