"""CPU rehearsals of the JoyAI-LLM-Flash cell at a tiny size (the cell's own
widths are for the chip): the contract line, the program against the plain
reference, every control and planted fault of `reference/joyai_flash.py`
reading not correct, the configuration against the catalog's row,
`work_mla.py` against a hand count, and which readers the cell selects.
"""

import json
import os

import numpy as np
import pytest

from benchmark import compare, run, work_mla
from benchmark.drivers import train_scan_lm
from benchmark.reference import joyai_flash as ref

CELL = "joyai-flash.train_4k"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96, "router_width": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "moe_intermediate_size": 48, "vocab_size": 512, "attention_block": 16,
        "num_hidden_layers": 3}
TINY_TRAFFIC = {"sequence_length": 64}
# Limits for THIS size on the CPU (the cell's own come from chip readings, PERF.md
# section 2): between what the program reads here and what each control and fault reads.
# Read over two seeds: the program loss <= 1.2e-3, grad <= 5.3e-3, first_grad <= 2.1e-3, early_delta <= 2.7e-3,
# delta <= 8.1e-3; tower_fp8 delta >= 0.013, table_bf16 early_delta >= 0.95, half_batch grad >= 0.43, no_routed
# and no_mtp grad 1.0, drop_eighth grad >= 0.061, noncausal grad >= 0.15, no_rope grad >= 0.040,
# mtp_unshifted loss >= 6.0e-3 and grad >= 0.020.
TEST_LIMITS = {"loss_gap": 1.5e-3, "grad_gap": 6.5e-3, "first_grad_gap": 4e-3, "early_delta_gap": 4e-3,
               "delta_gap": 0.011}
# the catalog's row for JoyAI-LLM-Flash (the `model-configs` guide), every number and flag of its `config`
PUBLISHED = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
             "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
             "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
             "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
             "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32,
             "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
             "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
             "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
             "vocab_size": 129280}


_RESOLVE = run.resolve


def _resolve_tiny():
    bench, cell, cfg, traffic = _RESOLVE(CELL)
    return bench, cell, dict(cfg, **TINY), dict(traffic, **TINY_TRAFFIC)


@pytest.fixture(scope="module")
def session():
    _, cell, cfg, traffic = _resolve_tiny()
    s = train_scan_lm.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=2**31 + 11)
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
    assert {"tables/token", "dense/head", "dense/L0.attn", "dense/L0.mlp", "dense/L1.router", "dense/L1.experts",
            "dense/L1.shared", "dense/mtp.merge", "dense/mtp.attn", "dense/mtp.experts"} <= set(reference["grad"])
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
    assert {"moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps", "lm.mtp_loss_share",
            "entry.compiles_in_window", "trainer.scan_traces"} <= set(line["metrics"])
    assert line["metrics"]["trainer.scan_traces"]["value"] == 1 and line["metrics"]["entry.compiles_in_window"]["value"] == 0
    assert 0.8 < line["metrics"]["lm.mtp_loss_share"]["value"] < 1.2  # random weights: both terms near log(vocabulary)
    assert not {"lm.mla_step_mfu", "lm.mla_matmul_roofline", "lm.step_mfu", "trainer.step_mfu"} & set(line["metrics"])
    assert line["device"]["platform"] == "cpu"
    assert metrics.report()["moe.dropped"] == 0


def test_traced_run_selects_no_reader_of_another_familys_work():
    """The cell's per-layer metrics name no reader that counts DeepFM's widths
    (`work.tower_layers`) or NemotronH's layers (`work_lm.train_flops_per_step`),
    and no other cell selects this family's."""
    bench = run.resolve(CELL)[0]
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL, set())}
    mine = run.metrics_of(bench, "per_layer", CELL, e2e)
    readers = {run.load(f"layer_metrics/{m['name']}.json")["reader"] for m in mine}
    for reader in readers:
        text = open(os.path.join(run.HERE, "readers", reader + ".py")).read()
        assert "work." not in text.replace("work_lm.", "").replace("work_mla.", ""), reader
        assert "work_lm.train_flops" not in text and "pattern_of" not in text, reader
    names = {m["name"] for m in mine}
    assert {"lm.mla_step_mfu", "lm.mla_matmul_roofline", "lm.mtp_loss_share", "lm.nonmatmul_ms_per_step",
            "sparse.token_rows_roofline", "moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps",
            "trainer.step_ms", "dense.matmul_ms_per_step", "device.idle_share", "device.peak_hbm_gib",
            "entry.compiles_in_window", "trainer.scan_traces"} == names
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {n for n in theirs if n.startswith(("lm.mla_", "lm.mtp_"))}


def test_configuration_keeps_every_published_number():
    cfg = run.load("configs/joyai-llm-flash-l5-e16of256.json")
    assert set(cfg["reduced"]) == set(cfg["published"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["published"]}
    assert {k: cfg[k] for k in PUBLISHED if k not in cfg["reduced"]} == \
        {k: v for k, v in PUBLISHED.items() if k not in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["router_width"], cfg["vocab_size"]) == \
        (5, 16, 256, 129280 // 8)
    assert "16 chips" in cfg["deployment"] and {"mtp_loss_weight", "mtp_merge", "rotary"} <= set(cfg["assumed"])
    bench = run.resolve(CELL)[0]
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] == "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json"
    # attention 26.35M a layer; dense layer 70.39M; routed layer 107.09M; module 115.48M; table + head 2 x 33.10M
    sizes = ref.group_sizes(cfg)
    assert sizes["L0.attn"] == 26_349_568 and sizes["L0.attn"] + sizes["L0.mlp"] == 70_391_808
    assert sum(sizes[f"L1.{p}"] for p in ("attn", "router", "experts", "shared")) == 107_092_224
    assert sum(v for g, v in sizes.items() if g.startswith("mtp.")) == 115_486_976
    dense = sum(int(np.prod(shape)) for _, shape, _ in ref.dense_leaves(cfg))
    assert dense + cfg["vocab_size"] * cfg["hidden_size"] == 680_441_088


def test_work_mla_flops_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4, "qk_nope_head_dim": 3,
           "qk_rope_head_dim": 2, "v_head_dim": 3, "intermediate_size": 10, "router_width": 8, "n_routed_experts": 2,
           "num_experts_per_tok": 2, "moe_intermediate_size": 5, "n_shared_experts": 1, "num_hidden_layers": 2,
           "first_k_dense_replace": 1, "num_nextn_predict_layers": 1, "vocab_size": 10}
    # attention MACs a token: 8 x 6 + 6 x 2 x 5 + 8 x (4 + 2) + 4 x 2 x 6 + 6 x 8 = 48 + 60 + 48 + 48 + 48 = 252
    assert work_mla.attention_macs_per_token(cfg) == 252
    # batch 2 x seq 4 = 8 tokens, the module over 2 x 3 = 6 positions. core = 2 heads x (3 + 2 + 3) = 16 a pair.
    # layer 0: 8 x (252 + 3 x 8 x 10 = 240) + 2 x 16 x 10 pairs = 3936 + 320 = 4256
    # layer 1: 8 x (252 + router 64 + shared 3 x 8 x 5 = 120) + 320 = 3488 + 320 = 3808
    # head: 8 x 80 = 640
    # module: 6 x (merge 2 x 8 x 8 = 128 + 252 + 184 + head 80 = 644) + 2 x 16 x 6 pairs = 3864 + 192 = 4056
    # routed: 2 layers x balanced 8 x 2 x 2 / 8 = 4 pairs x 3 x 8 x 5 = 120 -> 960
    # forward FLOPs = 2 x (4256 + 3808 + 640 + 4056 + 960) = 2 x 13720
    assert work_mla.routed_layers(cfg) == 2 and work_mla.balanced_pairs_per_layer(cfg, 8) == 4
    assert work_mla.forward_flops_per_step(cfg, 2, 4) == 2 * 13720
    assert work_mla.train_flops_per_step(cfg, 2, 4) == 6 * 13720
    assert work_mla.forward_flops_per_step(cfg, 2, 4, pairs_per_layer=6) == 2 * 13720 + 2 * 2 * 2 * 120
    # without the module: its 4056 and one routed layer's pairs go
    assert work_mla.forward_flops_per_step(dict(cfg, num_nextn_predict_layers=0), 2, 4) == 2 * (13720 - 4056 - 480)


def test_work_mla_at_the_cell_is_2_6_gigaflops_a_token():
    cfg = run.load("configs/joyai-llm-flash-l5-e16of256.json")
    flops = work_mla.train_flops_per_step(cfg, 2, 4096)
    # six attention cores at 4k are 6 x 1.03 of the step's 21.7 TFLOP
    assert 21.5e12 < flops < 21.9e12 and 2.62e9 < flops / 8192 < 2.68e9
    core = 3 * 2 * 2 * 32 * (128 + 64 + 128) * 4096 * 4097 / 2
    assert 1.02e12 < core < 1.04e12
