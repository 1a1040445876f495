"""CPU rehearsals of the SDAR cell at a tiny size (the cell's own widths are
for the chip): the contract line, the program against the plain reference,
every control and planted fault of `reference/sdar_moe.py` reading not
correct, the configuration against the catalog's row, the host's noise
against its law, `work_bd.py` against a hand count, and which readers the
cell selects.
"""

import json
import os

import numpy as np
import pytest

from benchmark import compare, run, work_bd
from benchmark.drivers import train_scan_lm_diffusion as driver
from benchmark.reference import sdar_moe as ref

CELL = "sdar30b.train_4k_bd"
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "router_width": 16,
        "num_experts": 4, "num_experts_per_tok": 3, "moe_intermediate_size": 48, "vocab_size": 512, "mask_id": 511,
        "attention_block": 16, "num_hidden_layers": 2, "working_pairs": 0}
TINY_TRAFFIC = {"sequence_length": 64}
# Limits for THIS size on the CPU (the cell's own come from chip readings, PERF.md
# section 2): between what the program reads here and what each control and fault reads.
# Read over two seeds: the program loss <= 2.7e-4, grad <= 2.3e-3, early_delta <= 2.4e-3, delta <= 8.6e-3;
# tower_fp8 loss >= 2.5e-3 and grad >= 0.031, table_bf16 early_delta >= 0.95, no_qk_norm loss >= 1.3e-3 and
# grad >= 0.033, answer_leak loss >= 4.9e-3 and delta >= 0.096, drop_eighth loss >= 3.8e-3, clean_positions_offset
# loss >= 5.2e-3, every other fault loss >= 0.015.
TEST_LIMITS = {"loss_gap": 1e-3, "grad_gap": 8e-3, "early_delta_gap": 6e-3, "delta_gap": 0.02}
# the catalog's row for SDAR-30B-A3B-Chat (the `model-configs` guide), every number and flag of its `config`
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
             "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48,
             "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
             "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
             "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
             "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
SEED = 2**31 + 11

_RESOLVE = run.resolve


def _resolve_tiny():
    bench, cell, cfg, traffic = _RESOLVE(CELL)
    return bench, cell, dict(cfg, **TINY), dict(traffic, **TINY_TRAFFIC)


@pytest.fixture(scope="module")
def session():
    _, cell, cfg, traffic = _resolve_tiny()
    s = driver.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=SEED)
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
    assert {"tables/token", "dense/head", "dense/L0.attn", "dense/L0.router", "dense/L1.experts"} <= \
        set(reference["grad"])
    # the reference counted the pairs its own router sent the held experts: 2 x 128 positions, top 3
    assert 0 < session.ctx["ref_pairs_per_layer"] <= 2 * 128 * 3
    # random weights: the first loss near log(vocabulary) times E[weight] = 1
    assert 0.6 * np.log(512) < reference["losses"][0] < 1.4 * np.log(512)


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
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # a traced rehearsal has no device trace; the program's counters are read all the same
    assert {"moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps", "bd.masked_share",
            "attn.bd_key_block_share", "attn.bd_fused_cores", "entry.compiles_in_window",
            "trainer.scan_traces"} <= set(got)
    assert got["trainer.scan_traces"] == 1 and got["entry.compiles_in_window"] == 0
    # E[t] = (1 + 0.001) / 2 over 2 x 64 positions a step
    assert 0.3 < got["bd.masked_share"] < 0.7
    # head_dim 16 is no shape of the kernel's: the plain body, every block, no fused call site
    assert got["attn.bd_key_block_share"] == 1.0 and got["attn.bd_fused_cores"] == 0
    assert "lm.sdar_step_mfu" not in got
    assert line["device"]["platform"] == "cpu"
    assert metrics.report()["moe.dropped"] == 0


def test_traced_run_selects_no_reader_of_another_familys_work():
    """The cell's per-layer metrics are the routed cells' program counters,
    the device's, and the family's own four; no other cell selects those."""
    bench = run.resolve(CELL)[0]
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL, set())}
    mine = run.metrics_of(bench, "per_layer", CELL, e2e)
    readers = {run.load(f"layer_metrics/{m['name']}.json")["reader"] for m in mine}
    for reader in readers:
        text = open(os.path.join(run.HERE, "readers", reader + ".py")).read()
        assert "from benchmark import work\n" not in text and "work_lm." not in text and "pattern_of" not in text, \
            reader
    names = {m["name"] for m in mine}
    assert {"lm.sdar_step_mfu", "attn.bd_key_block_share", "bd.masked_share", "attn.bd_fused_cores",
            "moe.pairs_here", "moe.load_max_over_mean", "moe.full_steps", "trainer.step_ms",
            "dense.matmul_ms_per_step", "device.idle_share", "device.peak_hbm_gib", "entry.compiles_in_window",
            "trainer.scan_traces"} <= names
    assert not {"lm.step_mfu", "lm.mla_step_mfu", "attn.fused_cores", "attn.key_block_share"} & names
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {"lm.sdar_step_mfu", "attn.bd_key_block_share", "bd.masked_share", "attn.bd_fused_cores"} & theirs


def test_configuration_keeps_every_published_number():
    cfg = run.load("configs/sdar-30b-a3b-chat-e16of128.json")
    assert set(cfg["reduced"]) == set(cfg["published"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["published"]}
    assert {k: cfg[k] for k in PUBLISHED if k not in cfg["reduced"]} == \
        {k: v for k, v in PUBLISHED.items() if k not in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["router_width"], cfg["vocab_size"]) == \
        (5, 16, 128, 151936 // 8)
    assert cfg["mask_id"] == cfg["vocab_size"] - 1 and cfg["block_length"] == 4
    assert "8 chips" in cfg["deployment"] and {"block_length", "noise_schedule", "labels", "mask_id"} <= \
        set(cfg["assumed"])
    # the rule for the depth: 6 layers would leave under 1 GiB of the chip
    assert cfg["compiled_for_v5e"]["layers_6"]["room_gib"] < 1 <= cfg["compiled_for_v5e"]["layers_5"]["room_gib"]
    # the working size the cell runs at fits beside them
    assert cfg["compiled_for_v5e"][f"layers_5_working_{cfg['working_pairs']}"]["total_gib"] < 15.75
    bench = run.resolve(CELL)[0]
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    # attention 18.87M + router and norms 0.27M + 16 experts x 4.72M a layer; table + head 2 x 38.90M
    sizes = ref.group_sizes(cfg)
    assert sizes["L0.attn"] == 18_874_368 + 2048 + 256
    assert sizes["L0.attn"] + sizes["L0.router"] + sizes["L0.experts"] == 19_140_864 + 16 * 4_718_592
    dense = sum(int(np.prod(shape)) for _, shape, _ in ref.dense_leaves(cfg))
    assert dense + cfg["vocab_size"] * cfg["hidden_size"] == 550_984_960


def test_host_noise_follows_its_law_and_the_seed():
    """The driver's noise: a block's t ~ U[0.001, 1], a position masked with
    probability t (share E[t]), weight 1 / t where masked (mean 1); the same
    seed draws the same batches, another seed others."""
    _, _, cfg, traffic = run.resolve(CELL)
    cfg = dict(cfg, vocab_size=512, mask_id=511)
    traffic = dict(traffic, sequence_length=4096)
    b = driver.diffusion_batches(2**40 + 5, 4, 8, cfg, traffic)
    ids, w, x0 = b["sparse"]["token"], b["dense"], b["label"]
    assert ids.shape == (4, 8, 8192) and w.shape == x0.shape == (4, 8, 4096)
    np.testing.assert_array_equal(ids[..., 4096:], x0)
    masked = ids[..., :4096] == 511
    np.testing.assert_array_equal(masked, w > 0)
    assert x0.max() < 511 and np.array_equal(ids[..., :4096][~masked], x0[~masked])
    assert abs(masked.mean() - 1.001 / 2) < 0.01 and abs(w.mean() - 1.0) < 0.08
    blocks = w.reshape(4, 8, 1024, 4)
    assert np.all((blocks == 0) | (blocks == blocks.max(axis=-1, keepdims=True)))
    again = driver.diffusion_batches(2**40 + 5, 4, 8, cfg, traffic)
    assert np.array_equal(again["sparse"]["token"], ids) and np.array_equal(again["dense"], w)
    other = driver.diffusion_batches(2**40 + 6, 4, 8, cfg, traffic)
    assert not np.array_equal(other["dense"], w)
    with pytest.raises(SystemExit, match="blocks of"):
        driver.diffusion_batches(1, 1, 1, cfg, dict(traffic, block_length=8))


def test_work_bd_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4, "router_width": 8,
           "num_experts": 2, "num_experts_per_tok": 2, "moe_intermediate_size": 5, "num_hidden_layers": 2,
           "vocab_size": 10, "block_length": 2}
    # a sequence of 2 x 4 positions in blocks of 2: noisy queries see 2 noisy keys and 0, 0, 2, 2 clean ones,
    # clean queries 2, 2, 4, 4: 8 + 4 + 12 = 24 = L^2 + L x block = 16 + 8
    assert work_bd.mask_pairs(8, 2) == 24
    # projections a position: 8 x (2 x 8 + 2 x 4) + router 8 x 8 = 192 + 64 = 256
    assert work_bd.projection_macs_per_position(cfg) == 256
    # batch 2 x 8 positions = 16: balanced pairs 16 x 2 x 2 / 8 = 8 a layer
    assert work_bd.balanced_pairs_per_layer(cfg, 16) == 8
    # a layer: 16 x 256 + core 2 x 2 heads x (4 + 4) x 24 pairs + experts 8 x 3 x 8 x 5 = 4096 + 768 + 960 = 5824
    # two layers 11648 + head over 2 x 4 noisy positions x 8 x 10 = 640 -> 12288 MACs
    assert work_bd.forward_flops_per_step(cfg, 2, 8) == 2 * 12288
    assert work_bd.train_flops_per_step(cfg, 2, 8) == 6 * 12288
    assert work_bd.forward_flops_per_step(cfg, 2, 8, pairs_per_layer=12) == 2 * (12288 + 2 * 4 * 120)
    # the core alone: forward and backward, 6 products of 2 heads x 4 x 24 pairs a sequence
    assert work_bd.attn_core_flops(cfg, 2, 8) == 3 * 2 * 2 * 4 * 2 * 2 * 24
    # bytes: q 2 x 8 x 2 x 4 x 2 = 256, k and v 128 each, rows 2 x 8 x 2 x 4 = 128
    assert work_bd.attn_core_bytes(cfg, 2, 8) == (256 + 256 + 256 + 128) + (256 + 256 + 256 + 256 + 256 + 256)


def test_work_bd_at_the_cell():
    """The count at B 2 x 2L 8192: a layer's projections 618.5 GF, its core
    550.3 GF (38% of the step's forward at 5 layers), the head 637 GF."""
    cfg = run.load("configs/sdar-30b-a3b-chat-e16of128.json")
    core = 2.0 * 2 * 2 * 32 * 128 * work_bd.mask_pairs(8192, 4)
    assert work_bd.mask_pairs(8192, 4) == 16_793_600 and core == pytest.approx(550.3e9, rel=1e-3)
    assert 2.0 * 16384 * work_bd.projection_macs_per_position(cfg) == pytest.approx(627.1e9, rel=1e-3)
    fwd = work_bd.forward_flops_per_step(cfg, 2, 8192)
    assert fwd == pytest.approx(5 * (618.5e9 + 8.6e9 + 154.6e9 + 550.3e9) + 637.3e9, rel=1e-3)
    assert 0.37 < 5 * core / fwd < 0.39
    assert work_bd.attn_core_flops(cfg, 2, 8192) == pytest.approx(3 * core)
