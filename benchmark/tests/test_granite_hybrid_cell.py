"""CPU rehearsals of the Granite 4.0-H cell at a tiny size (the cell's own
widths are for the chip): the contract line, the program against the plain
reference over packed batches, every control and planted fault of
`reference/granite_hybrid.py` reading not correct, the packed driver's batches,
`work_ssd.py` against a hand count, the configuration against the catalog, and
which readers the cell selects.
"""

import inspect
import json
import os

import numpy as np
import pytest

from benchmark import compare, run, work_ssd
from benchmark.drivers import train_scan_lm, train_scan_lm_keywords, train_scan_lm_packed
from benchmark.reference import granite_hybrid as ref

CELL = "granite4h.train_8k_packed"
CONFIG = "configs/granite-4.0-h-micro-l10.json"
TINY = {"hidden_size": 64, "num_hidden_layers": 4, "layer_types": ["mamba", "attention", "mamba", "mamba"],
        "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16, "mamba_chunk_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "shared_intermediate_size": 96,
        "attention_multiplier": 0.0625, "vocab_size": 512, "attention_block": 32}
TINY_TRAFFIC = {"sequence_length": 96, "doc_length_median": 14, "doc_length_sigma": 0.8, "doc_length_min": 3,
                "doc_length_max": 96}
# Controls and faults are read against a program that computes in float32 here. Limits for THIS size on
# the CPU (the cell's own come from chip readings, PERF.md section 2), read over two seeds: the f32 program
# loss <= 7.7e-8, grad <= 1.2e-6, delta <= 3.0e-6; every control and fault reads grad >= 1.8e-3 (the least:
# no_state_reset 1.8e-3, tower_fp8 1.9e-3, noncausal 2.2e-3, attn_scale_rsqrt 6.1e-3, conv_leak 0.0107) and
# delta >= 4.2e-3; untied_head moves the loss by 6e-7 alone (the first step's logits are the tied ones) and
# the gradients by 0.09. At 7 documents in 96 positions a start is 70 times as frequent as in the cell's
# own mix: what the packing faults read on the chip is PERF.md's to say.
F32 = {"tower_dtype": "float32"}
TEST_LIMITS = {"loss_gap": 2e-6, "grad_gap": 1.5e-5, "delta_gap": 1.5e-5}
# the same cell as the chip runs it, bf16
BF16_LIMITS = {"loss_gap": 8e-3, "grad_gap": 0.045, "delta_gap": 0.045}

_RESOLVE = run.resolve


def _resolve_tiny(**more):
    bench, cell, cfg, traffic = _RESOLVE(CELL)
    return bench, cell, dict(cfg, **TINY, **more), dict(traffic, **TINY_TRAFFIC)


@pytest.fixture(scope="module")
def session():
    _, cell, cfg, traffic = _resolve_tiny(**F32)
    s = train_scan_lm_packed.open_session(cfg=cfg, traffic=traffic, chips=cell["chips"], seed=2**31 + 11)
    s.setup()
    s.ctx = s.context()
    s.starts = s.host["dense"]
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
    assert {"dense/table", "dense/norm_f", "dense/L0.M", "dense/L1.attn", "dense/L2.M", "dense/L0.mlp",
            "dense/L3.mlp"} == set(reference["grad"]) - {"dense/L1.mlp", "dense/L2.mlp", "dense/L3.M"}
    assert not any(leaf.startswith("tables/") for leaf in reference["grad"])  # the table trains densely


def test_the_batches_are_packed_documents_from_the_seed(session):
    starts = session.starts
    assert starts.shape == (4, 1, 96) and starts.dtype == np.int32 and set(np.unique(starts)) == {0, 1}
    assert np.all(starts[:, :, 0] == 1)  # position 0 always starts a document
    docs = starts.sum(axis=-1)
    assert docs.min() >= 2 and docs.max() <= 96 // 3 + 1
    for row in starts.reshape(-1, 96):  # no document but a sequence's last is shorter than the floor
        assert np.diff(np.flatnonzero(row)).min() >= 3
    again = train_scan_lm_packed.document_starts(2**31 + 11, 4, 96, session.traffic)
    np.testing.assert_array_equal(again.reshape(starts.shape), starts)
    other = train_scan_lm_packed.document_starts(2**31 + 12, 4, 96, session.traffic)
    assert np.any(other.reshape(starts.shape) != starts)
    # the pairs inside documents: by hand over the lengths
    want = 0
    for row in starts.reshape(-1, 96):
        lengths = np.diff(np.append(np.flatnonzero(row), 96))
        want += sum(int(n) * (int(n) + 1) // 2 for n in lengths)
    assert session.ctx["ref_pairs_per_layer"] == want / 4 < 96 * 97 / 2


def test_the_cells_own_mix_draws_about_six_documents_a_sequence():
    traffic = run.resolve(CELL)[3]
    starts = train_scan_lm_packed.document_starts(7, 256, 8192, traffic)
    docs = starts.sum(axis=1)
    assert 5.0 < docs.mean() < 8.0 and docs.min() >= 1
    longest = [np.diff(np.append(np.flatnonzero(r), 8192)).max() for r in starts]
    assert 0.4 < np.mean(longest) / 8192 < 0.7
    gaps = np.concatenate([np.diff(np.flatnonzero(r)) for r in starts])
    assert gaps.min() >= 32 and 500 < np.median(gaps) < 900


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
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"pack.reset_sites", "pack.docs_per_sequence", "pack.longest_doc_share", "attn.fused_cores",
            "entry.compiles_in_window", "trainer.scan_traces", "trainer.windows"} <= set(got)
    assert got["trainer.scan_traces"] == 1 and got["entry.compiles_in_window"] == 0
    # 3 Mamba layers x (conv + scan) + 1 attention layer = 7 sites a trace of the module: `init`, the scan
    assert got["pack.reset_sites"] == 14 and got["attn.fused_cores"] == 0
    assert 2 <= got["pack.docs_per_sequence"] <= 33 and 3 / 96 <= got["pack.longest_doc_share"] <= 1
    assert not {"lm.granite_step_mfu", "lm.zaya_step_mfu", "lm.step_mfu", "trainer.step_mfu", "moe.pairs_here",
                "sparse.token_rows_roofline", "sparse.shared_pulls", "sparse.apply_fill"} & set(got)
    assert line["device"]["platform"] == "cpu"


def test_the_keyword_map_is_the_configurations_own():
    from openembedding_tpu import models
    cfg = run.load(CONFIG)
    train_scan_lm_keywords.with_row(cfg)
    assert train_scan_lm.KEYWORDS["granite_hybrid"] == ((), cfg["make_keywords"])
    names = inspect.signature(models.make_granite_hybrid).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)
    model = train_scan_lm_keywords.build_model(dict(cfg, **TINY))
    assert model.config["layer_types"] == TINY["layer_types"] and model.config["head_dim"] == 16
    assert model.config["logits_scaling"] == 8 and model.specs["token"].sparse_as_dense


def test_traced_run_selects_this_familys_readers_alone():
    bench = run.resolve(CELL)[0]
    e2e = {m["name"] for m in run.metrics_of(bench, "end_to_end", CELL, set())}
    mine = run.metrics_of(bench, "per_layer", CELL, e2e)
    for m in mine:  # every reader is one the benchmark had
        assert os.path.exists(os.path.join(run.HERE, "readers", run.load(f"layer_metrics/{m['name']}.json")["reader"] + ".py"))
    names = {m["name"] for m in mine}
    assert {"lm.granite_step_mfu", "pack.reset_sites", "pack.docs_per_sequence", "pack.longest_doc_share",
            "attn.fused_cores", "lm.nonmatmul_ms_per_step", "trainer.step_ms", "dense.matmul_ms_per_step",
            "device.idle_share", "device.peak_hbm_gib", "entry.compiles_in_window", "trainer.scan_traces",
            "trainer.windows", "trainer.init_s", "trainer.scan_trace_s", "trainer.scan_compile_s",
            "trainer.scan_cache_misses", "trainer.scan_executables"} <= names
    # the table trains densely and nothing is routed: nothing of those layers is read here
    assert not {n for n in names if n.startswith(("sparse.", "moe.", "kda.", "cca.", "router.", "loop.", "exchange."))}
    for cell in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in run.metrics_of(bench, "per_layer", cell, e2e)}
        assert not {n for n in theirs if n.startswith(("pack.", "lm.granite_"))}


def test_work_ssd_flops_by_hand():
    cfg = {"hidden_size": 8, "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba", "mamba"],
           "mamba_n_heads": 4, "mamba_d_head": 3, "mamba_n_groups": 1, "mamba_d_state": 5, "mamba_chunk_size": 4,
           "num_attention_heads": 4, "num_key_value_heads": 2, "shared_intermediate_size": 6, "vocab_size": 10}
    # a Mamba layer's projections, MACs a token: in_proj 8 x (12 + 12 + 2 x 5 + 4) = 304, out_proj 12 x 8 = 96
    assert work_ssd.mamba_projection_macs_per_token(cfg) == 400
    # its scan at chunk 4: (4 + 1) / 2 = 2.5 pairs a token; C.B^T 5 a pair ONCE (one group), times x 4 heads x 3 =
    # 12 a pair -> 42.5; what a chunk leaves and what a position reads, 4 x 5 x 3 = 60 each -> 162.5
    assert work_ssd.scan_macs_per_token(cfg) == 162.5
    # attention: q and o 2 x 8 x 8 = 128, k and v 2 x 8 x 4 = 64
    assert work_ssd.attention_projection_macs_per_token(cfg) == 192
    # batch 2 x seq 6 = 12 tokens; 3 layers held (2 Mamba, 1 attention): SwiGLU 3 x 3 x 8 x 6 = 432, head 80
    # -> 12 x 512 = 6144; Mamba 12 x 2 x 562.5 = 13500; attention 12 x 192 = 2304 + pairs x 4 heads x 2 x 2 = 16 a pair
    whole = 2 * 21
    assert work_ssd.forward_flops_per_step(cfg, 2, 6) == 2 * (6144 + 13500 + 2304 + whole * 16)
    assert work_ssd.forward_flops_per_step(cfg, 2, 6, pairs_per_layer=17) == 2 * (6144 + 13500 + 2304 + 17 * 16)
    assert work_ssd.train_flops_per_step(cfg, 2, 6, 17) == 3 * 2 * (6144 + 13500 + 2304 + 272)


def test_work_ssd_at_the_cell_is_41_teraflops_a_step():
    cfg = run.load(CONFIG)
    flops = work_ssd.train_flops_per_step(cfg, 1, 8192)
    assert 40e12 < flops < 43e12
    # of the forward's 13.6 TFLOP: ten SwiGLUs 8.2, nine Mamba layers' projections 3.8, their scans 0.23,
    # the tied head 0.84 (6.1%), the attention layer 0.17 + 0.27 at one document a sequence
    assert 8.2e12 < 10 * 2 * 8192 * 3 * 2048 * 8192 < 8.3e12
    assert 3.8e12 < 9 * 2 * 8192 * work_ssd.mamba_projection_macs_per_token(cfg) < 3.9e12
    assert 0.23e12 < 9 * 2 * 8192 * work_ssd.scan_macs_per_token(cfg) < 0.24e12
    assert 0.84e12 < 2 * 8192 * 2048 * 25088 < 0.85e12
    packed = work_ssd.train_flops_per_step(cfg, 1, 8192, pairs_per_layer=8192 * 8193 / 2 / 4)
    assert 0.5e12 < flops - packed < 0.7e12  # a quarter of the pairs: three quarters of the core's 0.82 TFLOP


def test_configuration_keeps_every_published_number_and_counts_its_parameters():
    from openembedding_tpu import models
    cfg = run.load(CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
        assert cfg["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {"num_hidden_layers", "vocab_size"}
    # what was cut is a count (layers, rows), never a width
    assert (cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["shared_intermediate_size"]) == \
        (2048, 64, 64, 1, 128, 4, 256, 32, 8, 8192)
    assert (cfg["attention_multiplier"], cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["logits_scaling"]) == (0.015625, 12, 0.22, 8)
    assert len(cfg["layer_types"]) == 40 and [i for i, k in enumerate(cfg["layer_types"]) if k == "attention"] == \
        [5, 15, 25, 35]
    assert ref.kinds_of(cfg) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4  # one whole period
    assert cfg["vocab_size"] * 4 == 100352 and cfg["tie_word_embeddings"] is True
    assert {"block", "scalars", "mamba", "packing", "head", "optimizer", "precision"} <= set(cfg["assumed"])
    sizes = ref.group_sizes(cfg)
    assert sizes["table"] == 25088 * 2048 and sizes["norm_f"] == 2048
    assert sizes["L0.M"] == 25_847_232 + 2048 and sizes["L5.attn"] == 10_485_760 + 2048
    assert sizes["L0.mlp"] == 50_331_648 + 2048
    assert sizes["L0.M"] + sizes["L0.mlp"] == 76_182_976 and sizes["L5.attn"] + sizes["L5.mlp"] == 60_821_504
    assert sum(sizes.values()) == 797_850_560
    names = inspect.signature(models.make_granite_hybrid).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)
