"""Solar-Open2 (gated delta-rule linear attention 3 : 1 with gated softmax
attention without positions, every layer routed; a share of the heads held)
on the normal train path, against the benchmark's plain reference
(`benchmark/reference/solar_open2.py`: float32, the recurrence one position at
a time, full-softmax attention, a loop over the experts held) at small widths
on seeded random weights."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import solar_open2 as ref
from openembedding_tpu import models
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import nemotron_h as nh
from openembedding_tpu.models import solar_open2 as so

CFG = dict(hidden_size=64, num_hidden_layers=4, gqa_layers=[0, 4, 8],
           num_attention_heads=4, num_key_value_heads=2, head_dim=8,
           use_gqa_gate=True,
           linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8,
                                   num_heads=3, num_kv_heads=None),
           gate_rank=6, kda_allow_neg_eigval=True, chunk_size=16,
           n_routed_experts=4, router_width=16, expert_offset=4,
           num_experts_per_tok=3, moe_intermediate_size=24, n_shared_experts=1,
           routed_scaling_factor=1.0, norm_topk_prob=True, rms_norm_eps=1e-5,
           vocab_size=64, table_init_stddev=1.0, learning_rate=0.05,
           adagrad_initial_accumulator=0.1, adagrad_epsilon=1e-7)
ACC0 = CFG["adagrad_initial_accumulator"]
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "solar-open2-250b-l4-h8of64.json")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def make(cfg, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 8)
    lin = cfg["linear_attn_config"]
    return models.make_solar_open2(
        vocabulary=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        gqa_layers=cfg["gqa_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], use_gqa_gate=cfg["use_gqa_gate"],
        linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        gate_rank=cfg["gate_rank"],
        allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        chunk_size=cfg["chunk_size"], n_routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"], eps=cfg["rms_norm_eps"], **kw)


def _path(kp):
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def seeded(cfg, model, batch, seed=3):
    """(trainer, state with every leaf from the benchmark's hash draw, the
    reference's flat {path: leaf})."""
    tr = Trainer(model, embed.Adagrad(
        learning_rate=cfg["learning_rate"],
        initial_accumulator_value=cfg["adagrad_initial_accumulator"],
        epsilon=cfg["adagrad_epsilon"]))
    state = jax.jit(tr.init)(batch)
    keys = ref.make_keys(seed, cfg)
    dense = ref.init_dense(keys, cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state.dense_params)
    assert {_path(kp): v.shape for kp, v in flat} == \
        {p: tuple(s) for p, s, _ in ref.dense_leaves(cfg)}
    params = jax.tree_util.tree_unflatten(
        treedef, [jnp.array(dense[_path(kp)]) for kp, _ in flat])
    ts = state.tables["token"]
    rows = ref.init_rows(keys, cfg, jnp.arange(cfg["vocab_size"]))["token"]
    state = state.replace(dense_params=params,
                          tables={"token": ts.replace(weights=rows)})
    return tr, state, dense


def batches(k, b=2, s=37, vocab=64, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, vocab, size=(k, b, s + 1)).astype(np.int32)
    return {"sparse": {"token": tok[:, :, :-1]}, "label": tok[:, :, 1:]}


def one(stacked, i=0):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


# -- the chunked form against the recurrence ------------------------------------

def _delta_inputs(length, seed=0, fast=False, beta_hi=False, h=2, dk=8, dv=8):
    r = np.random.default_rng(seed)
    q = r.normal(size=(2, length, h, dk))
    k = r.normal(size=(2, length, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(2, length, h, dv))
    g = -np.abs(r.normal(size=(2, length, h, dk))) * 0.3
    if fast:  # a channel that decays by e^-288 inside a chunk of 32
        g[..., 0] = -9.0
    beta = 2.0 / (1.0 + np.exp(-r.normal(size=(2, length, h))))
    if beta_hi:
        beta = 2.0 - 1e-3 * np.abs(r.normal(size=(2, length, h)))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


@pytest.mark.parametrize("length,kw", [
    (128, {}), (100, {}), (37, {}), (70, {"fast": True}),
    (64, {"beta_hi": True})],
    ids=["whole_chunks", "ragged_tail", "shorter_than_two_chunks",
         "fast_channel", "beta_near_2"])
def test_chunked_delta_rule_equals_the_recurrence(length, kw):
    """Forward and `jax.grad` of every input: the chunked WY form against the
    position-by-position recurrence of the reference."""
    args = _delta_inputs(length, **kw)
    want = jax.jit(ref.delta_rule)(*args)
    got, floor = jax.jit(lambda *a: so.kda_chunked(*a, chunk=32))(*args)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert 0.0 <= float(floor) <= 1.0
    if kw.get("fast"):
        # the naive factoring (k e^{-G}) would overflow here: e^{288}
        assert float(floor) == 0.0 and np.all(np.isfinite(got))

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    g_got = jax.jit(jax.grad(scalar(
        lambda *a: so.kda_chunked(*a, chunk=32)[0]), argnums=(0, 1, 2, 3, 4)))(*args)
    g_want = jax.jit(jax.grad(scalar(ref.delta_rule), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(g_got, g_want):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, atol=3e-4)


def test_chunk_without_sub_blocks_and_bf16_products():
    """A chunk that is no multiple of the sub-block takes the pairwise form
    whole; bf16 products stay near the f32 recurrence."""
    args = _delta_inputs(50, seed=4)
    want = jax.jit(ref.delta_rule)(*args)
    got, _ = jax.jit(lambda *a: so.kda_chunked(*a, chunk=10))(*args)
    np.testing.assert_allclose(got, want, atol=3e-5)
    low, _ = jax.jit(lambda *a: so.kda_chunked(*a, chunk=32, dtype=jnp.bfloat16))(*args)
    assert low.dtype == jnp.float32
    assert 1e-6 < float(jnp.mean(jnp.abs(low - want))) < 0.05


# -- the model against the reference --------------------------------------------

def _ref_loss(cfg, y, fault=""):
    w = jnp.ones(y.shape)

    def loss(dense, rows):
        return ref.xent(ref.forward(dense, rows, cfg, "f32", fault)[0], y, w)
    return loss


def test_logits_loss_and_every_gradient_leaf_match_reference():
    batch = one(batches(1))
    model = make(CFG)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    y = batch["label"]

    def prog_loss(params, rows):
        return model.loss_fn(
            model.module.apply({"params": params}, {"token": rows}), y)

    logits = jax.jit(lambda p, r: model.module.apply(
        {"params": p}, {"token": r}))(state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.logits_fn(d, r, CFG))(dense, rows)
    np.testing.assert_allclose(logits, want, atol=3e-5)
    lr, (gd, gr) = jax.jit(jax.value_and_grad(_ref_loss(CFG, y), (0, 1)))(dense, rows)
    lp, (pd, pr) = jax.jit(jax.value_and_grad(prog_loss, (0, 1)))(
        state.dense_params, rows)
    assert abs(float(lp) - float(lr)) < 1e-5
    np.testing.assert_allclose(pr, gr, atol=2e-6)
    got = {_path(kp): v for kp, v in
           jax.tree_util.tree_flatten_with_path(pd)[0]}
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=5e-6, err_msg=path)
    assert not np.any(got["layers_1/moe/router_correction_bias"])
    # every fault of the reference moves the loss it is compared by
    for fault in ("noncausal", "chunk_reset", "no_decay", "no_delta",
                  "beta_unscaled", "no_gate", "no_routed"):
        assert abs(float(jax.jit(_ref_loss(CFG, y, fault))(dense, rows))
                   - float(lr)) > 1e-5, fault


def _group_sums(cfg, state, dense0):
    """Per leaf group [sum(acc - acc0), sum((w - w0)^2)] of a program state."""
    groups, out = ref.leaf_groups(cfg), {}
    params = {_path(kp): v for kp, v in
              jax.tree_util.tree_flatten_with_path(state.dense_params)[0]}
    slots = {_path(kp[:-1]): v for kp, v in
             jax.tree_util.tree_flatten_with_path(state.dense_slots)[0]}
    for path, w0 in dense0.items():
        s = np.array([np.sum(np.asarray(slots[path], np.float64) - ACC0),
                      np.sum(np.square(np.asarray(params[path], np.float64)
                                       - np.asarray(w0, np.float64)))])
        out[groups[path]] = out.get(groups[path], 0.0) + s
    return out


def test_three_step_train_many_matches_reference_follow():
    from openembedding_tpu.utils import metrics
    metrics.reset_all()  # `kda.scans` below counts this test's traces alone
    stacked = batches(3)
    model = make(CFG)
    tr, state, dense0 = seeded(CFG, model, one(stacked))
    rows0 = np.asarray(state.tables["token"].weights, np.float64)
    state, m = tr.jit_train_many()(state, stacked)
    ids = np.arange(CFG["vocab_size"], dtype=np.int32)
    masks = np.ones((3, ids.size), np.float32)
    out = jax.device_get(ref.follow(
        3, CFG, 1, ids, stacked["sparse"]["token"], stacked["label"], masks))
    np.testing.assert_allclose(m["loss"], out["losses"], rtol=2e-5)
    assert float(m["loss"][0]) > float(m["loss"][2])
    got = _group_sums(CFG, state, dense0)
    assert set(got) == set(out["dense"]) == set(ref.group_sizes(CFG))
    assert {"L0.attn", "L1.kda", "L2.kda", "L3.kda", "L0.router",
            "L1.experts", "L3.shared", "head"} <= set(got)
    for g, v in out["dense"].items():
        np.testing.assert_allclose(got[g], [v[0], v[2]], rtol=2e-3, err_msg=g)
    ts = state.tables["token"]
    np.testing.assert_allclose(
        [np.sum(np.asarray(ts.slots["accum"], np.float64) - ACC0),
         np.sum(np.square(np.asarray(ts.weights, np.float64) - rows0))],
        [out["tables"]["token"][0], out["tables"]["token"][2]], rtol=2e-3)
    assert set(m["module"]) == set(dict(so.SolarOpen2.window_stats))
    assert int(m["module"]["moe.dropped"]) == 0
    assert 0.0 < float(m["module"]["kda.chunk_decay_floor"]) < 1.0
    assert out["pairs_held"].shape == (3, 4)  # every layer is routed
    np.testing.assert_allclose(m["module"]["moe.pairs_here"],
                               np.mean(out["pairs_held"]), rtol=1e-6)
    tr.record_window_stats(m)
    report = metrics.report()
    assert report["kda.chunk_decay_floor"] == pytest.approx(
        float(m["module"]["kda.chunk_decay_floor"]))
    # 3 linear layers a trace of the module
    assert report['kda.scans{path="chunked"}'] in (3, 6, 9)


# -- shares add up ----------------------------------------------------------------

def _uncut(cfg, softmax, seed=5):
    """One uncut sub-layer's leaves from the reference's draw."""
    full = dict(cfg, num_hidden_layers=1, gqa_layers=[0] if softmax else [])
    dense = ref.init_dense(ref.make_keys(seed, full), full)
    prefix = "layers_0/attn/" if softmax else "layers_0/kda/"
    return full, {k[len(prefix):]: v for k, v in dense.items()
                  if k.startswith(prefix)}


def _tree(flat):
    out = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def test_linear_head_shares_add_up_to_the_uncut_sub_layer():
    """Guide section 4: 8 linear heads in 4 shares of 2 (the heads' columns of
    W_q, W_k, W_v, the conv taps, W_f2, dt_bias, A_log, w_b, W_g2 and their
    rows of W_o; W_f1, W_g1 and the norm whole on every share): the shares'
    partial sums add up to the reference's uncut sub-layer."""
    cfg = dict(CFG, linear_attn_config=dict(CFG["linear_attn_config"], num_heads=8))
    full, p = _uncut(cfg, softmax=False)
    d, shares = 8, 4
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 37, 64)), jnp.float32)
    want = jax.jit(lambda p, x: ref.linear_attention(p, x, full, "f32", ""))(p, x)
    per = 8 // shares * d
    total = jnp.zeros_like(want)
    for s in range(shares):
        cols = slice(s * per, (s + 1) * per)
        conv = p["conv_kernel"].reshape(4, 3, 8 * d)[:, :, cols].reshape(4, 3 * per)
        mine = dict(p, **{n + "/kernel": p[n + "/kernel"][:, cols]
                          for n in ("q_proj", "k_proj", "v_proj", "f_b", "g_b")})
        mine.update({"conv_kernel": conv, "dt_bias": p["dt_bias"][cols],
                     "A_log": p["A_log"][2 * s:2 * s + 2],
                     "b_proj/kernel": p["b_proj/kernel"][:, 2 * s:2 * s + 2],
                     "o_proj/kernel": p["o_proj/kernel"][cols]})
        layer = so.KDAMixer(64, 2, d, 4, cfg["gate_rank"], 16, 1e-5,
                            dtype=jnp.float32)
        y, _ = jax.jit(layer.apply)({"params": _tree(mine)}, x)
        share = dict(full, linear_attn_config=dict(full["linear_attn_config"], num_heads=2))
        np.testing.assert_allclose(y, jax.jit(
            lambda p, x: ref.linear_attention(p, x, share, "f32", ""))(mine, x), atol=3e-5)
        total = total + y
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_softmax_head_shares_add_up_to_the_uncut_sub_layer():
    """8 query heads over 4 key/value heads in 4 shares of 2 query heads and
    the key/value head they share: the gated partial sums add up."""
    cfg = dict(CFG, num_attention_heads=8, num_key_value_heads=4)
    full, p = _uncut(cfg, softmax=True)
    d = 8
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 37, 64)), jnp.float32)
    want = jax.jit(lambda p, x: ref.attention(p, x, full, "f32", ""))(p, x)
    total = jnp.zeros_like(want)
    for s in range(4):
        qc, kc = slice(s * 2 * d, (s + 1) * 2 * d), slice(s * d, (s + 1) * d)
        mine = {"q_proj/kernel": p["q_proj/kernel"][:, qc], "k_proj/kernel": p["k_proj/kernel"][:, kc],
                "v_proj/kernel": p["v_proj/kernel"][:, kc], "g_proj/kernel": p["g_proj/kernel"][:, qc],
                "o_proj/kernel": p["o_proj/kernel"][qc]}
        layer = nh.Attention(64, 2, 1, d, 8, jnp.float32, gate=True)
        total = total + jax.jit(layer.apply)({"params": _tree(mine)}, x)
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_expert_shares_add_up_to_the_uncut_routed_layer():
    """16 experts in 4 shares of 4: the routed parts of all shares plus the
    shared expert counted once equal the uncut routed layer of the reference."""
    full = dict(CFG, n_routed_experts=16, expert_offset=0, num_hidden_layers=1)
    dense = ref.init_dense(ref.make_keys(5, full), full)
    p = {k.split("moe/")[1]: v for k, v in dense.items() if "moe/" in k}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 64)), jnp.float32)
    want = jax.jit(lambda p, x: ref.experts(p, x, full, "f32", ""))(p, x)
    shared = jax.jit(lambda p, x: ref.experts(p, x, full, "f32", "no_routed"))(p, x)
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        mine = dict(p, **{k: p[k][first:first + 4]
                          for k in ("experts_gate", "experts_up", "experts_down")})
        layer = nh.MoE(64, 16, 3, 24, 24, 4, first, 1.0, True,
                       dtype=jnp.float32, gated=True)
        y, stats = jax.jit(layer.apply)({"params": mine}, x)
        assert int(stats["dropped"]) == 0
        total = total + (y - shared)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


@pytest.mark.parametrize("gated", [False, True])
def test_both_picks_of_a_blocks_weights_give_one_routed_layer(gated, monkeypatch):
    """A block's weights by the one-hot product (held experts in whole tiles
    of `EXPERT_TILE`) and gathered (any other count): one output, one
    gradient for every leaf and for the input."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 64)), jnp.float32)
    layer = nh.MoE(64, 16, 3, 24, 24, 4, 4, 1.0, True, dtype=jnp.float32,
                   gated=gated)
    params = layer.init(jax.random.PRNGKey(3), x)
    seen, gathers = {}, {}
    for tile, path in ((4, "product"), (8, "gather")):
        monkeypatch.setattr(nh, "EXPERT_TILE", tile)
        fn = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(layer.apply(p, x)[0] ** 2), argnums=(0, 1)))
        gathers[path] = fn.lower(params, x).as_text().count("stablehlo.gather")
        seen[path] = fn(params, x)
    assert gathers["gather"] > gathers["product"]  # two programs, not one
    (lp, gp), (lg, gg) = seen["product"], seen["gather"]
    np.testing.assert_allclose(lp, lg, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gg)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-9)


# -- the configuration, and a forced full-size step ----------------------------

def test_configuration_keeps_every_published_number_and_counts_its_parameters():
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows if r["name"] == "Solar-Open2-250B")
        assert cfg["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    # what was cut is a count (layers, experts, heads, rows), never a width
    lin, pub_lin = cfg["linear_attn_config"], cfg["published"]["linear_attn_config"]
    assert {k: v for k, v in lin.items() if k != "num_heads"} == \
        {k: v for k, v in pub_lin.items() if k != "num_heads"}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["router_width"]) == (4096, 128, 1280, 8, 320)
    assert cfg["num_attention_heads"] * 8 == 64 and cfg["num_key_value_heads"] * 8 == 8
    assert lin["num_heads"] * 8 == 64 and cfg["vocab_size"] * 8 == 196608
    assert [ref.is_softmax(cfg, i) for i in range(4)] == [True, False, False, False]
    sizes = ref.group_sizes(cfg)
    total = sum(sizes.values()) + cfg["vocab_size"] * cfg["hidden_size"]
    assert sizes["L0.attn"] == 13_631_488 + 4096
    assert sizes["L1.kda"] == 18_134_152 + 4096
    assert round(total / 1e6, 1) == 966.7
    # every `make_solar_open2` keyword the map names exists
    import inspect
    names = inspect.signature(models.make_solar_open2).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)


@pytest.mark.parametrize("working_pairs", [0, 16])
def test_no_pair_dropped_when_every_token_chooses_held_experts(working_pairs):
    """A router bias planted so that every token's choices are all held here:
    with a working size of 16 pairs the steps run full size, nothing is
    dropped, and the first loss is the reference's."""
    cfg = dict(CFG, num_hidden_layers=2)
    model = make(cfg, working_pairs=working_pairs)
    stacked = batches(2, s=21)
    tr, state, dense0 = seeded(cfg, model, one(stacked))
    bias = np.zeros(16, np.float32)
    bias[4:8] = 10.0  # experts [4, 8) are the held ones; top 3 of them a token
    params = jax.tree_util.tree_map(lambda x: x, state.dense_params)
    planted = {}
    for i in range(2):
        params[f"layers_{i}"]["moe"]["router_correction_bias"] = jnp.asarray(bias)
        planted[f"layers_{i}/moe/router_correction_bias"] = jnp.asarray(bias)
    state = state.replace(dense_params=params)
    state, m = tr.jit_train_many()(state, stacked)
    assert float(m["module"]["moe.pairs_here"]) == 2 * 21 * 3
    assert int(m["module"]["moe.dropped"]) == 0
    assert int(m["module"]["moe.full_steps"]) == (2 if working_pairs else 0)
    rows = ref.init_rows(ref.make_keys(3, cfg), cfg, jnp.arange(64))["token"]
    first = one(stacked)
    want = jax.jit(_ref_loss(cfg, first["label"]))(
        dict(dense0, **planted), rows[first["sparse"]["token"]])
    np.testing.assert_allclose(m["loss"][0], want, rtol=2e-5)


def test_make_solar_open2_round_trips_through_from_config():
    model = make(CFG, compute_dtype=jnp.bfloat16, working_pairs=512)
    again = models.from_config(model.config)
    assert again.config == model.config
    assert again.module == model.module
    assert again.specs["token"].output_dim == CFG["hidden_size"]
    assert model.config["experts_held"] == 4 and model.config["n_routed_experts"] == 16
    assert model.config["gqa_layers"] == [0]  # of the published list, the layers held
    with pytest.raises(ValueError, match="are not among"):
        make(dict(CFG, expert_offset=14))


def test_bf16_compute_stays_near_the_reference():
    batch = one(batches(1))
    model = make(CFG, compute_dtype=jnp.bfloat16)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    got = jax.jit(lambda p, r: model.module.apply({"params": p}, {"token": r}))(
        state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.logits_fn(d, r, CFG))(dense, rows)
    assert got.dtype == jnp.float32
    assert 1e-6 < float(jnp.mean(jnp.abs(got - want))) < 0.1
    out = tr.jit_eval_step()(state, batch)
    assert out["logits"].shape == got.shape and np.isfinite(float(out["loss"]))


def test_every_stage_name_reaches_the_compiled_program():
    from openembedding_tpu.utils import trace
    batch = one(batches(1))
    model = make(CFG)
    tr, state, _ = seeded(CFG, model, batch)
    text = tr.jit_train_step().lower(state, batch).compile().as_text()
    inner = {p.rsplit("/", 1)[-1] for p in trace.scope_map(text).values()}
    assert {"kda.qkv", "kda.conv", "kda.gates", "kda.scan", "kda.gate_norm",
            "kda.out", "attn.qkv", "attn.core", "attn.gate", "attn.out",
            "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
            "moe.shared", "lm.head", "lm.loss"} <= inner
