"""ZAYA1 (compressed convolutional attention, a router MLP whose state
travels up the stack, top-1 routed experts, the token table tied to the head
and trained densely) on the normal train path, against the benchmark's plain
reference (`benchmark/reference/zaya1.py`: float32, full-softmax attention, a
loop over the experts held, one dense Adagrad step on the tied table) at
small widths on seeded random weights."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import zaya1 as ref
from openembedding_tpu import models
from openembedding_tpu.model import TABLES_KEY, Trainer
from openembedding_tpu.models import nemotron_h as nh
from openembedding_tpu.models import zaya1 as za

CFG = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, cca_time0=2, cca_time1=2,
           partial_rotary_factor=0.5,
           rope_parameters={"hybrid": {"rope_theta": 5000000}},
           layer_types=["hybrid"] * 2, router_hidden_size=16, num_experts=4,
           router_width=8, expert_offset=4, num_experts_per_tok=1,
           moe_intermediate_size=24, rms_norm_eps=1e-5, vocab_size=64,
           table_init_stddev=0.3, learning_rate=0.05,
           adagrad_initial_accumulator=0.1, adagrad_epsilon=1e-7)
ACC0 = CFG["adagrad_initial_accumulator"]
TABLE = ref.TABLE
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "zaya1-8b-e8of16.json")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def make(cfg, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 16)
    return models.make_zaya1(
        vocabulary=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], cca_time0=cfg["cca_time0"],
        cca_time1=cfg["cca_time1"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=cfg["rope_parameters"]["hybrid"]["rope_theta"],
        router_hidden_size=cfg["router_hidden_size"],
        num_experts=cfg["router_width"], experts_held=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        eps=cfg["rms_norm_eps"], table_init_stddev=cfg["table_init_stddev"],
        **kw)


def _path(kp):
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def _flat(tree):
    return {_path(kp): v for kp, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def seeded(cfg, model, batch, seed=3):
    """(trainer, state with every leaf from the benchmark's hash draw, the
    reference's flat {path: leaf})."""
    tr = Trainer(model, embed.Adagrad(
        learning_rate=cfg["learning_rate"],
        initial_accumulator_value=cfg["adagrad_initial_accumulator"],
        epsilon=cfg["adagrad_epsilon"]))
    state = jax.jit(tr.init)(batch)
    dense = ref.init_dense(ref.make_keys(seed, cfg), cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state.dense_params)
    assert {_path(kp): v.shape for kp, v in flat} == \
        {p: tuple(s) for p, s, _ in ref.dense_leaves(cfg)}
    assert not state.tables and ref.tables_of(cfg) == {}
    params = jax.tree_util.tree_unflatten(
        treedef, [jnp.array(dense[_path(kp)]) for kp, _ in flat])
    return tr, state.replace(dense_params=params), dense


def batches(k, b=2, s=29, vocab=64, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, vocab, size=(k, b, s + 1)).astype(np.int32)
    return {"sparse": {"token": tok[:, :, :-1]}, "label": tok[:, :, 1:]}


def one(stacked, i=0):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def _ref_loss(cfg, tokens, y, fault=""):
    weight = jnp.ones(y.shape, jnp.float32)
    return lambda dense: ref.xent(
        ref.logits_fn(dense, tokens, cfg, "f32", fault), y, weight)


def _prog_logits(model, params, tokens):
    """The module as `Trainer` calls it: rows looked up from the table, and
    the table itself beside them."""
    table = params["__embeddings__"]["token"]
    return model.module.apply(
        {"params": params}, {"token": table[tokens], TABLES_KEY: {"token": table}})


# -- pieces against their plain forms -------------------------------------------

def test_rope_half_turns_pairs_a_half_apart_and_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 3, 16)),
                    jnp.float32)
    got = za.rope_half(x, jnp.arange(9), 1e4, 8)
    want = ref._rotary(x, 1e4, 8)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)  # position 0
    # a turn keeps each pair's length
    np.testing.assert_allclose(got[..., 0] ** 2 + got[..., 4] ** 2,
                               x[..., 0] ** 2 + x[..., 4] ** 2, rtol=1e-5)


def test_grouped_causal_conv_mixes_inside_a_head_only_and_reads_no_future():
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(1, 6, 3, 4)), jnp.float32)
    w = jnp.asarray(r.normal(size=(2, 3, 4, 4)), jnp.float32)
    b = jnp.asarray(r.normal(size=(3, 4)), jnp.float32)
    got = za.grouped_causal_conv(x, w, b, jnp.float32)
    want = np.zeros((1, 6, 3, 4))
    for t in range(6):
        for n in range(3):
            want[0, t, n] = b[n] + x[0, t, n] @ w[1, n] + (
                x[0, t - 1, n] @ w[0, n] if t else 0.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    moved = za.grouped_causal_conv(x.at[0, 4, 1].add(1.0), w, b, jnp.float32)
    changed = np.abs(np.asarray(moved - got)).sum(axis=-1)[0]  # (S, N)
    assert changed[:4].sum() == 0 and changed[:, [0, 2]].sum() == 0
    assert changed[4, 1] > 0 and changed[5, 1] > 0


# -- program against reference -----------------------------------------------------

@pytest.fixture(scope="module")
def first_step():
    """One batch through both sides, each compiled ONCE for the tests below:
    (batch, trainer, seeded state, the reference's leaves, the program's
    (loss, logits, gradients), the reference's)."""
    with jax.default_matmul_precision("highest"):
        batch = one(batches(1))
        model = make(CFG)
        tr, state, dense = seeded(CFG, model, batch)
        tokens, y = batch["sparse"]["token"], batch["label"]

        def prog(params):
            logits = _prog_logits(model, params, tokens)
            return model.loss_fn(logits, y), logits

        def plain(d):
            logits = ref.logits_fn(d, tokens, CFG)
            return ref.xent(logits, y, jnp.ones(y.shape, jnp.float32)), logits

        (lp, logits), pd = jax.jit(jax.value_and_grad(prog, has_aux=True))(
            state.dense_params)
        (lr, want), gd = jax.jit(jax.value_and_grad(plain, has_aux=True))(dense)
        return batch, tr, state, dense, (lp, logits, _flat(pd)), (lr, want, gd)


def test_logits_loss_and_every_gradient_leaf_match_reference(first_step):
    batch, _, _, _, (lp, logits, got), (lr, want, gd) = first_step
    np.testing.assert_allclose(logits, want, atol=3e-5)
    assert abs(float(lp) - float(lr)) < 1e-5
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=5e-6, err_msg=path)
    assert not np.any(got["layers_1/router/balance_bias"])
    # the tied table's gradient is the SUM of the lookup's and the head's:
    # rows no token looks up still get the head's part
    absent = np.setdiff1d(np.arange(CFG["vocab_size"]),
                          np.unique(batch["sparse"]["token"]))
    assert absent.size and np.all(np.abs(np.asarray(got[TABLE])[absent]).sum(-1) > 0)
    # the router's state reaches the layer above: layer 1's scale on the
    # state it receives has a gradient
    assert np.any(got["layers_1/router/carry_scale"] != 0)


def test_router_state_of_a_layer_reaches_the_layer_above():
    """Layer l's router state is what layer l + 1's router adds in: moving
    layer 0's `down/bias` (which enters layer 0's rho alone) moves layer 1's
    gates, and the reference's `no_router_carry` cuts exactly that path. (The
    program is held to the reference WITH the path by the test above, whose
    `carry_scale` gradient is not zero.)"""
    batch = one(batches(1))
    dense = ref.init_dense(ref.make_keys(3, CFG), CFG)
    tokens = batch["sparse"]["token"]

    def layer1_gate(bias0, fault):
        d = dict(dense, **{"layers_0/router/down/bias": bias0})
        r, carried = d[TABLE][tokens], None
        for i in range(2):
            lp = ref._sub(d, f"layers_{i}/")
            u = ref._rms(r, lp["attn_norm_scale"], 1e-5)
            r = ref._merge(ref._sub(lp, "attn_merge/"), r,
                           ref.cca(ref._sub(lp, "cca/"), u, CFG, "f32", ""), i > 0)
            ut = ref._rms(r, lp["ffn_norm_scale"], 1e-5).reshape(-1, 64)
            _, gate, carried = ref.route(ref._sub(lp, "router/"), ut, carried,
                                         CFG, fault)
        return gate  # layer 1's; r is untouched by the experts here

    b0 = dense["layers_0/router/down/bias"]
    with_carry = jax.jit(lambda b: layer1_gate(b, ""))
    without = jax.jit(lambda b: layer1_gate(b, "no_router_carry"))
    assert float(jnp.max(jnp.abs(with_carry(b0 + 0.5) - with_carry(b0)))) > 1e-3
    np.testing.assert_array_equal(without(b0), without(b0 + 0.5))


def _group_sums(cfg, state, dense0):
    """Per leaf group [sum(acc - acc0), sum((w - w0)^2)] of a program state."""
    groups, out = ref.leaf_groups(cfg), {}
    params = _flat(state.dense_params)
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
    metrics.reset_all()
    stacked = batches(3)
    model = make(CFG)
    tr, state, dense0 = seeded(CFG, model, one(stacked))
    many = tr.jit_train_many()
    # every stage name reaches the lowered scan, and no shared expert's does
    text = many.lower(state, stacked).as_text(debug_info=True)
    for name in ("cca.project", "cca.conv", "cca.mean_norm", "attn.core",
                 "cca.out", "moe.route/router.mlp", "moe.dispatch",
                 "moe.experts", "moe.combine", "lm.head", "lm.loss"):
        assert name in text, name
    assert "moe.shared" not in text
    state, m = many(state, stacked)
    ids = np.arange(CFG["vocab_size"], dtype=np.int32)
    out = jax.device_get(ref.follow(
        3, CFG, 1, ids, stacked["sparse"]["token"], stacked["label"], None))
    np.testing.assert_allclose(m["loss"], out["losses"], rtol=2e-5)
    assert float(m["loss"][0]) > float(m["loss"][2])
    got = _group_sums(CFG, state, dense0)
    assert set(got) == set(out["dense"]) == set(ref.group_sizes(CFG))
    assert {"table", "head", "L0.cca", "L1.router", "L1.experts",
            "L0.ffn"} <= set(got)
    for g, v in out["dense"].items():
        np.testing.assert_allclose(got[g], [v[0], v[2]], rtol=2e-3, err_msg=g)
    assert out["tables"] == {}
    assert set(m["module"]) == set(dict(za.Zaya1.window_stats))
    assert int(m["module"]["moe.dropped"]) == 0
    assert 1.0 / 8 < float(m["module"]["router.gate_mean"]) < 1.0
    assert float(m["module"]["cca.key_temp_max"]) >= 1.0
    assert out["pairs_held"].shape == (3, 2)
    np.testing.assert_allclose(m["module"]["moe.pairs_here"],
                               np.mean(out["pairs_held"]), rtol=1e-6)
    tr.record_window_stats(m)
    report = metrics.report()
    assert report["router.gate_mean"] == pytest.approx(
        float(m["module"]["router.gate_mean"]))
    assert report["cca.key_temp_max"] == pytest.approx(
        float(m["module"]["cca.key_temp_max"]))


def test_tied_table_takes_one_adagrad_step_on_the_summed_gradient(first_step):
    """The rows the ids pull and the head are one parameter with one
    accumulator: after one step the accumulator has received the SQUARE OF
    THE SUM of the lookup's and the head's gradients (the reference's whole
    gradient of the table), once, and the table is one dense Adagrad step on
    that sum: not a step a gradient."""
    batch, tr, state, dense, _, (_, _, gd) = first_step
    g = gd[TABLE]
    acc = ACC0 + g * g
    want = dense[TABLE] - CFG["learning_rate"] * g / (jnp.sqrt(acc) + 1e-7)
    new, _ = tr.jit_train_step()(jax.tree_util.tree_map(jnp.array, state), batch)
    np.testing.assert_allclose(new.dense_params["__embeddings__"]["token"],
                               want, atol=2e-6)
    np.testing.assert_allclose(
        new.dense_slots["__embeddings__"]["token"]["accum"].reshape(acc.shape),
        acc, rtol=1e-4)
    assert not new.tables  # nothing of it is on the sparse path
    # rows the batch never looks up moved too (the head's gradient alone)
    absent = np.setdiff1d(np.arange(CFG["vocab_size"]),
                          np.unique(batch["sparse"]["token"]))
    moved = np.abs(np.asarray(new.dense_params["__embeddings__"]["token"]
                              - dense[TABLE]))[absent]
    assert np.all(moved.sum(-1) > 0)


# -- shares add up, and the opening in `MoE` ----------------------------------------

def _layer_params(cfg, seed=5):
    full = dict(cfg, num_hidden_layers=1)
    dense = ref.init_dense(ref.make_keys(seed, full), full)
    return full, {k[len("layers_0/"):]: v for k, v in dense.items()
                  if k.startswith("layers_0/")}


def test_expert_shares_add_up_to_the_uncut_expert_sub_layer():
    """16 experts in 2 shares of 8 (`expert_offset` 0 and 8), the router
    whole on both: the shares' partial sums add up to the reference's uncut
    expert sub-layer; there is no shared expert to count once."""
    full, p = _layer_params(dict(CFG, num_experts=16, router_width=16,
                                 expert_offset=0))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 64)), jnp.float32)
    ut = x.reshape(-1, 64)
    rp = ref._sub(p, "router/")
    chosen, gate, _ = jax.jit(lambda rp, ut: ref.route(rp, ut, None, full, ""))(rp, ut)
    want = jax.jit(lambda mp, ut, c, g: ref.experts(mp, ut, c, g, full, "f32"))(
        ref._sub(p, "moe/"), ut, chosen, gate)
    total, pairs = jnp.zeros_like(want), 0
    for first in (0, 8):
        mine = {k: p["moe/" + k][first:first + 8]
                for k in ("experts_gate", "experts_up", "experts_down")}
        layer = nh.MoE(64, 16, 1, 24, 0, 8, first, dtype=jnp.float32, gated=True)
        y, stats = jax.jit(layer.apply)(
            {"params": mine}, x, (chosen[:, None], gate[:, None]))
        assert int(stats["dropped"]) == 0
        total, pairs = total + y.reshape(-1, 64), pairs + int(stats["pairs_here"])
    assert pairs == ut.shape[0]  # one expert a token, every token in one share
    np.testing.assert_allclose(total, want, atol=5e-5)


@pytest.mark.parametrize("working_pairs", [0, 256, 16],
                         ids=["every_pair_fits", "compact", "forced_full_size"])
def test_one_expert_a_token_goes_through_every_path_of_the_routed_layer(
        working_pairs, monkeypatch):
    """k = 1 with the routing handed in: the whole-run path (the working size
    holds every pair), the compact path and the full-size path (a working
    size of 16 pairs for 48 tokens) give the reference's sub-layer and drop
    nothing."""
    monkeypatch.setattr(nh, "BLOCK_ROWS", 8)
    full, p = _layer_params(dict(CFG, num_experts=4, router_width=8,
                                 expert_offset=2))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 150, 64)), jnp.float32)
    ut = x.reshape(-1, 64)
    chosen, gate, _ = jax.jit(lambda rp, ut: ref.route(rp, ut, None, full, ""))(
        ref._sub(p, "router/"), ut)
    mp = ref._sub(p, "moe/")
    want = jax.jit(lambda mp, ut, c, g: ref.experts(mp, ut, c, g, full, "f32"))(
        mp, ut, chosen, gate)
    layer = nh.MoE(64, 8, 1, 24, 0, 4, 2, working_pairs=working_pairs,
                   dtype=jnp.float32, gated=True)
    y, stats = jax.jit(layer.apply)({"params": mp}, x,
                                    (chosen[:, None], gate[:, None]))
    np.testing.assert_allclose(y.reshape(-1, 64), want, atol=5e-5)
    held = int(np.sum((np.asarray(chosen) >= 2) & (np.asarray(chosen) < 6)))
    assert int(stats["pairs_here"]) == held and int(stats["dropped"]) == 0
    assert int(stats["full_steps"]) == (working_pairs == 16)
    if working_pairs == 256:
        assert 16 < held <= 256  # the compact path was the one that ran


def test_no_shared_expert_is_built_at_width_zero_and_a_callers_routing_needs_no_router():
    x = jnp.zeros((1, 8, 64), jnp.float32)
    routing = (jnp.zeros((8, 1), jnp.int32), jnp.ones((8, 1), jnp.float32))
    layer = nh.MoE(64, 8, 1, 24, 0, 4, 0, dtype=jnp.float32, gated=True)
    names = set(jax.eval_shape(layer.init, jax.random.PRNGKey(0), x,
                               routing)["params"])
    assert names == {"experts_gate", "experts_up", "experts_down"}
    own = set(jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"])
    assert own == names | {"router_kernel", "router_correction_bias"}


# -- the configuration ------------------------------------------------------------

def test_configuration_keeps_every_published_number_and_counts_its_parameters():
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows if r["name"] == "ZAYA1-8B")
        assert cfg["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    # what was cut is a count (layers, experts, rows), never a width
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["cca_time0"],
            cfg["cca_time1"], cfg["router_hidden_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["router_width"], cfg["partial_rotary_factor"]) == \
        (2048, 8, 2, 128, 2, 2, 256, 2048, 1, 16, 0.5)
    assert cfg["vocab_size"] * 8 == 262272 and cfg["num_experts"] * 2 == 16
    assert cfg["tie_word_embeddings"] is True and "zaya_use_mod" in cfg["assumed"]
    sizes = ref.group_sizes(cfg)
    assert sizes["table"] == 32784 * 2048
    assert sizes["L1.experts"] == 8 * 3 * 2048 * 2048
    assert sizes["L1.cca"] - sizes["L0.cca"] == 2 * 2048
    assert sizes["L1.router"] - sizes["L0.router"] == 256
    assert sum(sizes.values()) == 708_660_588
    names = inspect.signature(models.make_zaya1).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)


def test_published_file_builds_through_from_config_and_round_trips():
    """The published file's keys through `make_keywords` -> `make_zaya1` ->
    `EmbeddingModel.config` -> `models.from_config`: the same module (shapes
    only: nothing of this size is made here)."""
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)

    def at(path):
        node = cfg
        for key in path.split("."):
            node = node[key]
        return node
    model = models.make_zaya1(**{kw: at(path) for path, kw
                                 in cfg["make_keywords"].items()})
    again = models.from_config(model.config)
    assert again.config == model.config and again.module == model.module
    assert model.config["family"] == "zaya1"
    assert model.module.dims.rope_theta == 5_000_000
    spec = again.specs["token"]
    assert spec.sparse_as_dense and (spec.input_dim, spec.output_dim) == (32784, 2048)
    assert again.module.takes_tables
    with pytest.raises(ValueError, match="ONE expert"):
        models.from_config(model.config, num_experts_per_tok=2)


def test_bf16_compute_stays_near_the_reference():
    batch = one(batches(1))
    _, state, dense = seeded(CFG, make(CFG), batch)
    tokens = batch["sparse"]["token"]
    model = make(CFG, compute_dtype=jnp.bfloat16)
    got = jax.jit(lambda p: _prog_logits(model, p, tokens))(state.dense_params)
    want = jax.jit(lambda d: ref.logits_fn(d, tokens, CFG))(dense)
    assert got.dtype == jnp.float32
    # a top-1 flip under bf16 moves a token's whole expert term: compare by
    # the typical element, and ask that only few elements stray
    err = np.abs(np.asarray(got - want))
    assert np.median(err) < 0.05 * float(np.std(want))
    assert np.mean(err > 0.5 * float(np.std(want))) < 0.05
