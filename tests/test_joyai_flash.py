"""JoyAI-LLM-Flash (latent attention, a dense SwiGLU layer, routed SwiGLU
experts, a multi-token-prediction module) on the normal train path, against
the benchmark's plain reference (`benchmark/reference/joyai_flash.py`:
float32, full-softmax attention with explicit rotary tables, a loop over the
experts held, the module as written) at small widths on seeded random
weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import joyai_flash as ref
from openembedding_tpu import models
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import joyai_flash as jf
from openembedding_tpu.models import nemotron_h as nh

CFG = dict(hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
           num_nextn_predict_layers=1, num_attention_heads=4, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=8, rope_theta=32000000, intermediate_size=48,
           n_routed_experts=4, router_width=16, expert_offset=4,
           num_experts_per_tok=3, moe_intermediate_size=24, n_shared_experts=1,
           routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
           vocab_size=64, mtp_loss_weight=0.3, table_init_stddev=1.0,
           learning_rate=0.05, adagrad_initial_accumulator=0.1,
           adagrad_epsilon=1e-7)
ACC0 = CFG["adagrad_initial_accumulator"]
SAME = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta", "intermediate_size", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "expert_offset",
        "routed_scaling_factor", "norm_topk_prob", "mtp_loss_weight")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def make(cfg, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 8)
    return models.make_joyai_flash(
        vocabulary=cfg["vocab_size"], n_routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"], eps=cfg["rms_norm_eps"],
        **{k: cfg[k] for k in SAME}, **kw)


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


def batches(k, b=2, s=21, vocab=64, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, vocab, size=(k, b, s + 1)).astype(np.int32)
    return {"sparse": {"token": tok[:, :, :-1]}, "label": tok[:, :, 1:]}


def one(stacked, i=0):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def _ref_loss(cfg, y, fault=""):
    w = jnp.ones(y.shape)

    def loss(dense, rows):
        main, nxt, _ = ref.forward(dense, rows, cfg)
        return ref.losses(main, nxt, y, w, cfg, fault)[0]
    return loss


def test_logits_both_losses_and_every_gradient_leaf_match_reference():
    batch = one(batches(1))
    model = make(CFG)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    y, w = batch["label"], jnp.ones(batch["label"].shape)

    def prog_loss(params, rows):
        return model.loss_fn(
            model.module.apply({"params": params}, {"token": rows}), y)

    main, nxt = jax.jit(lambda p, r: model.module.apply(
        {"params": p}, {"token": r}))(state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.logits_fn(d, r, CFG))(dense, rows)
    np.testing.assert_allclose(main, want[0], atol=2e-5)
    np.testing.assert_allclose(nxt, want[1], atol=2e-5)
    terms_r = ref.losses(want[0], want[1], y, w, CFG)
    lr, (gd, gr) = jax.jit(jax.value_and_grad(_ref_loss(CFG, y), (0, 1)))(dense, rows)
    (lp, terms), (pd, pr) = jax.jit(jax.value_and_grad(
        prog_loss, (0, 1), has_aux=True))(state.dense_params, rows)
    assert abs(float(lp) - float(lr)) < 1e-5
    assert abs(float(terms["lm.main_loss"]) - float(terms_r[1])) < 1e-5
    assert abs(float(terms["lm.mtp_loss"]) - float(terms_r[2])) < 1e-5
    assert abs(float(lp) - float(terms_r[1] + 0.3 * terms_r[2])) < 1e-5
    np.testing.assert_allclose(pr, gr, atol=1e-6)
    got = {_path(kp): v for kp, v in
           jax.tree_util.tree_flatten_with_path(pd)[0]}
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=2e-6, err_msg=path)
    # the correction bias is a buffer: no gradient reaches it
    assert not np.any(got["layers_1/moe/router_correction_bias"])
    assert not np.any(got["mtp/layer/moe/router_correction_bias"])


def test_table_rows_receive_the_sum_of_both_uses():
    """One pull, two uses (the stack's input, and the next token's row in the
    prediction module): the rows' gradient under the whole loss is the main
    term's plus the module's, through the Trainer's own step."""
    batch = one(batches(1))
    cfg = dict(CFG, adagrad_initial_accumulator=0.0)  # one step leaves g^2, exactly
    w0 = np.asarray(ref.init_rows(ref.make_keys(3, cfg), cfg,
                                  jnp.arange(cfg["vocab_size"]))["token"])
    grads = {}
    for name, kw in (("whole", {}), ("main", {"mtp_weight": 0.0}),
                     ("mtp", {"main_weight": 0.0})):
        model = make(cfg)
        model.loss_fn = lambda o, y, w=None, kw=kw: jf.mtp_xent(
            o, y, w, **{"mtp_weight": 0.3, **kw})
        tr, state, _ = seeded(cfg, model, batch)
        state, _ = tr.jit_train_step()(state, batch)
        ts = state.tables["token"]
        grads[name] = np.sqrt(np.asarray(ts.slots["accum"], np.float64)) * \
            np.sign(w0 - np.asarray(ts.weights))
    assert np.abs(grads["mtp"]).max() > 1e-4 and np.abs(grads["main"]).max() > 1e-4
    np.testing.assert_allclose(grads["main"] + grads["mtp"], grads["whole"],
                               atol=1e-6)


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
    assert {"L0.attn", "L0.mlp", "L1.router", "L1.experts", "L1.shared",
            "mtp.merge", "mtp.attn", "mtp.experts", "head"} <= set(got)
    for g, v in out["dense"].items():
        np.testing.assert_allclose(got[g], [v[0], v[2]], rtol=2e-3, err_msg=g)
    ts = state.tables["token"]
    np.testing.assert_allclose(
        [np.sum(np.asarray(ts.slots["accum"], np.float64) - ACC0),
         np.sum(np.square(np.asarray(ts.weights, np.float64) - rows0))],
        [out["tables"]["token"][0], out["tables"]["token"][2]], rtol=2e-3)
    assert set(m["module"]) == set(dict(jf.JoyAIFlash.window_stats))
    assert int(m["module"]["moe.dropped"]) == 0
    # the window's loss terms are the reference's, averaged over its steps
    np.testing.assert_allclose(
        [m["module"]["lm.main_loss"], m["module"]["lm.mtp_loss"]],
        np.mean(out["loss_terms"], axis=0), rtol=2e-5)
    # 3 routed layers counted: two of the stack and the module's
    assert out["pairs_held"].shape == (3, 3)
    np.testing.assert_allclose(m["module"]["moe.pairs_here"],
                               np.mean(out["pairs_held"]), rtol=1e-6)
    tr.record_window_stats(m)
    from openembedding_tpu.utils import metrics
    assert metrics.report()["lm.mtp_loss"] == pytest.approx(
        float(m["module"]["lm.mtp_loss"]))


# key width, value width, query heads, key/value heads: the latent-attention
# shape (keys wider than values, a head each) and NemotronH's grouped heads
@pytest.mark.parametrize("d,dv,hq,hkv", [(12, 8, 4, 4), (8, 8, 4, 2)])
@pytest.mark.parametrize("seq,block", [(32, 8), (21, 8), (16, 16), (7, 16)])
def test_blockwise_core_equals_full_softmax(seq, block, d, dv, hq, hkv):
    rng = np.random.default_rng(seq + d)
    q = jnp.asarray(rng.normal(size=(2, seq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, seq, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, seq, hkv, dv)), jnp.float32)
    kf, vf = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vf)
    got = jax.jit(lambda *t: nh.blockwise_causal_attention(*t, block=block))(q, k, v)
    assert got.shape == (2, seq, hq, dv)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("width,theta", [(4, 32e6), (64, 32e6), (8, 1e4)])
def test_rotary_over_interleaved_pairs_is_a_complex_multiplication(width, theta):
    rng = np.random.default_rng(width)
    x = rng.normal(size=(2, 37, 3, width)).astype(np.float32)
    z = x[..., 0::2].astype(np.float64) + 1j * x[..., 1::2]
    ang = np.arange(37)[:, None] * theta ** (-2.0 * np.arange(width // 2) / width)
    z = z * np.exp(1j * ang)[None, :, None, :]
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    got = jax.jit(lambda x: jf.rope_interleaved(x, jnp.arange(37), theta))(x)
    np.testing.assert_allclose(got, want, atol=3e-6)
    np.testing.assert_allclose(ref.rope(jnp.asarray(x), theta), want, atol=3e-6)
    # a rotation: norms of pairs kept, and q . k depends on the distance alone
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def _moe_layer(cfg, held, offset, **kw):
    return nh.MoE(cfg["hidden_size"], cfg["router_width"],
                  cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
                  cfg["n_shared_experts"] * cfg["moe_intermediate_size"], held,
                  offset, cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
                  dtype=jnp.float32, gated=True, **kw)


def _moe_params(cfg, seed=5):
    """The uncut routed layer's leaves (all `router_width` experts)."""
    full = dict(cfg, n_routed_experts=cfg["router_width"], expert_offset=0,
                num_hidden_layers=1, first_k_dense_replace=0,
                num_nextn_predict_layers=0)
    dense = ref.init_dense(ref.make_keys(seed, full), full)
    return full, {k.split("moe/")[1]: v for k, v in dense.items() if "moe/" in k}


def _share(p, first, count):
    return dict(p, **{k: p[k][first:first + count]
                      for k in ("experts_gate", "experts_up", "experts_down")})


def test_shares_add_up_to_the_uncut_layer():
    """Guide section 4: with 16 experts in shares of 4, the routed parts of
    all four shares plus the shared expert counted once equal the uncut
    gated layer of the reference."""
    full, p = _moe_params(CFG)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)), jnp.float32)
    want = jax.jit(lambda p, x: ref.experts(p, x, full, "f32", ""))(p, x)
    shared = jax.jit(lambda p, x: ref.experts(p, x, full, "f32", "no_routed"))(p, x)
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = dict(full, n_routed_experts=4, expert_offset=first)
        y, stats = jax.jit(_moe_layer(CFG, 4, first).apply)(
            {"params": _share(p, first, 4)}, x)
        assert int(stats["dropped"]) == 0
        np.testing.assert_allclose(
            y, jax.jit(lambda p, x: ref.experts(p, x, share, "f32", ""))(
                _share(p, first, 4), x), atol=2e-5)
        total = total + (y - shared)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


@pytest.mark.parametrize("working_pairs", [0, 16])
def test_no_pair_dropped_when_every_token_chooses_held_experts(working_pairs):
    """A router bias planted so that every token's choices are all held here,
    in a stack layer and in the module's: with a working size of 16 pairs the
    steps run full size, nothing is dropped, and the losses are the
    reference's."""
    cfg = dict(CFG, num_hidden_layers=2)
    model = make(cfg, working_pairs=working_pairs)
    stacked = batches(2)
    tr, state, dense0 = seeded(cfg, model, one(stacked))
    bias = np.zeros(16, np.float32)
    bias[4:8] = 10.0  # experts [4, 8) are the held ones; top 3 of them a token
    params = jax.tree_util.tree_map(lambda x: x, state.dense_params)
    params["layers_1"]["moe"]["router_correction_bias"] = jnp.asarray(bias)
    params["mtp"]["layer"]["moe"]["router_correction_bias"] = jnp.asarray(bias)
    state = state.replace(dense_params=params)
    state, m = tr.jit_train_many()(state, stacked)
    assert float(m["module"]["moe.pairs_here"]) == 2 * 21 * 3
    assert int(m["module"]["moe.dropped"]) == 0
    assert int(m["module"]["moe.full_steps"]) == (2 if working_pairs else 0)
    planted = {"layers_1/moe/router_correction_bias": jnp.asarray(bias),
               "mtp/layer/moe/router_correction_bias": jnp.asarray(bias)}
    dense = dict(dense0, **planted)
    rows = ref.init_rows(ref.make_keys(3, cfg), cfg, jnp.arange(64))["token"]
    first = one(stacked)
    want = jax.jit(_ref_loss(cfg, first["label"]))(dense, rows[first["sparse"]["token"]])
    np.testing.assert_allclose(m["loss"][0], want, rtol=2e-5)


def test_make_joyai_flash_round_trips_through_from_config():
    model = make(CFG, compute_dtype=jnp.bfloat16, working_pairs=512)
    again = models.from_config(model.config)
    assert again.config == model.config
    assert again.module == model.module
    assert again.specs["token"].output_dim == CFG["hidden_size"]
    assert model.config["experts_held"] == 4 and model.config["n_routed_experts"] == 16
    assert again.loss_fn.keywords == {"mtp_weight": 0.3}
    with pytest.raises(ValueError, match="are not among"):
        make(dict(CFG, expert_offset=14))
    # without the module: one array of logits, the second term reads 0
    plain = make(dict(CFG, num_nextn_predict_layers=0))
    batch = one(batches(1))
    tr, state, _ = seeded(dict(CFG, num_nextn_predict_layers=0), plain, batch)
    out = tr.jit_eval_step()(state, batch)
    assert out["logits"].shape == (2, 21, 64)
    state, m = tr.jit_train_many()(state, batches(1))
    assert float(m["module"]["lm.mtp_loss"]) == 0.0


def test_bf16_compute_stays_near_the_reference():
    """The configuration's precision (bf16 compute, f32 parameters) at the
    small size: near the f32 reference, not equal to it."""
    batch = one(batches(1))
    model = make(CFG, compute_dtype=jnp.bfloat16)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    main, nxt = jax.jit(lambda p, r: model.module.apply({"params": p}, {"token": r}))(
        state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.logits_fn(d, r, CFG))(dense, rows)
    for got, w in ((main, want[0]), (nxt, want[1])):
        assert got.dtype == jnp.float32
        assert 1e-6 < float(jnp.mean(jnp.abs(got - w))) < 0.1
    # eval keeps the main logits alone
    out = tr.jit_eval_step()(state, batch)
    assert out["logits"].shape == main.shape and np.isfinite(float(out["loss"]))


def test_every_stage_name_reaches_the_compiled_program():
    """`trace.scope_map` of the compiled step finds each of the model's stage
    names, the module's layer's own under `mtp.layer`."""
    from openembedding_tpu.utils import trace
    batch = one(batches(1))
    model = make(CFG)
    tr, state, _ = seeded(CFG, model, batch)
    text = tr.jit_train_step().lower(state, batch).compile().as_text()
    paths = set(trace.scope_map(text).values())
    inner = {p.rsplit("/", 1)[-1] for p in paths}
    assert {"attn.q_latent", "attn.kv_latent", "attn.rope", "attn.core",
            "attn.out", "mlp.dense", "moe.route", "moe.dispatch",
            "moe.experts", "moe.combine", "moe.shared", "mtp.merge",
            "mtp.head", "mtp.loss", "lm.head", "lm.loss"} <= inner
    assert any("mtp.layer/attn.core" in p for p in paths)
    assert any("mtp.layer/moe.experts" in p for p in paths)
