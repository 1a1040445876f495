"""SDAR (the Qwen3-MoE decoder trained by block diffusion over a noised and a
clean copy of every sequence) on the normal train path, against the
benchmark's plain reference (`benchmark/reference/sdar_moe.py`: float32, the
block-diffusion mask written out in full, rotary tables made on the host, a
loop over the experts held, the weighted masked loss as written) at width 64
on seeded random weights; the routed layer's expert shares against the uncut
layer; the device-side noise against its law; the mask as the model sees it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import sdar_moe as ref
from openembedding_tpu import models
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import nemotron_h as nh
from openembedding_tpu.models import sdar_moe as sd

# 4 of 16 experts held (from 4), 2 layers, sequences of 16 clean tokens in
# denoising blocks of 4: 32 positions through the stack
CFG = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, rope_theta=1_000_000,
           router_width=16, num_experts=4, expert_offset=4,
           num_experts_per_tok=3, moe_intermediate_size=32,
           norm_topk_prob=True, rms_norm_eps=1e-6, vocab_size=64, mask_id=63,
           block_length=4, table_init_stddev=1.0, learning_rate=0.05,
           adagrad_initial_accumulator=0.1, adagrad_epsilon=1e-7)
ACC0 = CFG["adagrad_initial_accumulator"]
L = 16


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def make(cfg, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 8)
    return models.make_sdar_moe(
        vocabulary=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        num_experts=cfg["router_width"], experts_held=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"], eps=cfg["rms_norm_eps"],
        block_length=cfg["block_length"], **kw)


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


def batches(k, b=2, seed=0, cfg=CFG):
    """K block-diffusion batches: clean ids below the mask id, the noise from
    `corrupt_blocks`."""
    x0 = np.random.default_rng(seed).integers(
        0, cfg["mask_id"], size=(k, b, L)).astype(np.int32)
    xt, w = jax.jit(lambda key, x: sd.corrupt_blocks(
        key, x, cfg["block_length"], 1e-3, cfg["mask_id"]))(
            jax.random.PRNGKey(seed), x0.reshape(k * b, L))
    xt = np.asarray(xt).reshape(k, b, L)
    return {"sparse": {"token": np.concatenate([xt, x0], axis=-1)},
            "dense": np.asarray(w, np.float32).reshape(k, b, L), "label": x0}


def one(stacked, i=0):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def test_loss_and_every_gradient_leaf_match_reference():
    batch = one(batches(1))
    model = make(CFG)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    y, w = batch["label"], batch["dense"]
    assert 0 < np.mean(w > 0) < 1

    def prog_loss(params, rows):
        return model.loss_fn(model.module.apply(
            {"params": params}, {"token": rows}, w), y)

    def ref_loss(dense, rows):
        return ref.diffusion_loss(ref.forward(dense, rows, CFG)[0], y, w)

    logits, weight = jax.jit(lambda p, r: model.module.apply(
        {"params": p}, {"token": r}, w))(state.dense_params, rows)
    assert logits.shape == (2, L, 64) and logits.dtype == jnp.float32
    np.testing.assert_array_equal(weight, w)
    want = jax.jit(lambda d, r: ref.forward(d, r, CFG)[0])(dense, rows)
    np.testing.assert_allclose(logits, want, atol=2e-5)
    lr, (gd, gr) = jax.jit(jax.value_and_grad(ref_loss, (0, 1)))(dense, rows)
    lp, (pd, pr) = jax.jit(jax.value_and_grad(prog_loss, (0, 1)))(
        state.dense_params, rows)
    # f32 at `highest` on both sides: the sums differ in order alone
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    np.testing.assert_allclose(pr, gr, atol=1e-6)
    got = {_path(kp): v for kp, v in jax.tree_util.tree_flatten_with_path(pd)[0]}
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=2e-6, err_msg=path)
    # the clean half's rows get a gradient through the noisy queries alone
    assert float(jnp.abs(pr[:, L:]).max()) > 1e-5


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


def test_two_step_train_many_matches_reference_follow():
    """`Trainer.jit_train_many` with the token table on the sparse path
    against the reference's dense Adagrad over the same two steps: losses,
    each leaf group's gradient and change sums, the table's, and the
    window's counters."""
    stacked = batches(2)
    model = make(CFG)
    tr, state, dense0 = seeded(CFG, model, one(stacked))
    rows0 = np.asarray(state.tables["token"].weights, np.float64)
    state, m = tr.jit_train_many()(state, stacked)
    ids = np.arange(CFG["vocab_size"], dtype=np.int32)
    masks = np.ones((3, ids.size), np.float32)
    out = jax.device_get(ref.follow(
        3, CFG, 1, ids, stacked["sparse"]["token"], stacked["label"], masks,
        weight=stacked["dense"]))
    np.testing.assert_allclose(m["loss"], out["losses"], rtol=2e-5)
    got = _group_sums(CFG, state, dense0)
    assert set(got) == set(out["dense"]) == set(ref.group_sizes(CFG))
    assert {"L0.attn", "L0.router", "L0.experts", "L1.attn", "head"} <= set(got)
    for g, v in out["dense"].items():
        np.testing.assert_allclose(got[g], [v[0], v[2]], rtol=2e-3, err_msg=g)
    ts = state.tables["token"]
    np.testing.assert_allclose(
        [np.sum(np.asarray(ts.slots["accum"], np.float64) - ACC0),
         np.sum(np.square(np.asarray(ts.weights, np.float64) - rows0))],
        [out["tables"]["token"][0], out["tables"]["token"][2]], rtol=2e-3)
    # the mask id's row is pulled by the noisy half and trained
    assert np.any(np.asarray(ts.weights)[CFG["mask_id"]] != rows0[CFG["mask_id"]])
    assert set(m["module"]) == set(dict(sd.SdarMoE.window_stats))
    assert int(m["module"]["moe.dropped"]) == 0
    np.testing.assert_allclose(m["module"]["moe.pairs_here"],
                               np.mean(out["pairs_held"]), rtol=1e-6)
    np.testing.assert_allclose(m["module"]["bd.masked_share"],
                               np.mean(stacked["dense"] > 0), rtol=1e-6)
    # this width is no shape of the kernel's: the plain body, every block
    assert float(m["module"]["bd.key_block_share"]) == 1.0


def test_shares_add_up_to_the_uncut_layer():
    """Guide section 4: with 16 experts in shares of 4, the four shares'
    outputs under the softmax router summed equal the uncut layer of the
    reference (no shared expert to count once)."""
    full = dict(CFG, num_experts=16, expert_offset=0, num_hidden_layers=1)
    dense = ref.init_dense(ref.make_keys(5, full), full)
    p = {k[len("layers_0/"):]: v for k, v in dense.items() if "/moe/" in k}
    kernel = dense["layers_0/router_kernel"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 64)), jnp.float32)
    xt = x.reshape(48, 64)
    routing = sd.softmax_top_k(xt, kernel, 3)
    want_routing = ref.route({"router_kernel": kernel}, xt, full)
    np.testing.assert_array_equal(routing[0], want_routing[0])
    np.testing.assert_allclose(routing[1], want_routing[1], atol=1e-7)
    want = ref.experts(p, xt, *routing, full, "f32", "")
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        layer = nh.MoE(64, 16, 3, 32, 0, 4, first, dtype=jnp.float32, gated=True)
        share = {k: v[first:first + 4] for k, v in p.items()}
        y, stats = jax.jit(layer.apply)(
            {"params": {k[len("moe/"):]: v for k, v in share.items()}}, x, routing)
        assert int(stats["dropped"]) == 0
        np.testing.assert_allclose(
            y.reshape(48, 64), ref.experts(share, xt, *routing, dict(
                full, num_experts=4, expert_offset=first), "f32", ""), atol=2e-5)
        total = total + y.reshape(48, 64)
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_corrupt_blocks_follows_its_law():
    """t_b ~ U[eps, 1] a block, a position masked with probability t_b: the
    masked share is E[t] = (1 + eps) / 2, a masked position weighs 1 / t_b
    (the same in a block) and so E[weight] = 1; unmasked positions keep
    their id and weigh 0."""
    B, S, block, eps = 64, 4096, 4, 1e-3
    x0 = jnp.asarray(np.random.default_rng(0).integers(0, 100, (B, S)), jnp.int32)
    xt, w = jax.jit(lambda k: sd.corrupt_blocks(k, x0, block, eps, 100))(
        jax.random.PRNGKey(7))
    xt, w, x0 = np.asarray(xt), np.asarray(w), np.asarray(x0)
    masked = xt == 100
    np.testing.assert_array_equal(masked, w > 0)
    np.testing.assert_array_equal(xt[~masked], x0[~masked])
    assert abs(masked.mean() - (1 + eps) / 2) < 0.006
    assert abs(w.mean() - 1.0) < 0.05
    assert w.max() <= 1 / eps and w[masked].min() >= 1.0
    blocks = w.reshape(B, S // block, block)
    top = blocks.max(axis=-1, keepdims=True)
    assert np.all((blocks == 0) | (blocks == top))
    with pytest.raises(ValueError, match="no whole number"):
        sd.corrupt_blocks(jax.random.PRNGKey(0), x0[:, :10], 4, eps, 100)


def test_a_noisy_block_reads_its_own_noise_and_the_clean_blocks_before_it():
    """The mask as the model sees it, one row moved at a time: a clean row of
    block j reaches the noisy half's logits of blocks after j alone (never
    its own block's: the answer is not leaked); a noisy row of block j
    reaches its own block's alone."""
    batch = one(batches(1))
    model = make(CFG)
    tr, state, _ = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    f = jax.jit(lambda r: model.module.apply(
        {"params": state.dense_params}, {"token": r}, batch["dense"])[0])
    base = np.asarray(f(rows))
    by_block = lambda d: np.abs(d).reshape(2, L // 4, 4, -1).max(axis=(0, 2, 3))
    clean = by_block(np.asarray(f(rows.at[:, L + 5].add(1.0))) - base)
    assert np.all(clean[:2] == 0) and np.all(clean[2:] > 1e-4), clean
    noisy = by_block(np.asarray(f(rows.at[:, 9].add(1.0))) - base)
    assert noisy[2] > 1e-4 and np.all(noisy[[0, 1, 3]] == 0), noisy


def test_diffusion_pairs_and_the_walks_by_hand():
    """The pair rule over a sequence of 2 x 8 positions in blocks of 4, and
    the kernel's walk over n blocks a half: n^2 + 2n of the n(2n + 1) a
    causal walk visits; 80 of 136 at the cell's 2L = 8192 in blocks of 512."""
    from openembedding_tpu.ops import flash_attention as fa
    pos = np.arange(16)
    seen = nh.diffusion_pairs(pos[:, None], pos[None, :], 8, 4)
    assert seen[1].tolist() == [1] * 4 + [0] * 12            # its own noise
    assert seen[5].tolist() == [0] * 4 + [1] * 4 + [1] * 4 + [0] * 4
    assert seen[9].tolist() == [0] * 8 + [1] * 4 + [0] * 4   # clean, block 0
    assert seen[13].tolist() == [0] * 8 + [1] * 8
    assert int(seen.sum()) == 8 * 8 + 8 * 4                  # L^2 + L B_k
    assert fa.diffusion_key_blocks(0, 4) == [4, 0]
    assert fa.diffusion_key_blocks(2, 4) == [4, 5, 6, 2]
    assert fa.diffusion_key_blocks(6, 4) == [4, 5, 6]
    assert fa.diffusion_key_block_share(8192, 512) == pytest.approx(80 / 136)
    assert fa.diffusion_block(8192, 128, 128, 32, 4, 4) == 512
    # halved until it divides the half; none under 128 rows or off the block
    assert fa.diffusion_block(512, 128, 128, 4, 4, 4) == 256
    assert fa.diffusion_block(256, 128, 128, 4, 4, 64) == 128
    assert fa.diffusion_block(256, 128, 128, 4, 4, 256) is None
    assert fa.diffusion_block(256, 128, 128, 4, 4, 3) is None
    assert fa.diffusion_block(384, 128, 128, 4, 4, 4) is None


def test_make_sdar_moe_round_trips_through_from_config():
    model = make(CFG, compute_dtype=jnp.bfloat16, working_pairs=512)
    again = models.from_config(model.config)
    assert again.config == model.config and again.module == model.module
    assert model.config["experts_held"] == 4 and model.config["num_experts"] == 16
    with pytest.raises(ValueError, match="are not among"):
        make(dict(CFG, expert_offset=14))
    batch = one(batches(1))
    tr, state, _ = seeded(CFG, make(CFG), batch)
    out = tr.jit_eval_step()(state, batch)
    assert out["logits"].shape == (2, L, 64) and np.isfinite(float(out["loss"]))
    with pytest.raises(ValueError, match="weights"):
        tr.jit_eval_step()(state, dict(batch, dense=batch["dense"][:, :4]))


def test_bf16_compute_stays_near_the_reference():
    """The configuration's precision (bf16 compute, f32 parameters) at the
    small size: near the f32 reference, not equal to it."""
    batch = one(batches(1))
    model = make(CFG, compute_dtype=jnp.bfloat16)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    got = jax.jit(lambda p, r: model.module.apply(
        {"params": p}, {"token": r}, batch["dense"])[0])(state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.forward(d, r, CFG)[0])(dense, rows)
    assert got.dtype == jnp.float32
    assert 1e-6 < float(jnp.mean(jnp.abs(got - want))) < 0.1


def test_every_stage_name_reaches_the_compiled_program():
    """At the configuration's bf16 compute (in f32 `bd.embed`'s cast of the
    rows is no op at all)."""
    from openembedding_tpu.utils import trace
    batch = one(batches(1))
    model = make(CFG, compute_dtype=jnp.bfloat16)
    tr, state, _ = seeded(CFG, model, batch)
    text = tr.jit_train_step().lower(state, batch).compile().as_text()
    inner = {p.rsplit("/", 1)[-1] for p in set(trace.scope_map(text).values())}
    assert {"bd.embed", "attn.qkv", "attn.qk_norm", "attn.rope", "attn.core",
            "attn.out", "moe.route", "moe.dispatch", "moe.experts",
            "moe.combine", "bd.head", "bd.loss"} <= inner
