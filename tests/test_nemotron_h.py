"""NemotronH (hybrid Mamba-2 / attention / routed experts) on the normal train
path, against the benchmark's plain reference (`benchmark/reference/
nemotron_h.py`: float32, the recurrence step by step, full-softmax attention,
a loop over the experts held) at small widths on seeded random weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import nemotron_h as ref
from openembedding_tpu import models
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import nemotron_h as nh
from openembedding_tpu.parallel.sequence import reference_attention

CFG = dict(hidden_size=32, hybrid_override_pattern="ME*M", num_hidden_layers=3,
           mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
           conv_kernel=4, chunk_size=8, num_attention_heads=4,
           num_key_value_heads=2, head_dim=8, n_routed_experts=4,
           router_width=16, expert_offset=4, num_experts_per_tok=3,
           moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
           routed_scaling_factor=2.5, norm_topk_prob=True,
           layer_norm_epsilon=1e-5, vocab_size=64, time_step_min=0.001,
           time_step_max=0.1, table_init_stddev=1.0, learning_rate=0.05,
           adagrad_initial_accumulator=0.1, adagrad_epsilon=1e-7)
ACC0 = CFG["adagrad_initial_accumulator"]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def make(cfg, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 8)
    return models.make_nemotron_h(
        vocabulary=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=ref.pattern_of(cfg),
        **{k: cfg[k] for k in (
            "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
            "conv_kernel", "chunk_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_experts_per_tok",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "expert_offset", "routed_scaling_factor", "norm_topk_prob")},
        n_routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"], eps=cfg["layer_norm_epsilon"],
        **kw)


def _path(kp):
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def _adagrad():
    return embed.Adagrad(learning_rate=CFG["learning_rate"],
                         initial_accumulator_value=ACC0,
                         epsilon=CFG["adagrad_epsilon"])


def seeded(cfg, model, batch, seed=3):
    """(trainer, state with every leaf from the benchmark's hash draw, the
    reference's flat {path: leaf})."""
    tr = Trainer(model, _adagrad())
    state = jax.jit(tr.init)(batch)  # one compile, not an eager forward pass op by op
    keys = ref.make_keys(seed, cfg)
    dense = ref.init_dense(keys, cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(state.dense_params)
    assert {_path(kp): v.shape for kp, v in flat} == \
        {p: tuple(s) for p, s, _ in ref.dense_leaves(cfg)}
    params = jax.tree_util.tree_unflatten(
        treedef, [jnp.array(dense[_path(kp)]) for kp, _ in flat])  # copies: the state is donated
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


def test_logits_loss_and_every_gradient_leaf_match_reference():
    batch = one(batches(1))
    model = make(CFG)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    y, w = batch["label"], jnp.ones(batch["label"].shape)

    def ref_loss(dense, rows):
        return ref.xent(ref.logits_fn(dense, rows, CFG), y, w)

    def prog_loss(params, rows):
        return nh.softmax_xent(
            model.module.apply({"params": params}, {"token": rows}), y)

    logits = jax.jit(lambda p, r: model.module.apply({"params": p}, {"token": r}))(
        state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.logits_fn(d, r, CFG))(dense, rows)
    np.testing.assert_allclose(logits, want, atol=2e-5)
    lr, (gd, gr) = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1)))(dense, rows)
    lp, (pd, pr) = jax.jit(jax.value_and_grad(prog_loss, argnums=(0, 1)))(
        state.dense_params, rows)
    assert abs(float(lp) - float(lr)) < 1e-5
    np.testing.assert_allclose(pr, gr, atol=1e-6)
    got = {_path(kp): v for kp, v in
           jax.tree_util.tree_flatten_with_path(pd)[0]}
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=2e-6, err_msg=path)
    # the correction bias is a buffer: no gradient reaches it
    assert not np.any(got["layers_1/mixer/router_correction_bias"])


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
    for g, v in out["dense"].items():
        np.testing.assert_allclose(got[g], [v[0], v[2]], rtol=2e-3, err_msg=g)
    ts = state.tables["token"]
    np.testing.assert_allclose(
        [np.sum(np.asarray(ts.slots["accum"], np.float64) - ACC0),
         np.sum(np.square(np.asarray(ts.weights, np.float64) - rows0))],
        [out["tables"]["token"][0], out["tables"]["token"][2]], rtol=2e-3)
    assert set(m["module"]) == set(dict(nh.NemotronH.window_stats))
    assert int(m["module"]["moe.dropped"]) == 0


@pytest.mark.parametrize("length", [40, 29, 8, 5])
def test_chunked_ssd_equals_the_recurrence(length):
    rng = np.random.default_rng(length)
    bt, h, p, g, n = 2, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(bt, length, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(bt, length, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.normal(size=(h,)), jnp.float32))
    b, c = (jnp.asarray(rng.normal(size=(bt, length, g, n)), jnp.float32)
            for _ in range(2))
    def both(f):
        def loss(*t):
            y = f(*t)
            return jnp.sum(jnp.sin(y)), y
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True))

    (_, want), gw = both(ref.recurrence)(x, dt, a, b, c)
    (_, got), gg = both(lambda *t: nh.ssd_chunked(*t, chunk=8))(x, dt, a, b, c)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
    for u, v in zip(gg, gw):
        np.testing.assert_allclose(u, v, atol=5e-4, rtol=1e-3)
    # a fault the benchmark plants: the state zeroed at every chunk boundary
    if length > 8:
        reset = jax.jit(lambda *t: ref.recurrence(*t, reset_every=8))(x, dt, a, b, c)
        assert np.max(np.abs(reset - want)) > 1e-2


# the chunked scan's TPU kernel (`ops/ssd_scan.py`) traced as on a TPU, under
# the Pallas interpreter, against the plain body: (chunk, groups, heads,
# head width, length, where documents begin); a padded tail where the length
# is no multiple of the chunk, starts on a chunk boundary and inside a chunk;
# two 64-wide heads share a lane tile, a 128-wide head fills one
KERNEL_CASES = {
    "q128_one_group_padded_tail": (128, 1, 8, 64, 300, None),
    "q256_two_groups": (256, 2, 16, 64, 512, None),
    "q128_two_groups_starts_on_and_inside_chunks_padded_tail":
        (128, 2, 16, 64, 300, (0, 50, 128, 200)),
    "q256_one_group_starts_on_and_inside_a_chunk_padded_tail":
        (256, 1, 8, 64, 600, (0, 100, 256, 300)),
    "q128_one_group_128_wide_heads_starts_inside_a_chunk":
        (128, 1, 8, 128, 384, (0, 70, 300)),
}


@pytest.mark.parametrize("case", KERNEL_CASES.values(), ids=KERNEL_CASES.keys())
def test_scan_kernel_equals_the_plain_body(monkeypatch, case):
    from openembedding_tpu.ops import ssd_scan
    from openembedding_tpu.utils import metrics
    chunk, groups, heads, width, length, at = case
    rng = np.random.default_rng(chunk + length)
    x = jnp.asarray(rng.normal(size=(1, length, heads, width)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(1, length, heads)), jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(heads,)), jnp.float32))
    b, c = (jnp.asarray(rng.normal(size=(1, length, groups, 128)) / 8,
                        jnp.float32) for _ in range(2))
    starts = None
    if at is not None:
        starts = np.zeros((1, length), np.int32)
        starts[0, list(at)] = 1

    def run():
        def loss(*t):
            y = nh.ssd_chunked(*t, chunk=chunk, starts=starts)
            return jnp.sum(jnp.sin(y)), y
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True))(
            x, dt, a, b, c)
    (_, want), gw = run()
    kernel = ssd_scan.scan
    monkeypatch.setattr(ssd_scan, "scan",
                        lambda *args: kernel(*args, interpret=True))
    monkeypatch.setattr(nh, "_traces_for_tpu", lambda: True)
    before = metrics.report().get('ssm.scans{path="fused"}', 0)
    (_, got), gg = run()
    assert metrics.report()['ssm.scans{path="fused"}'] == before + 1
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for u, v in zip(gg, gw):
        np.testing.assert_allclose(u, v, atol=1e-4 * float(np.max(np.abs(v))),
                                   rtol=1e-5)


def test_chunks_the_kernel_refuses_count_as_chunked():
    from openembedding_tpu.ops import ssd_scan
    from openembedding_tpu.utils import metrics
    assert ssd_scan.takes(256, 64, 1, 64, 128)          # Granite's layer
    assert ssd_scan.takes(128, 64, 8, 64, 128)          # Nemotron's
    assert not ssd_scan.takes(8, 4, 2, 8, 16)           # this file's
    assert not ssd_scan.takes(128, 12, 1, 64, 128)      # 12 heads a group
    assert not ssd_scan.takes(128, 8, 1, 32, 128)       # 32-wide heads
    assert not ssd_scan.takes(128, 8, 1, 64, 64)        # a state of 64
    assert not ssd_scan.takes(128, 8, 1, 64, 256)       # a state of 256
    x = jnp.ones((1, 16, 4, 8), jnp.float32)
    before = metrics.report().get('ssm.scans{path="chunked"}', 0)
    jax.eval_shape(lambda *t: nh.ssd_chunked(*t, chunk=8), x,
                   jnp.ones((1, 16, 4)), -jnp.ones((4,)),
                   jnp.ones((1, 16, 2, 16)), jnp.ones((1, 16, 2, 16)))
    assert metrics.report()['ssm.scans{path="chunked"}'] == before + 1


@pytest.mark.parametrize("seq,block", [(32, 8), (21, 8), (16, 16), (7, 16)])
def test_blockwise_attention_equals_reference_attention(seq, block):
    rng = np.random.default_rng(seq)
    q = jnp.asarray(rng.normal(size=(2, seq, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, seq, 2, 8)), jnp.float32)
            for _ in range(2))
    want = reference_attention(q, jnp.repeat(k, 2, axis=2),
                               jnp.repeat(v, 2, axis=2), causal=True)
    got = jax.jit(lambda *t: nh.blockwise_causal_attention(*t, block=block))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-6)


def _moe_layer(cfg, held, offset, **kw):
    return nh.MoE(cfg["hidden_size"], cfg["router_width"],
                  cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
                  cfg["moe_shared_expert_intermediate_size"], held, offset,
                  cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
                  dtype=jnp.float32, **kw)


def _moe_params(cfg, seed=5):
    """The uncut layer's leaves (all `router_width` experts), reference names."""
    full = dict(cfg, n_routed_experts=cfg["router_width"], expert_offset=0,
                hybrid_override_pattern="E", num_hidden_layers=1)
    dense = ref.init_dense(ref.make_keys(seed, full), full)
    return full, {k.split("mixer/")[1]: v for k, v in dense.items()
                  if "mixer/" in k}


def _share(p, first, count):
    return dict(p, experts_up=p["experts_up"][first:first + count],
                experts_down=p["experts_down"][first:first + count])


def test_shares_add_up_to_the_uncut_layer():
    """Guide section 4: with 16 experts in shares of 4, the routed parts of
    all four shares plus the shared expert counted once equal the uncut
    layer of the reference."""
    full, p = _moe_params(CFG)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)), jnp.float32)
    want = jax.jit(lambda p, x: ref.experts(p, x, full, "f32", ""))(p, x)
    shared = jax.jit(lambda p, x: ref.experts(p, x, full, "f32", "no_routed"))(p, x)
    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = dict(full, n_routed_experts=4, expert_offset=first)
        y, stats = jax.jit(_moe_layer(CFG, 4, first).apply)({"params": _share(p, first, 4)}, x)
        assert int(stats["dropped"]) == 0
        # ... and each share is the reference's for the same share
        np.testing.assert_allclose(
            y, jax.jit(lambda p, x: ref.experts(p, x, share, "f32", ""))(_share(p, first, 4), x),
            atol=2e-5)
        total = total + (y - shared)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


@pytest.mark.parametrize("gated,norm,scaling", [
    (False, True, 1.0), (True, True, 2.5), (True, False, 1.0)],
    ids=["relu2_normalised", "swiglu_scaled", "swiglu_raw_scores"])
def test_built_in_router_and_shared_expert_are_bit_for_bit_what_they_were(
        gated, norm, scaling):
    """The opening in `MoE` (routing handed in by the caller, `shared_width` 0
    = no shared expert; `zaya1.py`) leaves the default path as it was: the
    layer with its built-in router and its shared expert equals, BIT FOR BIT,
    the same layer handed the routing of the router's formula written out
    here as it stood before the opening (f32 linear map at highest, sigmoid,
    the top k of score + bias, chosen scores over their sum, scaled), plus
    the shared expert's formula."""
    cfg = dict(CFG, norm_topk_prob=norm, routed_scaling_factor=scaling)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 24, 32)), jnp.float32)
    layer = _moe_layer(cfg, 4, 4, gated=gated)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    params = dict(params, router_correction_bias=jnp.asarray(
        np.random.default_rng(4).normal(size=(16,)) * 0.1, jnp.float32))
    got, stats = jax.jit(layer.apply)({"params": params}, x)

    def as_it_was(p, x):
        xt = x.reshape(-1, 32)
        logits = jnp.dot(xt.astype(jnp.float32), p["router_kernel"],
                         precision=jax.lax.Precision.HIGHEST)
        score = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(
            score + jax.lax.stop_gradient(p["router_correction_bias"]),
            cfg["num_experts_per_tok"])
        gate = jnp.take_along_axis(score, chosen, axis=-1)
        if norm:
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
        bare = nh.MoE(32, 16, cfg["num_experts_per_tok"],
                      cfg["moe_intermediate_size"], 0, 4, 4,
                      dtype=jnp.float32, gated=gated)
        held = {k: v for k, v in p.items() if k.startswith("experts_")}
        routed, s = bare.apply({"params": held}, x, (chosen, gate * scaling))
        shared = nh._expert_mlp(xt, p.get("shared_gate"), p["shared_up"],
                                p["shared_down"], jnp.float32)
        return (routed.reshape(-1, 32) + shared).reshape(x.shape), s

    want, stats_was = jax.jit(as_it_was)(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert {k: float(v) for k, v in stats.items()} == \
        {k: float(v) for k, v in stats_was.items()}
    assert float(stats["pairs_here"]) > 0 and ("shared_gate" in params) == gated


@pytest.mark.parametrize("working_pairs", [0, 16, 1 << 20])
def test_no_token_dropped_when_every_token_chooses_held_experts(working_pairs):
    """A router bias planted so that every token's choices are all held here:
    more pairs than the working size, so the step runs full size
    (`moe.full_steps` > 0), nothing is dropped, and it equals the reference."""
    cfg = dict(CFG, hybrid_override_pattern="EM", num_hidden_layers=2)
    model = make(cfg, working_pairs=working_pairs)
    stacked = batches(2)
    tr, state, dense0 = seeded(cfg, model, one(stacked))
    bias = np.zeros(16, np.float32)
    bias[4:8] = 10.0  # experts [4, 8) are the held ones; top 3 of them a token
    params = jax.tree_util.tree_map(lambda x: x, state.dense_params)
    params["layers_0"]["mixer"]["router_correction_bias"] = jnp.asarray(bias)
    state = state.replace(dense_params=params)
    state, m = tr.jit_train_many()(state, stacked)
    pairs = 2 * 21 * 3
    assert float(m["module"]["moe.pairs_here"]) == pairs
    assert int(m["module"]["moe.dropped"]) == 0
    # working size 0 = 1.5 x the balanced load (256 at least): fits; 16: not
    assert int(m["module"]["moe.full_steps"]) == (2 if working_pairs == 16 else 0)
    want = _follow_with(cfg, stacked, {"layers_0/mixer/router_correction_bias": bias})
    np.testing.assert_allclose(m["loss"], want, rtol=2e-5)
    tr.record_window_stats(m)
    from openembedding_tpu.utils import metrics
    assert metrics.report()["moe.dropped"] == 0


def _follow_with(cfg, stacked, planted, seed=3):
    """Reference losses of the stacked steps with some leaves replaced."""
    dense = dict(ref.init_dense(ref.make_keys(seed, cfg), cfg))
    dense.update({k: jnp.asarray(v) for k, v in planted.items()})
    rows = ref.init_rows(ref.make_keys(seed, cfg), cfg, jnp.arange(cfg["vocab_size"]))["token"]
    acc = {k: jnp.full_like(v, ACC0) for k, v in dense.items()}
    racc = jnp.full_like(rows, ACC0)
    @jax.jit
    def step(dense, acc, rows, racc, ix, y):
        def loss_fn(dense, pulled):
            return ref.xent(ref.logits_fn(dense, pulled, cfg), y, jnp.ones(y.shape))

        loss, (gd, gr) = jax.value_and_grad(loss_fn, (0, 1))(dense, rows[ix])
        new = {k: ref._adagrad(dense[k], acc[k], gd[k], cfg) for k in dense}
        rows, racc = ref._adagrad(rows, racc, jnp.zeros_like(rows).at[ix].add(gr), cfg)
        return {k: v[0] for k, v in new.items()}, {k: v[1] for k, v in new.items()}, rows, racc, loss

    losses = []
    for i in range(stacked["label"].shape[0]):
        dense, acc, rows, racc, loss = step(dense, acc, rows, racc, stacked["sparse"]["token"][i],
                                            stacked["label"][i])
        losses.append(float(loss))
    return losses


def test_make_nemotron_h_round_trips_through_from_config():
    model = make(CFG, compute_dtype=jnp.bfloat16, working_pairs=512)
    again = models.from_config(model.config)
    assert again.config == model.config
    assert again.module == model.module
    assert again.specs["token"].output_dim == CFG["hidden_size"]
    assert model.config["experts_held"] == 4 and model.config["n_routed_experts"] == 16
    with pytest.raises(ValueError, match="are not among"):
        make(dict(CFG, expert_offset=14))


def test_bf16_compute_stays_near_the_reference():
    """The configuration's precision (bf16 compute, f32 parameters) at the
    small size: near the f32 reference, not equal to it."""
    batch = one(batches(1))
    model = make(CFG, compute_dtype=jnp.bfloat16)
    tr, state, dense = seeded(CFG, model, batch)
    rows = state.tables["token"].weights[batch["sparse"]["token"]]
    logits = jax.jit(lambda p, r: model.module.apply({"params": p}, {"token": r}))(
        state.dense_params, rows)
    want = jax.jit(lambda d, r: ref.logits_fn(d, r, CFG))(dense, rows)
    assert logits.dtype == jnp.float32
    assert 1e-6 < float(jnp.mean(jnp.abs(logits - want))) < 0.1
