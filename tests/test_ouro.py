"""Ouro (a dense decoder stack walked `total_ut_steps` times over shared
weights as ONE scanned body, an exit gate a pass, the expected loss over the
exits through one head, computed inside the walk) on the normal train path,
against the benchmark's plain reference (`benchmark/reference/ouro.py`:
float32, the passes a Python loop, full-softmax attention, each exit's logits
in turn, one dense Adagrad step a shared leaf) at small widths on seeded
random weights."""

import inspect
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import ouro as ref
from openembedding_tpu import models
from openembedding_tpu.model import TARGETS_KEY, Trainer
from openembedding_tpu.models import ouro as ou
from openembedding_tpu.utils import metrics

CFG = dict(hidden_size=64, num_hidden_layers=2, total_ut_steps=4,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           intermediate_size=96, rope_theta=1000000, rms_norm_eps=1e-6,
           exit_entropy_weight=0.1, vocab_size=64, table_init_stddev=1.0,
           learning_rate=0.05, adagrad_initial_accumulator=0.1,
           adagrad_epsilon=1e-7)
ACC0 = CFG["adagrad_initial_accumulator"]
CONFIG_FILE = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "ouro-2.6b-ut4.json")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def make(cfg, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 16)
    return models.make_ouro(
        vocabulary=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        total_ut_steps=cfg["total_ut_steps"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], intermediate_size=cfg["intermediate_size"],
        rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"],
        exit_entropy_weight=cfg["exit_entropy_weight"], **kw)


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


def batches(k, b=2, s=29, vocab=64, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, vocab, size=(k, b, s + 1)).astype(np.int32)
    return {"sparse": {"token": tok[:, :, :-1]}, "label": tok[:, :, 1:]}


def one(stacked, i=0):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def _apply(model, params, rows, y):
    """The module as `Trainer` calls it: the pulled rows, and the labels
    beside them -> (the last exit's logits, (T, B, S) losses, (T, B, S) gates)."""
    return model.module.apply({"params": params},
                              {"token": rows, TARGETS_KEY: {"label": y}})


# -- the exit distribution -------------------------------------------------------

def test_exit_distribution_sums_to_one_and_meets_its_closed_form():
    lam = jnp.asarray([[0.5, 0.2, 1.0, 0.0], [0.5, 0.9, 0.3, 0.0],
                       [0.5, 0.1, 0.7, 0.0], [0.5, 0.6, 0.2, 0.9]], jnp.float32)
    p = np.asarray(ou.exit_distribution(lam))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    # the issue's start: lam = 1/2 everywhere -> (1/2, 1/4, 1/8, 1/8)
    np.testing.assert_allclose(p[:, 0], [0.5, 0.25, 0.125, 0.125], atol=1e-7)
    # p_t = lam_t prod_{s<t} (1 - lam_s); p_T the rest; lam_T is not read
    np.testing.assert_allclose(
        p[:, 1], [0.2, 0.8 * 0.9, 0.8 * 0.1 * 0.1, 0.8 * 0.1 * 0.9], atol=1e-7)
    np.testing.assert_allclose(p[:, 2], [1.0, 0.0, 0.0, 0.0], atol=1e-7)  # all leave at once
    np.testing.assert_allclose(p[:, 3], [0.0, 0.0, 0.0, 1.0], atol=1e-7)  # none leaves early
    np.testing.assert_allclose(p, ref.exit_distribution(lam), atol=1e-7)
    # H((1/2, 1/4, 1/8, 1/8)) = 1.213 nats, ln 4 when flat
    h = lambda q: float(-np.sum(q * np.log(q)))
    assert h(p[:, 0]) == pytest.approx(1.2130, abs=1e-4) and h(np.full(4, 0.25)) == pytest.approx(math.log(4))


def test_expected_exit_loss_by_hand_and_its_weighted_mean():
    per_token = jnp.asarray([[[2.0, 4.0]], [[1.0, 3.0]]])      # (T=2, B=1, S=2)
    lam = jnp.asarray([[[0.25, 0.5]], [[0.9, 0.9]]])
    labels = jnp.zeros((1, 2), jnp.int32)
    loss, stats = ou.expected_exit_loss((None, per_token, lam), labels,
                                        entropy_weight=0.5)
    ent = [-(a * math.log(a) + (1 - a) * math.log(1 - a)) for a in (0.25, 0.5)]
    want = [0.25 * 2 + 0.75 * 1 - 0.5 * ent[0], 0.5 * 4 + 0.5 * 3 - 0.5 * ent[1]]
    assert float(loss) == pytest.approx(np.mean(want), rel=1e-6)
    assert float(stats["loop.exit_entropy"]) == pytest.approx(np.mean(ent), rel=1e-6)
    assert float(stats["loop.last_exit_mass"]) == pytest.approx((0.75 + 0.5) / 2)
    assert float(stats["loop.first_exit_loss"]) == 3.0 and float(stats["loop.last_exit_loss"]) == 2.0
    # a weight a position: the second token alone
    loss, _ = ou.expected_exit_loss((None, per_token, lam), labels,
                                    jnp.asarray([[0.0, 1.0]]), entropy_weight=0.5)
    assert float(loss) == pytest.approx(want[1], rel=1e-6)


# -- program against reference -----------------------------------------------------

@pytest.fixture(scope="module")
def first_step():
    """One batch through both sides, each compiled ONCE for the tests below:
    (batch, trainer, seeded state, the reference's leaves and rows, the
    program's (loss, outputs, gradients), the reference's)."""
    with jax.default_matmul_precision("highest"):
        batch = one(batches(1))
        model = make(CFG)
        tr, state, dense = seeded(CFG, model, batch)
        tokens, y = batch["sparse"]["token"], batch["label"]
        rows = state.tables["token"].weights[tokens]

        def prog(params, rows):
            out = _apply(model, params, rows, y)
            return model.loss_fn(out, y)[0], out

        def plain(d, rows):
            per_token, lam, logits = ref.forward(d, rows, y, CFG, keep_logits=True)
            return (ref.loss_of(per_token, lam, jnp.ones(y.shape), CFG)[0],
                    (per_token, lam, logits))

        grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))
        (lp, out), (pd, pr) = grad(prog)(state.dense_params, rows)
        (lr, want), (gd, gr) = grad(plain)(dense, rows)
        return (batch, tr, state, dense, rows, (lp, out, _flat(pd), pr),
                (lr, want, gd, gr))


def test_losses_gates_loss_and_every_gradient_leaf_match_reference(first_step):
    """Tolerances: float32 on both sides with matmuls at highest; the two
    differ in summation order alone (blockwise against full softmax, a
    scanned against a looped walk): 1e-5 of logits of size 3, 5e-6 of
    gradients of size 1e-2."""
    _, _, _, _, _, (lp, (logits, per_token, lam), got, pr), \
        (lr, (want_per, want_lam, want_logits), gd, gr) = first_step
    np.testing.assert_allclose(per_token, want_per, atol=1e-5)
    np.testing.assert_allclose(lam, want_lam, atol=2e-6)
    np.testing.assert_allclose(logits, want_logits[-1], atol=3e-5)
    assert abs(float(lp) - float(lr)) < 1e-5
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=5e-6, err_msg=path)
    np.testing.assert_allclose(pr, gr, atol=5e-6)
    # the gate's last value is not read: but its kernel has a gradient from the first three
    assert np.any(got["exit_gate_kernel"] != 0) and np.any(got["exit_gate_bias"] != 0)


@pytest.mark.parametrize("t", [1, 2, 3, 4])
def test_logits_of_every_exit_match_reference(first_step, t):
    """Exit t's logits are the LAST exit's of a walk of t passes over the
    same leaves (the weights are shared, so a shorter walk has the same
    parameter tree): every exit of the scanned walk against the reference's
    looped one."""
    batch, _, state, _, rows, _, (_, (_, _, want_logits), _, _) = first_step
    model = make(dict(CFG, total_ut_steps=t))
    logits, per_token, lam = jax.jit(
        lambda p, r: _apply(model, p, r, batch["label"]))(state.dense_params, rows)
    assert per_token.shape == lam.shape == (t,) + batch["label"].shape
    np.testing.assert_allclose(logits, want_logits[t - 1], atol=3e-5)


def test_one_pass_is_a_plain_decoder(first_step):
    """`total_ut_steps` 1: p_1 = 1, the loss is the first exit's mean
    cross-entropy, the entropy 0, and the gate has no gradient."""
    batch, _, state, _, rows, _, (_, (want_per, _, _), _, _) = first_step
    y = batch["label"]
    model = make(dict(CFG, total_ut_steps=1))

    def prog(params):
        out = _apply(model, params, rows, y)
        loss, stats = model.loss_fn(out, y)
        return loss, (stats, out)

    (loss, (stats, (_, per_token, lam))), g = jax.jit(
        jax.value_and_grad(prog, has_aux=True))(state.dense_params)
    np.testing.assert_allclose(ou.exit_distribution(lam), 1.0)
    assert float(loss) == pytest.approx(float(jnp.mean(want_per[0])), abs=1e-5)
    assert float(stats["loop.exit_entropy"]) == 0.0 and float(stats["loop.last_exit_mass"]) == 1.0
    assert not np.any(g["exit_gate_kernel"]) and not np.any(g["exit_gate_bias"])
    assert np.any(g["lm_head"])


def test_a_shared_leafs_gradient_is_the_sum_over_four_untied_copies(first_step):
    """The reference's walk given FOUR copies of the stack, one a pass (the
    same values, separate leaves): the program's gradient of a shared leaf
    is the sum of the four copies' gradients, leaf by leaf; and no single
    copy's (the last use's least of all) is the whole."""
    batch, _, _, dense, rows, (_, _, got, _), _ = first_step
    y = batch["label"]
    w = jnp.ones(y.shape, jnp.float32)
    T, eps = CFG["total_ut_steps"], CFG["rms_norm_eps"]

    def untied(copies):
        h, per_token, lams = rows, [], []
        for p in copies:
            x = h
            for i in range(CFG["num_hidden_layers"]):
                x = ref.decoder_layer(ref._sub(p, f"walk/layers_{i}/"), x, CFG, "f32", "")
            h = ref._rms(x, p["norm_f_scale"], eps)
            z = jnp.einsum("bsd,dv->bsv", h, p["lm_head"])
            per_token.append(jax.nn.logsumexp(z, axis=-1)
                             - jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0])
            lams.append(jax.nn.sigmoid(h @ p["exit_gate_kernel"] + p["exit_gate_bias"][0]))
        return ref.loss_of(jnp.stack(per_token), jnp.stack(lams), w, CFG)[0]

    grads = jax.jit(jax.grad(untied))([dict(dense) for _ in range(T)])
    for path in dense:
        total = sum(g[path] for g in grads)
        np.testing.assert_allclose(got[path], total, atol=5e-6, err_msg=path)
    for path in ("walk/layers_0/mlp_up", "walk/layers_1/attn/q_proj/kernel", "lm_head"):
        for g in grads:
            assert float(jnp.max(jnp.abs(got[path] - g[path]))) > 1e-4, path


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
    """Tolerances: losses 2e-5 relative (float32, summation order); the
    groups' sums of squares 2e-3 relative: `acc - 0.1` is a difference of
    float32 numbers near 0.1, whose ulp is 7.45e-9 against sums of 1e-4."""
    metrics.reset_all()
    stacked = batches(3)
    model = make(CFG)
    tr, state, dense0 = seeded(CFG, model, one(stacked))
    rows0 = np.asarray(state.tables["token"].weights, np.float64)
    many = tr.jit_train_many()
    # every stage name reaches the lowered scan
    text = many.lower(state, stacked).as_text(debug_info=True)
    for name in ("attn.qkv", "attn.rope", "attn.core", "attn.out", "mlp.dense",
                 "loop.norm", "lm.head", "lm.loss", "loop.gate"):
        assert name in text, name
    state, m = many(state, stacked)
    ids = np.arange(CFG["vocab_size"], dtype=np.int32)
    masks = np.ones((3, ids.size), np.float32)
    out = jax.device_get(ref.follow(
        3, CFG, 1, ids, stacked["sparse"]["token"], stacked["label"], masks))
    np.testing.assert_allclose(m["loss"], out["losses"], rtol=2e-5)
    assert float(m["loss"][0]) > float(m["loss"][2])
    got = _group_sums(CFG, state, dense0)
    assert set(got) == set(out["dense"]) == set(ref.group_sizes(CFG)) == \
        {"head", "gate", "norm_f", "L0.attn", "L0.mlp", "L1.attn", "L1.mlp"}
    for g, v in out["dense"].items():
        np.testing.assert_allclose(got[g], [v[0], v[2]], rtol=2e-3, err_msg=g)
    ts = state.tables["token"]
    np.testing.assert_allclose(
        [np.sum(np.asarray(ts.slots["accum"], np.float64) - ACC0),
         np.sum(np.square(np.asarray(ts.weights, np.float64) - rows0))],
        [out["tables"]["token"][0], out["tables"]["token"][2]], rtol=2e-3)
    # the window's stats are the reference's exit terms, averaged over its steps
    assert set(m["module"]) == set(dict(ou.Ouro.window_stats))
    np.testing.assert_allclose(
        [m["module"][k] for k in ("loop.exit_entropy", "loop.last_exit_mass",
                                  "loop.first_exit_loss", "loop.last_exit_loss")],
        np.mean(out["exit_terms"], axis=0), rtol=2e-5)
    assert out["pairs_held"].size == 0
    tr.record_window_stats(m)
    report = metrics.report()
    assert report["loop.exit_entropy"] == pytest.approx(float(m["module"]["loop.exit_entropy"]))
    assert report["loop.last_exit_mass"] == pytest.approx(float(m["module"]["loop.last_exit_mass"]))
    # ONE scanned walk a trace of the module: `init` and the scan
    assert report['loop.passes{path="scan"}'] == 2


def _count_eqns(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for inner in _inner_jaxprs(eqn):
            n += _count_eqns(inner)
    return n


def _inner_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _walk_bodies(jaxpr, length):
    """Equation counts of the bodies of every scan over `length` steps."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            out.append(_count_eqns(eqn.params["jaxpr"].jaxpr))
        for inner in _inner_jaxprs(eqn):
            out += _walk_bodies(inner, length)
    return out


def test_the_traced_step_holds_one_walk_body_whatever_the_passes():
    """The walk is a scan over the passes: the step's jaxpr holds two scans
    of that length (the walk and its transpose) whose bodies have the same
    number of equations at 3 and at 5 passes, and the whole step differs by
    the exit distribution's T - 1 elementwise products alone (an unrolled
    walk's would grow by two layer bodies a pass); one
    `loop.passes{path="scan"}` a trace, nothing counted as unrolled."""
    batch = one(batches(1))
    sizes, bodies = {}, {}
    for t in (3, 5):
        metrics.reset_all()
        model = make(dict(CFG, total_ut_steps=t))
        tr = Trainer(model, embed.Adagrad(learning_rate=0.05))
        state = jax.eval_shape(tr.init, batch)
        jaxpr = jax.make_jaxpr(tr.train_step)(state, batch).jaxpr
        sizes[t], bodies[t] = _count_eqns(jaxpr), sorted(_walk_bodies(jaxpr, t))
        report = metrics.report()
        assert report['loop.passes{path="scan"}'] == 2        # init, the step
        assert 'loop.passes{path="unrolled"}' not in report
    assert bodies[3] == bodies[5] and len(bodies[5]) == 2 and min(bodies[5]) > 100
    # 21 equations a pass here, of the loss; the forward body alone has 329
    assert 0 < sizes[5] - sizes[3] < min(bodies[5]) // 4


def test_bf16_compute_stays_near_the_reference(first_step):
    """The cell's own precision (bf16 compute on f32 parameters, norms,
    angles, softmax, gate, logits and loss in f32) against the float32
    reference: bf16 keeps 8 bits, two layers walked four times: 2% of the
    loss, a quarter of each gradient leaf's norm (the gate's bias apart)."""
    batch, _, state, _, rows, _, (lr, (want_per, want_lam, _), gd, _) = first_step
    y = batch["label"]
    model = make(CFG, compute_dtype=jnp.bfloat16)

    def prog(params):
        out = _apply(model, params, rows, y)
        return model.loss_fn(out, y)[0], out

    (loss, (logits, per_token, lam)), g = jax.jit(
        jax.value_and_grad(prog, has_aux=True))(state.dense_params)
    assert logits.dtype == per_token.dtype == lam.dtype == jnp.float32
    assert abs(float(loss) - float(lr)) < 0.02 * float(lr)
    np.testing.assert_allclose(per_token, want_per, atol=0.25)
    np.testing.assert_allclose(lam, want_lam, atol=0.03)
    for path, got in _flat(g).items():
        if path == "exit_gate_bias":
            continue  # one number, a sum of signed terms a token that cancel: no relative size
        norm = float(jnp.linalg.norm(gd[path]))
        assert float(jnp.linalg.norm(got - gd[path])) < 0.25 * norm + 1e-6, path


# -- the published file -------------------------------------------------------------

def test_configuration_keeps_every_published_number_and_counts_its_parameters():
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows if r["name"] == "Ouro-2.6B")
        assert cfg["source"] == pub["source_url"]
        for key, value in pub["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    # what was cut is the depth alone: no width, head count, vocabulary row or pass
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["rope_theta"], cfg["rms_norm_eps"]) == \
        (2048, 16, 16, 128, 5632, 49152, 4, 1000000, 1e-6)
    assert 4 <= cfg["num_hidden_layers"] == 8 and cfg["published"]["num_hidden_layers"] == 48
    assert cfg["tie_word_embeddings"] is False and len(cfg["layer_types"]) == 48
    for line in ("sandwich_norms", "loop_norm", "exit_gate", "exit_distribution",
                 "exit_entropy_weight", "exit_gate_start", "early_exit_threshold", "bias",
                 "rotary", "optimizer", "initial_weights", "precision", "attention_block"):
        assert line in cfg["assumed"], line
    sizes = ref.group_sizes(cfg)
    assert sizes["L0.attn"] == 4 * 2048 * 2048 + 2 * 2048
    assert sizes["L7.mlp"] == 3 * 2048 * 5632 + 2 * 2048
    assert sizes["head"] == 2048 * 49152 and sizes["gate"] == 2049 and sizes["norm_f"] == 2048
    table = ref.tables_of(cfg)["token"]["width"] * cfg["vocab_size"]
    assert sum(sizes.values()) + table == 612_438_017
    names = inspect.signature(models.make_ouro).parameters
    assert set(cfg["make_keywords"].values()) <= set(names)


def test_published_file_builds_through_from_config_and_round_trips():
    """The published file's keys through `make_keywords` -> `make_ouro` ->
    `EmbeddingModel.config` -> `models.from_config`: the same module (shapes
    only: nothing of this size is made here)."""
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    model = models.make_ouro(
        compute_dtype=jnp.dtype(cfg["tower_dtype"]),
        **{kw: cfg[key] for key, kw in cfg["make_keywords"].items()})
    again = models.from_config(model.config)
    assert again.module == model.module and again.config == model.config
    assert model.module.total_ut_steps == 4 and model.module.num_layers == 8
    tr = Trainer(model, embed.Adagrad(learning_rate=cfg["learning_rate"]))
    sample = {"sparse": {"token": np.zeros((1, 128), np.int32)},
              "label": np.zeros((1, 128), np.int32)}
    state = jax.eval_shape(tr.init, sample)
    shapes = {p: v.shape for p, v in _flat(state.dense_params).items()}
    assert shapes == {p: tuple(s) for p, s, _ in ref.dense_leaves(cfg)}
    assert state.tables["token"].weights.shape == (49152, 2048)
    with pytest.raises(ValueError, match="at least once"):
        models.make_ouro(64, 64, 2, total_ut_steps=0, num_attention_heads=4,
                         num_key_value_heads=4, head_dim=16, intermediate_size=96)
