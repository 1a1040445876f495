"""Granite 4.0-H (nine Mamba-2 layers in ten, a SwiGLU after every mixer, four
muP scalars, a tied head) on the normal train path over PACKED batches, against
the benchmark's plain reference (`benchmark/reference/granite_hybrid.py`:
float32, the recurrence one position at a time, the convolution tap by tap,
full-softmax attention under the document mask, one dense Adagrad step on the
tied table) at small widths on seeded random weights. And the packing itself:
in every function that takes `starts`, and in the module, each document of a
packed sequence gives what it gives alone."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from benchmark.reference import granite_hybrid as ref
from openembedding_tpu import models
from openembedding_tpu.model import TABLES_KEY, Trainer
from openembedding_tpu.models import granite_hybrid as gh
from openembedding_tpu.models import nemotron_h as nh
from openembedding_tpu.utils import metrics

S, CHUNK = 40, 8
CFG = dict(hidden_size=64, num_hidden_layers=4,
           layer_types=["mamba", "attention", "mamba", "mamba", "attention"],
           mamba_n_heads=4, mamba_d_head=32, mamba_n_groups=1, mamba_d_state=16,
           mamba_d_conv=4, mamba_chunk_size=CHUNK, num_attention_heads=4,
           num_key_value_heads=2, shared_intermediate_size=96,
           attention_multiplier=0.0625, embedding_multiplier=12,
           residual_multiplier=0.22, logits_scaling=8, rms_norm_eps=1e-5,
           vocab_size=64, table_init_stddev=0.1, time_step_min=0.001,
           time_step_max=0.1, learning_rate=0.05,
           adagrad_initial_accumulator=0.1, adagrad_epsilon=1e-7)
TABLE = ref.TABLE
# where documents begin, of S = 40 positions in chunks of 8
PACKINGS = {
    "on_chunk_edges": (0, 8, 24),           # 8..24 is longer than a chunk
    "off_chunk_edges": (0, 3, 13, 30),      # 13..30 crosses two edges
    "several_in_a_chunk": (0, 9, 11, 14, 33),
    "one_position_documents": (0, 1, 2, 17, 18, 39),
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def starts_of(at, batch=1):
    out = np.zeros((batch, S), np.int32)
    out[:, list(at)] = 1
    return out


def spans(at):
    return list(zip(at, list(at[1:]) + [S]))


def make(cfg=CFG, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attention_block", 16)
    names = ("hidden_size", "num_hidden_layers", "layer_types", "mamba_n_heads",
             "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
             "mamba_chunk_size", "num_attention_heads", "num_key_value_heads",
             "shared_intermediate_size", "attention_multiplier",
             "embedding_multiplier", "residual_multiplier", "logits_scaling",
             "table_init_stddev")
    args = {k: cfg[k] for k in names}
    args.update(kw)
    return models.make_granite_hybrid(vocabulary=cfg["vocab_size"],
                                      eps=cfg["rms_norm_eps"], **args)


def _path(kp):
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def _flat(tree):
    return {_path(kp): v for kp, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def batches(k, at, b=2, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], size=(k, b, S + 1)).astype(np.int32)
    return {"sparse": {"token": tok[:, :, :-1]},
            "dense": np.broadcast_to(starts_of(at, b), (k, b, S)).copy(),
            "label": tok[:, :, 1:]}


def one(stacked, i=0):
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


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


def _prog_logits(model, params, tokens, starts=None):
    """The module as `Trainer` calls it: rows looked up from the table, the
    table itself beside them, the batch's `dense` entry second."""
    table = params["__embeddings__"]["token"]
    return model.module.apply(
        {"params": params},
        {"token": table[tokens], TABLES_KEY: {"token": table}}, starts)


# -- each document of a packed sequence gives what it gives alone ----------------
#
# "Alone" is computed at the SAME shapes (one compile a function): every one of
# these functions is causal, so a document moved to position 0 of a sequence
# whose tail is whatever followed it gives, over its own length, what it gives
# as a sequence of its own.

def _moved(x, lo):
    return jnp.roll(x, -lo, axis=1)


@functools.cache
def _ssd():
    def f(x, dt, A, B, C, starts):
        return nh.ssd_chunked(x, dt, A, B, C, CHUNK, jnp.float32, starts)
    return jax.jit(f), jax.jit(functools.partial(f, starts=None))


def _ssd_inputs(seed=0, groups=1):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(1, S, 4, 6)), jnp.float32)
    dt = jnp.asarray(r.uniform(0.01, 0.6, size=(1, S, 4)), jnp.float32)
    A = -jnp.asarray(r.uniform(0.5, 4.0, size=(4,)), jnp.float32)
    B = jnp.asarray(r.normal(size=(1, S, groups, 5)), jnp.float32)
    C = jnp.asarray(r.normal(size=(1, S, groups, 5)), jnp.float32)
    return x, dt, A, B, C


@pytest.mark.parametrize("name", PACKINGS)
def test_ssd_chunked_gives_each_document_what_it_gives_alone(name):
    at = PACKINGS[name]
    packed, plain = _ssd()
    x, dt, A, B, C = _ssd_inputs()
    got = packed(x, dt, A, B, C, starts_of(at))
    for lo, hi in spans(at):
        alone = plain(_moved(x, lo), _moved(dt, lo), A, _moved(B, lo),
                      _moved(C, lo))
        np.testing.assert_allclose(got[:, lo:hi], alone[:, :hi - lo],
                                   atol=2e-5, err_msg=f"{name} [{lo}, {hi})")
    # and the recurrence one position at a time, the state zeroed at a start
    want = ref.recurrence(x, dt, A, B, C, jnp.asarray(starts_of(at) != 0))
    np.testing.assert_allclose(got, want, atol=2e-5)
    if len(at) > 1:  # the reset is no rounding: the state carried on differs
        assert np.abs(np.asarray(plain(x, dt, A, B, C) - got)).max() > 1e-2


def test_ssd_chunked_resets_a_padded_tail_and_several_groups():
    """L no multiple of the chunk (the tail padded), two groups of B and C."""
    x, dt, A, B, C = (t[:, :S - 3] if t.ndim > 1 else t
                      for t in _ssd_inputs(seed=1, groups=2))
    st = starts_of((0, 5, 21))[:, :S - 3]
    got = nh.ssd_chunked(x, dt, A, B, C, CHUNK, jnp.float32, st)
    want = ref.recurrence(x, dt, A, B, C, jnp.asarray(st != 0))
    np.testing.assert_allclose(got, want, atol=2e-5)


@functools.cache
def _conv():
    return (jax.jit(nh.causal_conv),
            jax.jit(functools.partial(nh.causal_conv, starts=None)))


@pytest.mark.parametrize("name", PACKINGS)
def test_causal_conv_gives_each_document_what_it_gives_alone(name):
    at = PACKINGS[name]
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(1, S, 7)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 7)), jnp.float32)
    b = jnp.asarray(r.normal(size=(7,)), jnp.float32)
    packed, plain = _conv()
    got = packed(x, w, b, starts_of(at))
    for lo, hi in spans(at):
        np.testing.assert_allclose(got[:, lo:hi],
                                   plain(_moved(x, lo), w, b)[:, :hi - lo],
                                   atol=1e-6, err_msg=f"{name} [{lo}, {hi})")
    np.testing.assert_allclose(
        got, ref.conv(x, w, b, ref.documents(starts_of(at))), atol=1e-6)


@functools.cache
def _attn():
    def f(q, k, v, starts):
        return nh.blockwise_causal_attention(q, k, v, block=16, scale=0.3,
                                             starts=starts)
    return jax.jit(f), jax.jit(functools.partial(f, starts=None))


@pytest.mark.parametrize("name", PACKINGS)
def test_attention_body_gives_each_document_what_it_gives_alone(name):
    at = PACKINGS[name]
    r = np.random.default_rng(3)
    q = jnp.asarray(r.normal(size=(1, S, 4, 8)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, S, 2, 8)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, S, 2, 8)), jnp.float32)
    packed, plain = _attn()
    got = packed(q, k, v, starts_of(at))
    for lo, hi in spans(at):
        alone = plain(_moved(q, lo), _moved(k, lo), _moved(v, lo))
        np.testing.assert_allclose(got[:, lo:hi], alone[:, :hi - lo],
                                   atol=1e-5, err_msg=f"{name} [{lo}, {hi})")


def test_score_scale_is_the_argument_and_defaults_to_rsqrt_of_the_head():
    r = np.random.default_rng(4)
    q, k, v = (jnp.asarray(r.normal(size=(1, 12, 2, 16)), jnp.float32)
               for _ in range(3))
    default = nh.blockwise_causal_attention(q, k, v, block=4)
    np.testing.assert_allclose(
        default, nh.blockwise_causal_attention(q, k, v, block=4, scale=0.25),
        atol=1e-6)
    other = nh.blockwise_causal_attention(q, k, v, block=4, scale=1 / 16)
    assert np.abs(np.asarray(other - default)).max() > 1e-2


@pytest.mark.parametrize("fn", ["ssd", "conv", "attn"])
def test_no_starts_equals_one_document(fn):
    """`starts=None` is a sequence of one document: a lone start at position
    0 gives the same numbers."""
    packed, plain = {"ssd": _ssd, "conv": _conv, "attn": _attn}[fn]()
    r = np.random.default_rng(5)
    if fn == "ssd":
        args = _ssd_inputs(seed=5)
    elif fn == "conv":
        args = (jnp.asarray(r.normal(size=(1, S, 7)), jnp.float32),
                jnp.asarray(r.normal(size=(4, 7)), jnp.float32),
                jnp.asarray(r.normal(size=(7,)), jnp.float32))
    else:
        args = (jnp.asarray(r.normal(size=(1, S, 4, 8)), jnp.float32),
                jnp.asarray(r.normal(size=(1, S, 2, 8)), jnp.float32),
                jnp.asarray(r.normal(size=(1, S, 2, 8)), jnp.float32))
    np.testing.assert_allclose(packed(*args, starts_of((0,))), plain(*args),
                               atol=1e-6)


# -- program against reference -----------------------------------------------------

AT = PACKINGS["off_chunk_edges"]


@pytest.fixture(scope="module")
def first_step():
    """One packed batch through both sides, each compiled ONCE for the tests
    below: (batch, model, seeded state, the reference's leaves, the
    program's (loss, logits, gradients), the reference's)."""
    with jax.default_matmul_precision("highest"):
        batch = one(batches(1, AT))
        model = make()
        tr, state, dense = seeded(CFG, model, batch)
        tokens, st, y = batch["sparse"]["token"], batch["dense"], batch["label"]

        def prog(params):
            logits = _prog_logits(model, params, tokens, st)
            return model.loss_fn(logits, y), logits

        def plain(d):
            logits = ref.forward(d, tokens, st, CFG)
            return ref.xent(logits, y, jnp.ones(y.shape, jnp.float32)), logits

        (lp, logits), pd = jax.jit(jax.value_and_grad(prog, has_aux=True))(
            state.dense_params)
        (lr, want), gd = jax.jit(jax.value_and_grad(plain, has_aux=True))(dense)
        return batch, model, state, dense, (lp, logits, _flat(pd)), (lr, want, gd)


def test_logits_loss_and_every_gradient_leaf_match_reference(first_step):
    batch, _, _, _, (lp, logits, got), (lr, want, gd) = first_step
    np.testing.assert_allclose(logits, want, atol=3e-5)
    assert abs(float(lp) - float(lr)) < 1e-5
    assert set(got) == set(gd)
    for path, g in gd.items():
        np.testing.assert_allclose(got[path], g, atol=5e-6, err_msg=path)
    # the tied table's gradient is the SUM of the lookup's and the head's:
    # rows no token looks up still get the head's part
    absent = np.setdiff1d(np.arange(CFG["vocab_size"]),
                          np.unique(batch["sparse"]["token"]))
    assert absent.size and np.all(np.abs(np.asarray(got[TABLE])[absent]).sum(-1) > 0)


def test_module_gives_each_document_what_it_gives_alone(first_step):
    batch, model, state, _, (_, logits, _), _ = first_step
    tokens = batch["sparse"]["token"]
    alone = jax.jit(lambda p, t, st=None: _prog_logits(model, p, t, st))
    for lo, hi in spans(AT):
        np.testing.assert_allclose(
            logits[:, lo:hi],
            alone(state.dense_params, _moved(tokens, lo))[:, :hi - lo],
            atol=3e-5, err_msg=f"[{lo}, {hi})")
    # one document a sequence: no `dense` entry, or a lone start at 0
    np.testing.assert_allclose(
        alone(state.dense_params, tokens),
        alone(state.dense_params, tokens, starts_of((0,), 2)), atol=1e-5)


@pytest.mark.parametrize("fault", ["no_state_reset", "conv_leak",
                                   "no_segment_mask", "noncausal"])
def test_the_reference_without_a_reset_is_another_model(first_step, fault):
    """What the packing faults plant is no rounding at this size."""
    batch, _, _, dense, _, (_, want, _) = first_step
    other = ref.forward(dense, batch["sparse"]["token"], batch["dense"], CFG,
                          "f32", fault)
    assert np.abs(np.asarray(other - want)).max() > 1e-3


@pytest.mark.parametrize("scalar,fault", [
    ("attention_multiplier", "attn_scale_rsqrt"),
    ("embedding_multiplier", "no_embedding_multiplier"),
    ("residual_multiplier", "no_residual_multiplier"),
    ("logits_scaling", "no_logits_scaling")])
def test_each_scalar_changes_the_output_as_the_reference_says(first_step, scalar,
                                                               fault):
    """A scalar at 1 (the score scale at head_dim^-1/2) is the reference's
    fault of that name: another model, and the program follows it there."""
    batch, _, state, dense, (_, logits, _), _ = first_step
    tokens, st = batch["sparse"]["token"], batch["dense"]
    value = 0.25 if scalar == "attention_multiplier" else 1.0  # 16^-1/2
    got = _prog_logits(make(**{scalar: value}), state.dense_params, tokens, st)
    assert np.abs(np.asarray(got - logits)).max() > 1e-3
    np.testing.assert_allclose(
        got, ref.forward(dense, tokens, st, CFG, "f32", fault), atol=3e-5)


def test_k_steps_of_train_many_follow_the_reference_and_count_the_packing():
    """Three packed steps through `jit_train_many` against the reference's
    three dense Adagrad steps: losses, every leaf and every accumulator; the
    tied leaf takes ONE step a step on the summed gradient; the window's
    `pack.*` series and the trace-time `pack.resets{site=}`."""
    K, at = 3, PACKINGS["several_in_a_chunk"]
    stacked = batches(K, at, seed=7)
    model = make()
    tr, state, dense = seeded(CFG, model, one(stacked))
    table0 = np.asarray(dense[TABLE])
    metrics.reset_all()
    state, m = tr.jit_train_many()(state, stacked)
    step = jax.jit(ref.train_step(CFG))
    ref_state = (dense, {n: jnp.full_like(p, 0.1) for n, p in dense.items()})
    weight = np.ones((2, S), np.float32)
    losses = []
    for k in range(K):
        b = one(stacked, k)
        ref_state, loss = step(ref_state, b["sparse"]["token"], b["dense"],
                               b["label"], weight)
        losses.append(float(loss))
    np.testing.assert_allclose(m["loss"], losses, rtol=2e-6)
    got, accs = _flat(state.dense_params), _flat(state.dense_slots)
    for path, w in ref_state[0].items():
        np.testing.assert_allclose(got[path], w, atol=2e-6, err_msg=path)
        np.testing.assert_allclose(accs[path + "/accum"].reshape(w.shape),
                                   ref_state[1][path], rtol=2e-4, err_msg=path)
    # every row moved (the head's gradient reaches rows no id pulled), once a step
    assert np.all(np.abs(np.asarray(got[TABLE]) - table0).sum(-1) > 0)
    report = metrics.report()
    assert report["pack.documents"] == len(at)
    assert report["pack.longest_doc_share"] == pytest.approx(19 / S)
    # 3 Mamba layers + 1 attention layer, traced once by `init` (under
    # `jax.jit`: cached from `seeded`) and once by the scan
    assert report['pack.resets{site="ssd"}'] == report['pack.resets{site="conv"}'] == 3
    assert report['pack.resets{site="attn"}'] == 1
    assert report['attn.cores{path="blockwise"}'] == 1 and report['attn.cores{path="fused"}'] == 0


def test_untied_head_is_another_training_step(first_step):
    """The reference's `untied_head` (the table keeps the lookup's gradient
    alone) leaves the rows no id pulled where they were; the program's tied
    leaf does not (`first_step`'s gradient on those rows is not zero)."""
    batch, _, _, dense, _, _ = first_step
    d = dict(dense, __head__=dense[TABLE])
    state = (d, {n: jnp.full_like(p, 0.1) for n, p in d.items()})
    weight = np.ones(batch["label"].shape, np.float32)
    (after, _), _ = jax.jit(ref.train_step(CFG, fault="untied_head"))(
        state, batch["sparse"]["token"], batch["dense"], batch["label"], weight)
    absent = np.setdiff1d(np.arange(CFG["vocab_size"]),
                          np.unique(batch["sparse"]["token"]))
    np.testing.assert_array_equal(np.asarray(after[TABLE])[absent],
                                  np.asarray(dense[TABLE])[absent])


def test_packing_stats_count_documents_and_the_longest():
    st = np.zeros((2, 10), np.int32)
    st[0, [0, 4]] = 1          # documents of 4 and 6
    st[1, [0, 1, 2, 9]] = 1    # 1, 1, 7, 1
    got = gh.packing_stats(jnp.asarray(st))
    assert float(got["pack.documents"]) == 3.0
    assert float(got["pack.longest_doc_share"]) == pytest.approx(0.65)
    none = gh.packing_stats(None)
    assert float(none["pack.documents"]) == float(none["pack.longest_doc_share"]) == 1.0
    np.testing.assert_array_equal(nh.segment_ids(st)[1], [1, 2, 3, 3, 3, 3, 3, 3, 3, 4])


def test_make_refuses_layer_types_it_does_not_know():
    with pytest.raises(ValueError, match="layer_types"):
        make(layer_types=["mamba", "moe", "mamba", "mamba"])
    with pytest.raises(ValueError, match="layer_types"):
        make(num_hidden_layers=6)
    model = make()
    again = models.from_config(model.config)
    assert again.config == model.config and again.module == model.module
    assert again.module.takes_tables and again.specs["token"].sparse_as_dense
    assert model.module.dims.head_dim == 16
