"""Optimizer parity tests.

Mirrors the reference's `test/optimizer_test.py` (each optimizer config run against the
real Keras apply path on identical gradients) plus tight parity against independent
numpy implementations of the reference formulas (`variable/EmbeddingOptimizer.h`), and
the sparse-specific semantics: duplicate grads summed, update once per unique id,
untouched rows bit-identical, per-row beta^t.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from openembedding_tpu.ops.sparse import sparse_apply_dense_table

from apply_reference import np_adagrad

DIM = 8
ROWS = 6


def rand_block(seed, rows=ROWS, dim=DIM):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(rows, dim)).astype(np.float32)
    g = rng.normal(size=(rows, dim)).astype(np.float32)
    return w, g


# -- independent numpy references of the TF formulas ------------------------

def np_sgd(w, g, s, lr=0.01, momentum=0.0, nesterov=False):
    m = s["moment"] * momentum + lr * g
    w = w - (m * momentum + lr * g) if nesterov else w - m
    return w, {"moment": m}


def np_adadelta(w, g, s, lr=0.001, rho=0.95, eps=1e-7):
    a = s["accum"] * rho + g * g * (1 - rho)
    upd = g * np.sqrt(s["accum_update"] + eps) / np.sqrt(a + eps)
    au = s["accum_update"] * rho + upd * upd * (1 - rho)
    return w - lr * upd, {"accum": a, "accum_update": au}


def np_adam(w, g, s, lr=0.001, b1=0.9, b2=0.999, eps=1e-7):
    b1t = s["beta_1_t"] * b1
    b2t = s["beta_2_t"] * b2
    lr_t = lr * np.sqrt(1 - b2t) / (1 - b1t)
    m = s["m"] * b1 + g * (1 - b1)
    v = s["v"] * b2 + g * g * (1 - b2)
    return w - lr_t * m / (np.sqrt(v) + eps), {
        "m": m, "v": v, "beta_1_t": b1t, "beta_2_t": b2t}


def np_adamax(w, g, s, lr=0.001, b1=0.9, b2=0.999, eps=1e-7):
    b1t = s["beta_1_t"] * b1
    lr_t = lr / (1 - b1t)
    m = s["m"] * b1 + g * (1 - b1)
    v = np.maximum(np.abs(g), s["v"] * b2)
    return w - lr_t * m / (v + eps), {"m": m, "v": v, "beta_1_t": b1t}


def np_ftrl(w, g, s, lr=0.001, l1=0.0, l2=0.0, l2s=0.0, lr_power=-0.5, beta=0.0):
    accum, linear = s["accum"], s["linear"]
    adj_l2 = l2 + beta / lr / 2
    gg = g + 2 * l2s * w
    accum_new = accum + g * g
    p = -lr_power
    sigma = (accum_new ** p - accum ** p) / lr
    linear = linear + gg - sigma * w
    quad = accum_new ** p / lr + 2 * adj_l2
    l1_adj = np.clip(linear, -l1, l1)
    return (l1_adj - linear) / quad, {"accum": accum_new, "linear": linear}


def np_rmsprop(w, g, s, lr=0.001, rho=0.9, momentum=0.0, eps=1e-7):
    a = s["accum"] * rho + g * g * (1 - rho)
    m = s["moment"] * momentum + lr * g / np.sqrt(a + eps)
    return w - m, {"accum": a, "moment": m}


CASES = [
    (embed.SGD(learning_rate=0.05), np_sgd, dict(lr=0.05)),
    (embed.SGD(learning_rate=0.05, momentum=0.9), np_sgd, dict(lr=0.05, momentum=0.9)),
    (embed.SGD(learning_rate=0.05, momentum=0.9, nesterov=True), np_sgd,
     dict(lr=0.05, momentum=0.9, nesterov=True)),
    (embed.Adagrad(learning_rate=0.1), np_adagrad, dict(lr=0.1)),
    (embed.Adadelta(learning_rate=0.7), np_adadelta, dict(lr=0.7)),
    (embed.Adam(learning_rate=0.01), np_adam, dict(lr=0.01)),
    (embed.Adamax(learning_rate=0.01), np_adamax, dict(lr=0.01)),
    (embed.Ftrl(learning_rate=0.05), np_ftrl, dict(lr=0.05)),
    (embed.Ftrl(learning_rate=0.05, l1_regularization_strength=0.01,
                l2_regularization_strength=0.02,
                l2_shrinkage_regularization_strength=0.01, beta=0.1), np_ftrl,
     dict(lr=0.05, l1=0.01, l2=0.02, l2s=0.01, beta=0.1)),
    (embed.Ftrl(learning_rate=0.05, learning_rate_power=-0.7), np_ftrl,
     dict(lr=0.05, lr_power=-0.7)),
    (embed.RMSprop(learning_rate=0.01), np_rmsprop, dict(lr=0.01)),
    (embed.RMSprop(learning_rate=0.01, momentum=0.9), np_rmsprop,
     dict(lr=0.01, momentum=0.9)),
]


@pytest.mark.parametrize("opt,np_fn,np_kwargs",
                         CASES, ids=lambda c: getattr(c, "category", None) or "")
def test_numpy_parity_multi_step(opt, np_fn, np_kwargs):
    w, _ = rand_block(0)
    slots = {k: np.asarray(v) for k, v in
             opt.init_slots(ROWS, DIM, jnp.float32).items()}
    jw = jnp.asarray(w)
    jslots = {k: jnp.asarray(v) for k, v in slots.items()}
    counts = jnp.ones((ROWS,), jnp.int32)
    apply_fn = jax.jit(opt.apply)
    for step in range(5):
        _, g = rand_block(step + 1)
        jw, jslots = apply_fn(jw, jslots, jnp.asarray(g), counts)
        w, slots = np_fn(w, g, slots, **np_kwargs)
    np.testing.assert_allclose(np.asarray(jw), w, rtol=2e-5, atol=2e-6)
    for k in slots:
        np.testing.assert_allclose(np.asarray(jslots[k]), slots[k],
                                   rtol=2e-5, atol=2e-6, err_msg=k)


@pytest.mark.parametrize("opt", [c[0] for c in CASES],
                         ids=[f"{c[0].category}{i}" for i, c in enumerate(CASES)])
def test_untouched_rows_bit_identical(opt):
    w, g = rand_block(3)
    slots = opt.init_slots(ROWS, DIM, jnp.float32)
    # touch only rows 1 and 4
    counts = jnp.asarray([0, 2, 0, 0, 1, 0], jnp.int32)
    new_w, new_slots = opt.apply(jnp.asarray(w), slots, jnp.asarray(g), counts)
    untouched = np.asarray([0, 2, 3, 5])
    np.testing.assert_array_equal(np.asarray(new_w)[untouched], w[untouched])
    for k in slots:
        np.testing.assert_array_equal(np.asarray(new_slots[k])[untouched],
                                      np.asarray(slots[k])[untouched], err_msg=k)
    touched = np.asarray([1, 4])
    assert not np.allclose(np.asarray(new_w)[touched], w[touched])


def test_sparse_apply_sums_duplicates_once():
    """Duplicate-id grads must be summed and the optimizer applied ONCE per unique id
    (reference: `MpscGradientReducer.h:26-53`, `EmbeddingOptimizerVariable.h:283-296`).
    Adagrad distinguishes sum-then-apply from apply-per-duplicate."""
    opt = embed.Adagrad(learning_rate=0.1)
    vocab, dim = 10, 4
    rng = np.random.default_rng(0)
    weights = jnp.asarray(rng.normal(size=(vocab, dim)).astype(np.float32))
    slots = opt.init_slots(vocab, dim, jnp.float32)
    ids = jnp.asarray([3, 7, 3, 3, 7, 1], jnp.int32)
    grads = jnp.asarray(rng.normal(size=(6, dim)).astype(np.float32))
    new_w, new_slots = sparse_apply_dense_table(opt, weights, slots, ids, grads)

    w = np.asarray(weights).copy()
    accum = np.full((vocab, dim), 0.1, np.float32)
    for uid in [1, 3, 7]:
        g = np.asarray(grads)[np.asarray(ids) == uid].sum(axis=0)
        w[uid], s = np_adagrad(w[uid], g, {"accum": accum[uid]}, lr=0.1)
        accum[uid] = s["accum"]
    np.testing.assert_allclose(np.asarray(new_w), w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_slots["accum"]), accum,
                               rtol=1e-5, atol=1e-6)


def test_test_optimizer_count_semantics():
    """The `test` optimizer divides by count and flips state — the contract the
    self-checking cluster tests rely on (`EmbeddingOptimizer.h:366-390`)."""
    opt = embed.TestOptimizer(learning_rate=0.1, flip=100.0, init=0.0)
    w = jnp.zeros((2, 3), jnp.float32)
    slots = opt.init_slots(2, 3, jnp.float32)
    g = jnp.ones((2, 3), jnp.float32) * 6.0
    counts = jnp.asarray([2, 3], jnp.int32)
    new_w, new_slots = opt.apply(w, slots, g, counts)
    # state flips 0 -> 100; w += 0.1*6/count + 100
    np.testing.assert_allclose(np.asarray(new_w)[0], 100.3, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_w)[1], 100.2, rtol=1e-6)
    new_w2, new_slots2 = opt.apply(new_w, new_slots, g, counts)
    # state flips back to 0
    np.testing.assert_allclose(np.asarray(new_slots2["flip_state"]), 0.0, atol=1e-6)


def test_keras_cross_check():
    """Loose cross-check vs real Keras (the reference asserts summed abs error < 10 vs
    TF, `test/optimizer_test.py:54-72`; Keras 3 moved epsilon placement slightly so the
    tolerance is loose-but-meaningful)."""
    keras = pytest.importorskip("keras")
    import tensorflow as tf

    configs = [
        (embed.SGD(learning_rate=0.05), keras.optimizers.SGD(learning_rate=0.05)),
        (embed.SGD(learning_rate=0.05, momentum=0.9),
         keras.optimizers.SGD(learning_rate=0.05, momentum=0.9)),
        (embed.Adagrad(learning_rate=0.1, initial_accumulator_value=0.1),
         keras.optimizers.Adagrad(learning_rate=0.1, initial_accumulator_value=0.1)),
        (embed.Adam(learning_rate=0.01), keras.optimizers.Adam(learning_rate=0.01)),
        (embed.RMSprop(learning_rate=0.01), keras.optimizers.RMSprop(learning_rate=0.01)),
        (embed.Ftrl(learning_rate=0.05, initial_accumulator_value=0.1),
         keras.optimizers.Ftrl(learning_rate=0.05, initial_accumulator_value=0.1)),
    ]
    for ours, theirs in configs:
        w0, _ = rand_block(11)
        var = tf.Variable(w0)
        jw = jnp.asarray(w0)
        jslots = ours.init_slots(ROWS, DIM, jnp.float32)
        counts = jnp.ones((ROWS,), jnp.int32)
        for step in range(5):
            _, g = rand_block(100 + step)
            theirs.apply_gradients([(tf.constant(g), var)])
            jw, jslots = ours.apply(jw, jslots, jnp.asarray(g), counts)
        err = np.abs(np.asarray(jw) - var.numpy()).sum()
        assert err < 0.5, f"{ours.category}: summed abs err {err}"


def test_make_optimizer_roundtrip():
    for opt in [c[0] for c in CASES] + [embed.TestOptimizer()]:
        again = embed.make_optimizer(opt.to_config())
        assert again == opt


def test_from_keras_rejections():
    keras = pytest.importorskip("keras")
    with pytest.raises(ValueError):
        embed.optimizers.from_keras(keras.optimizers.Adam(amsgrad=True))
    with pytest.raises(ValueError):
        embed.optimizers.from_keras(keras.optimizers.RMSprop(centered=True))
