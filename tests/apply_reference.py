"""What the tests of the fused apply share (`tests/test_packed_layout.py`,
`tests/test_packed_lines.py`, `tests/test_sparse_ops.py`,
`tests/test_optimizers.py`): every optimizer that has slots, a table whose
slots one update has moved off their constants, Adagrad in NumPy, and the
comparison of two layouts' tables.
Not collected: no test lives here."""

import numpy as np

import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.ops.sparse import sparse_apply_dense_table

# every optimizer that has slots (`Default` has none: its weights alone are
# one array already, `packed_layout` returns None and nothing packs)
SLOTTED_OPTS = [
    embed.SGD(learning_rate=0.05, momentum=0.9, nesterov=True),
    embed.Adagrad(learning_rate=0.1),
    embed.Adadelta(learning_rate=0.5),
    embed.Adam(learning_rate=0.01),
    embed.Adamax(learning_rate=0.01),
    embed.Ftrl(learning_rate=0.05, l1_regularization_strength=0.01,
               l2_regularization_strength=0.01),
    embed.RMSprop(learning_rate=0.05, momentum=0.5),
    embed.optimizers.TestOptimizer(),
]

# Between two COMPILED programs the packed and the split apply of these two
# differ by roundings: the CPU compiler contracts a multiply into the add
# after it or not by the kernel the pair lands in, and their `g / sqrt(accum)`
# carries it (measured on the CPU, PR 43, a 1,024-row table: RMSprop 47 of
# 8,192 weights, at most 2.4e-7 absolute and 1.1e-6 of the weight, 4 `moment`s
# by 1.5e-8; Adadelta 4 `accum_update`s by one ulp; equal op by op and under
# `XLA_FLAGS=--xla_cpu_max_isa=SSE4_2`, which a process sets once). A column
# read from the wrong place is off by the size of a weight. Every other
# optimizer's two programs agree bit for bit.
ROUNDS_UNDER_JIT = ("rmsprop", "adadelta")


def warm_table(opt, rows, dim, n, rng):
    """-> (weights, slots after one update, ids with duplicates and invalid
    ones, gradients): a table whose slots are past their initial constants."""
    w = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    ids0 = jnp.asarray(rng.integers(0, rows, n), jnp.int32)
    g0 = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    w, slots = sparse_apply_dense_table(opt, w, opt.init_slots(rows, dim),
                                        ids0, g0)
    ids = jnp.asarray(rng.integers(-1, rows, n), jnp.int32)  # incl. invalid
    return w, slots, ids, jnp.asarray(rng.standard_normal((n, dim)),
                                      jnp.float32)


def assert_same_table(want, got, *, exact=True):
    """(weights, slots) against (weights, slots): the same slots, bit for
    bit, or (`exact=False`) to the roundings `ROUNDS_UNDER_JIT` names."""
    assert set(want[1]) == set(got[1])
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-6)


def np_adagrad(w, g, s, lr=0.001, eps=1e-7):
    a = s["accum"] + g * g
    return w - lr * g / (np.sqrt(a) + eps), {"accum": a}
