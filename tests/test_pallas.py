"""Pallas kernel parity vs the XLA path (interpreter mode on CPU).

The reference validates its server hot path with self-checking expected-value tests
(`entry/c_api_test.h:32-154`); here the XLA implementation in `ops/sparse.py` is the
checked-elsewhere oracle and the Pallas kernels must match it bit-for-bit (same f32
math, same masking contract)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu.ops import pallas_sparse
from openembedding_tpu.ops.sparse import lookup_rows, sparse_apply_dense_table
from openembedding_tpu import optimizers


@pytest.fixture(autouse=True)
def _pallas_off_by_default():
    """Each test drives the mode explicitly; never leak state across tests."""
    pallas_sparse.set_mode("off")
    yield
    pallas_sparse.set_mode("off")


def _rand_table(rng, n_rows, dim, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal((n_rows, dim)), dtype)


def test_gather_rows_matches_xla():
    rng = np.random.default_rng(0)
    w = _rand_table(rng, 64, 12)
    rows = jnp.asarray(rng.integers(-5, 80, size=50), jnp.int32)  # incl. OOB both ends
    ref = lookup_rows(w, rows)
    got = pallas_sparse.gather_rows(w, rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_gather_rows_valid_mask():
    rng = np.random.default_rng(1)
    w = _rand_table(rng, 32, 8)
    rows = jnp.asarray(rng.integers(0, 32, size=20), jnp.int32)
    valid = jnp.asarray(rng.integers(0, 2, size=20).astype(bool))
    ref = lookup_rows(w, rows, valid)
    got = pallas_sparse.gather_rows(w, rows, valid, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_gather_rows_non_divisible_block():
    rng = np.random.default_rng(2)
    w = _rand_table(rng, 300, 9)  # dim 9: the reference benchmark dim, unaligned
    rows = jnp.asarray(rng.integers(0, 300, size=37), jnp.int32)
    ref = lookup_rows(w, rows)
    got = pallas_sparse.gather_rows(w, rows, block=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


ALL_OPTS = [
    optimizers.Default(learning_rate=0.1),
    optimizers.SGD(learning_rate=0.05, momentum=0.9, nesterov=True),
    optimizers.Adagrad(learning_rate=0.1),
    optimizers.Adadelta(learning_rate=0.5),
    optimizers.Adam(learning_rate=0.01),
    optimizers.Adamax(learning_rate=0.01),
    optimizers.Ftrl(learning_rate=0.05, l1_regularization_strength=0.01,
                    l2_regularization_strength=0.01),
    optimizers.RMSprop(learning_rate=0.05, momentum=0.5),
    optimizers.TestOptimizer(),
]


@pytest.mark.parametrize("opt", ALL_OPTS, ids=lambda o: o.category)
def test_fused_apply_matches_xla(opt):
    rng = np.random.default_rng(3)
    n_rows, dim, n = 64, 12, 40
    w = _rand_table(rng, n_rows, dim)
    slots = opt.init_slots(n_rows, dim)
    # warm the slots so non-trivial state paths are exercised
    ids0 = jnp.asarray(rng.integers(0, n_rows, size=n))
    g0 = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    w, slots = sparse_apply_dense_table(opt, w, slots, ids0, g0)

    ids = jnp.asarray(rng.integers(0, n_rows, size=n))  # duplicates likely
    g = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)

    ref_w, ref_s = sparse_apply_dense_table(opt, w, slots, ids, g)
    pallas_sparse.set_mode("interpret")
    got_w, got_s = sparse_apply_dense_table(opt, w, slots, ids, g)

    # rtol covers ftrl's slightly different operation order in the kernel (~1e-7 rel)
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(got_w),
                               rtol=2e-6, atol=1e-6)
    for k in ref_s:
        np.testing.assert_allclose(np.asarray(ref_s[k]), np.asarray(got_s[k]),
                                   rtol=2e-6, atol=1e-6, err_msg=k)


def test_fused_apply_padding_rows_untouched():
    """counts == 0 and out-of-range rows must leave the table bit-identical."""
    rng = np.random.default_rng(4)
    opt = optimizers.Adagrad(learning_rate=0.1)
    n_rows, dim = 32, 8
    w = _rand_table(rng, n_rows, dim)
    slots = opt.init_slots(n_rows, dim)
    rows = jnp.asarray([3, 7, n_rows, -1, 3 + n_rows * 10], jnp.int32)
    counts = jnp.asarray([1, 2, 1, 1, 1], jnp.int32)
    grads = jnp.asarray(rng.standard_normal((5, dim)), jnp.float32)
    new_w, new_s = pallas_sparse.fused_sparse_apply(
        opt, w, slots, rows, grads, counts, interpret=True)
    touched = {3, 7}
    for r in range(n_rows):
        if r in touched:
            assert not np.allclose(np.asarray(new_w[r]), np.asarray(w[r]))
        else:
            np.testing.assert_array_equal(np.asarray(new_w[r]), np.asarray(w[r]))
            np.testing.assert_array_equal(np.asarray(new_s["accum"][r]),
                                          np.asarray(slots["accum"][r]))


def test_fused_apply_bf16_table():
    """bf16 weights: f32 update math, bf16 store (slots stay f32)."""
    rng = np.random.default_rng(5)
    opt = optimizers.Adam(learning_rate=0.05)
    n_rows, dim, n = 48, 16, 24
    w = _rand_table(rng, n_rows, dim, jnp.bfloat16)
    slots = opt.init_slots(n_rows, dim)
    ids = jnp.asarray(rng.integers(0, n_rows, size=n))
    g = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    ref_w, ref_s = sparse_apply_dense_table(opt, w, slots, ids, g)
    pallas_sparse.set_mode("interpret")
    got_w, got_s = sparse_apply_dense_table(opt, w, slots, ids, g)
    np.testing.assert_array_equal(np.asarray(ref_w, np.float32),
                                  np.asarray(got_w, np.float32))
    for k in ref_s:
        np.testing.assert_allclose(np.asarray(ref_s[k]), np.asarray(got_s[k]),
                                   atol=1e-6)


def test_hash_table_apply_via_pallas():
    """The hash push path routes slots through the same fused apply."""
    from openembedding_tpu.embedding import (EmbeddingSpec, apply_gradients,
                                             init_table_state, lookup_train)
    rng = np.random.default_rng(6)
    spec = EmbeddingSpec(name="h", input_dim=-1, output_dim=8, capacity=128,
                         variable_id=0)
    opt = optimizers.Adagrad(learning_rate=0.1)
    state = init_table_state(spec, opt)
    ids = jnp.asarray(rng.integers(0, 1 << 40, size=30).astype(np.int64))
    state, _ = lookup_train(spec, state, ids)
    grads = jnp.asarray(rng.standard_normal((30, 8)), jnp.float32)

    ref = apply_gradients(spec, state, opt, ids, grads)
    pallas_sparse.set_mode("interpret")
    got = apply_gradients(spec, state, opt, ids, grads)
    np.testing.assert_allclose(np.asarray(ref.weights), np.asarray(got.weights),
                               atol=1e-6)


def test_single_device_train_step_with_pallas():
    """Whole Trainer step under interpret mode stays numerically on the XLA path."""
    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.data import synthetic_criteo

    model = make_deepfm(vocabulary=1 << 12, dim=8)
    batch = next(synthetic_criteo(64, id_space=1 << 12, steps=1, seed=0))

    def run():
        trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
        state = trainer.init(batch)
        state, metrics = trainer.jit_train_step()(state, batch)
        return float(metrics["loss"]), state

    loss_ref, state_ref = run()
    pallas_sparse.set_mode("interpret")
    loss_got, state_got = run()
    assert np.isfinite(loss_got)
    np.testing.assert_allclose(loss_got, loss_ref, atol=1e-6)
    for name in state_ref.tables:
        np.testing.assert_allclose(
            np.asarray(state_ref.tables[name].weights),
            np.asarray(state_got.tables[name].weights), atol=1e-6)


def test_env_mode_validated(monkeypatch):
    """Round-1 advisor: OETPU_PALLAS=garbage must not silently enable Pallas."""
    monkeypatch.setenv("OETPU_PALLAS", "TRUE")
    with pytest.warns(RuntimeWarning, match="OETPU_PALLAS"):
        assert pallas_sparse._env_mode() == "off"
    monkeypatch.setenv("OETPU_PALLAS", "interpret")
    assert pallas_sparse._env_mode() == "interpret"


def test_gather_rows_windows_matches_xla():
    """Window-batched gather (PERF lever #1): sorted, clustered, uniform, and
    OOB ids all match the XLA oracle."""
    rng = np.random.default_rng(3)
    w = _rand_table(rng, 1000, 12)
    # clustered (frequency-relabeled shape): many ids in the hot low range
    hot = np.sort(rng.integers(0, 64, size=40))
    cold = np.sort(rng.integers(64, 1000, size=24))
    for rows_np in (
        np.concatenate([hot, cold]),                      # sorted, clustered
        rng.integers(0, 1000, size=77),                   # unsorted uniform
        np.asarray([0, 1, 2, 998, 999]),                  # table-edge windows
        np.asarray([-3, 5, 1005]),                        # OOB both ends
    ):
        rows = jnp.asarray(rows_np, jnp.int32)
        ref = lookup_rows(w, rows)
        got = pallas_sparse.gather_rows_windows(w, rows, window=16,
                                                interpret=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_gather_rows_windows_small_table_falls_back():
    rng = np.random.default_rng(4)
    w = _rand_table(rng, 8, 4)  # table smaller than the window
    rows = jnp.asarray([0, 3, 7, 9, -1], jnp.int32)
    ref = lookup_rows(w, rows)
    got = pallas_sparse.gather_rows_windows(w, rows, window=16,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_gather_rows_windows_multiblock():
    rng = np.random.default_rng(5)
    w = _rand_table(rng, 4096, 8)
    rows = jnp.asarray(np.sort(rng.integers(0, 4096, size=700)), jnp.int32)
    ref = lookup_rows(w, rows)
    got = pallas_sparse.gather_rows_windows(w, rows, window=32, block=256,
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ---------------------------------------------------------------------------
# the apply's scatter as row DMAs (`ops/pallas_scatter.py`), behind
# `ops.sparse.scatter_rows` where the table's rows are whole lane lines
# ---------------------------------------------------------------------------


def _sorted_targets(rng, n_rows, n, n_valid, n_negative=0):
    """What the routed apply hands the scatter: `n_valid` ascending distinct
    rows, then padding at the distinct out-of-bounds rows n_rows + slot (and,
    for the kernel alone, `n_negative` targets under the table first)."""
    rows = np.sort(rng.choice(n_rows, n_valid, replace=False))
    return np.concatenate([np.arange(-n_negative, 0), rows, n_rows + np.arange(
        n_negative + n_valid, n)]).astype(np.int32)


@pytest.mark.parametrize("width,dtype,n,n_valid,n_negative,block", [
    (128, jnp.float32, 37, 20, 0, 16),    # W not a multiple of the block
    (256, jnp.float32, 70, 41, 0, 16),    # a row of two lane lines
    (128, jnp.float32, 64, 64, 0, 16),    # every block in range whole
    (128, jnp.float32, 90, 40, 0, 16),    # whole, mixed and padding blocks
    (128, jnp.float32, 40, 0, 0, 16),     # an empty valid prefix
    (128, jnp.float32, 50, 20, 19, 16),   # targets under the table: dropped
    (128, jnp.int32, 33, 17, 0, 32),
    (256, jnp.int32, 5, 3, 0, 1024),      # one block, the kernel's own size
], ids=["w128_ragged", "w256", "w128_all_valid", "w128_three_kinds_of_block",
        "w128_none_valid", "w128_negative_targets", "w128_int32",
        "w256_int32_one_block"])
def test_dma_scatter_equals_the_xla_scatter(width, dtype, n, n_valid,
                                            n_negative, block):
    """Bit for bit: in-range targets overwritten, rows out of range on either
    side and padding left untouched."""
    from openembedding_tpu.ops import pallas_scatter
    from openembedding_tpu.ops.sparse import scatter_rows
    rng = np.random.default_rng(n)
    table = np.asarray(rng.standard_normal((96, width)) * 100, dtype)
    new = np.asarray(rng.standard_normal((n, width)) * 100, dtype)
    idx = _sorted_targets(rng, 96, n, n_valid, n_negative)
    got = np.asarray(jax.jit(lambda *a: pallas_scatter.scatter_rows(
        *a, block=block, interpret=True))(table, idx, new))
    want, ok = table.copy(), (idx >= 0) & (idx < 96)
    want[idx[ok]] = new[ok]
    np.testing.assert_array_equal(want, got)
    if not n_negative:  # XLA's scatter wraps a negative target: never sent
        np.testing.assert_array_equal(np.asarray(scatter_rows(  # CPU: XLA's
            jnp.asarray(table), idx, new, sorted_unique=True)), got)


@pytest.mark.parametrize("shape,dtype,promised,path", [
    ((64, 128), jnp.float32, True, "dma"), ((64, 128), jnp.int32, True, "dma"),
    ((64, 256), jnp.float32, True, "xla"), ((64, 20), jnp.float32, True, "xla"),
    ((64, 2), jnp.float32, True, "xla"), ((64, 128), jnp.bfloat16, True, "xla"),
    ((64, 128), jnp.float32, False, None)])
def test_scatter_path_is_chosen_and_counted_from_the_shape(shape, dtype,
                                                           promised, path):
    """`sparse.scatters{path=}`: once a traced scatter under the promise, 1 on
    the path the shape chose and 0 on the other (both series exist); nothing
    without the promise. On the CPU both paths run XLA's scatter."""
    from openembedding_tpu.ops.sparse import scatter_rows, takes_row_dmas
    from openembedding_tpu.utils import metrics
    count = lambda p: metrics.report().get('sparse.scatters{path="%s"}' % p)
    table = jnp.zeros(shape, dtype)
    assert takes_row_dmas(table) == (path == "dma" or not promised)
    before = {p: count(p) or 0.0 for p in ("dma", "xla")}
    f = jax.jit(lambda t, i, v: scatter_rows(t, i, v, sorted_unique=promised))
    idx = jnp.asarray([3, 9, 64], jnp.int32)
    for _ in range(2):  # traced once
        out = f(table, idx, jnp.ones((3,) + shape[1:], dtype))
    assert np.asarray(out, np.float32).sum() == 2 * shape[1]
    after = {p: count(p) for p in ("dma", "xla")}
    if path is None:
        assert {p: after[p] or 0.0 for p in after} == before
    else:
        assert after == {p: before[p] + (p == path) for p in before}


def test_train_many_through_the_dma_scatter_leaves_the_plain_table(monkeypatch):
    """Two 3-step scans of a small dim-64 DeepFM (packed width 128, the
    ladder engaged): with the kernel in the TPU lowering's place (under the
    interpreter) the state is the plain path's bit for bit."""
    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.ops import pallas_scatter, sparse
    V, K = 1 << 10, 3
    monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", 0)
    batches = list(synthetic_criteo(64, id_space=V, steps=2 * K, seed=3))
    stack = lambda bs: jax.tree_util.tree_map(lambda *xs: np.stack(xs), *bs)

    def two_scans():
        tr = Trainer(make_deepfm(vocabulary=V, dim=64, hidden=(16,)),
                     embed.Adagrad(learning_rate=0.05))
        state, many = tr.init(batches[0]), tr.jit_train_many()
        for k in (0, K):
            state, _ = many(state, stack(batches[k:k + K]))
        return jax.tree_util.tree_map(np.asarray, state.tables)

    plain = two_scans()
    kernels = []

    def interpreted(*args):
        kernels.append(args[0].shape)
        return pallas_scatter.scatter_rows(*args, interpret=True)

    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: (interpreted if tpu is
                                     pallas_scatter.scatter_rows else tpu)(*args))
    forced = two_scans()
    assert kernels and set(kernels) == {(V, 128)}  # a rung each, dim 64's table
    jax.tree_util.tree_map(np.testing.assert_array_equal, plain, forced)
