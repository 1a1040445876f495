"""The row-DMA scatter kernel (`ops/pallas_scatter.py`) against XLA's scatter,
under the interpreter on the CPU: bit for bit on what `ops.sparse.scatter_rows`
hands it, the rule that chooses it from the table's shape (`takes_row_dmas`)
and its counter, and a `train_many` scan through it. The line form's kernels
(`ops/pallas_lines.py`) are `tests/test_packed_lines.py`'s, the attention
kernel `tests/test_attention_kernel.py`'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


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
