"""The sparse-ops layer's own contracts (`ops/sparse.py`), against NumPy: what a
row gather reads for a row it must not read, what the fused apply leaves of
the rows it must not write, the precision of its row math, and the rule the
layer is held to: one kernel family, chosen from shapes, with no switch."""

import ast
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import openembedding_tpu
from openembedding_tpu import optimizers
from openembedding_tpu.ops import sparse
from openembedding_tpu.ops.sparse import lookup_rows, sparse_apply_dense_table

from apply_reference import np_adagrad

PACKAGE = os.path.dirname(openembedding_tpu.__file__)


# ---------------------------------------------------------------------------
# the gather: a row out of range on either side, or masked, reads zeros
# ---------------------------------------------------------------------------

_ROWS, _DIM = 64, 12
# ascending, as every hint promises; `sorted_unique` also duplicate-free
_UNIQUE = [-2**31, -7, -1, 0, 3, 17, 62, 63, 64, 65, 1000, 2**31 - 1]
_REPEATS = [-7, -7, -1, 0, 0, 3, 3, 3, 63, 63, 64, 64, 2**31 - 1]


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "valid_mask"])
@pytest.mark.parametrize("hint", ["none", "sorted_unique", "ascending"])
def test_a_row_that_must_not_be_read_reads_zeros(hint, masked):
    """Negative, out-of-range and `valid=False` rows read zeros and every
    other row reads the table's, with each hint and with none; on rows that
    keep a hint's promise the hinted gather is the unhinted one."""
    rng = np.random.default_rng(0)
    table = rng.standard_normal((_ROWS, _DIM)).astype(np.float32)
    rows = np.asarray(_REPEATS if hint == "ascending" else _UNIQUE, np.int32)
    if hint == "none":       # no promise to keep: any order, repeats and all
        rows = rng.permutation(np.concatenate([rows, _REPEATS])).astype(np.int32)
    valid = rng.random(rows.size) < 0.6 if masked else None
    hints = {"none": {}, "sorted_unique": {"sorted_unique": True},
             "ascending": {"ascending": True}}[hint]
    got = np.asarray(jax.jit(lambda t, r, v: sparse._gather_rows(
        t, r, v, **hints))(table, rows, valid))
    read = (rows >= 0) & (rows < _ROWS) & (True if valid is None else valid)
    assert read.any() and (~read).any() and (table[rows[read]] != 0).all()
    want = np.where(read[:, None], table[np.where(read, rows, 0)], 0)
    np.testing.assert_array_equal(got, want.astype(np.float32))
    plain = np.asarray(sparse._gather_rows(jnp.asarray(table), rows, valid))
    np.testing.assert_array_equal(got, plain)
    if hint != "ascending":  # the public name takes the same rows
        np.testing.assert_array_equal(got, np.asarray(lookup_rows(
            jnp.asarray(table), rows, valid, **hints)))


# ---------------------------------------------------------------------------
# the fused apply against a NumPy reference
# ---------------------------------------------------------------------------

def test_apply_leaves_untouched_and_padding_rows_bit_identical():
    """Positions with `pre_counts` 0, negative ids and ids past the table's
    end (the first of them and far ones) train nothing: weights AND slots of
    every row but the valid positions' are the bytes they were, and the valid
    rows are the reference's."""
    rng = np.random.default_rng(4)
    opt = optimizers.Adagrad(learning_rate=0.1)
    n_rows, dim = 32, 8
    w = rng.standard_normal((n_rows, dim)).astype(np.float32)
    accum = (0.1 + rng.random((n_rows, dim))).astype(np.float32)
    ids = np.asarray([3, 7, n_rows, -1, 3 + n_rows * 10, 9, 7, 2**31 - 1, 11],
                     np.int32)
    pre = np.asarray([1, 2, 1, 1, 1, 0, 1, 3, 0], np.int32)  # 9, 11: padding
    g = rng.standard_normal((ids.size, dim)).astype(np.float32)
    new_w, new_s = jax.jit(lambda *a: sparse_apply_dense_table(opt, *a))(
        w, {"accum": accum}, ids, g, pre)
    new_w, new_accum = np.asarray(new_w), np.asarray(new_s["accum"])
    touched = [3, 7]
    rest = np.setdiff1d(np.arange(n_rows), touched)
    np.testing.assert_array_equal(new_w[rest], w[rest])
    np.testing.assert_array_equal(new_accum[rest], accum[rest])
    for r in touched:        # duplicates summed, the optimizer applied once
        want_w, want_s = np_adagrad(w[r], g[ids == r].sum(0),
                                    {"accum": accum[r]}, lr=0.1)
        np.testing.assert_allclose(new_w[r], want_w, rtol=1e-6)
        np.testing.assert_allclose(new_accum[r], want_s["accum"], rtol=1e-6)
        assert (new_w[r] != w[r]).all()


def test_apply_updates_a_bf16_table_in_float32_and_casts_back():
    """bf16 weights: the row math runs on the row upcast to float32 against
    float32 slots and is rounded to bf16 once, on the way back (in bf16
    `beta_2^t` rounds to 1 and Adam's step vanishes); the slots stay float32
    and rows out of the update keep their bytes."""
    rng = np.random.default_rng(5)
    opt = optimizers.Adam(learning_rate=0.05)
    n_rows, dim, n = 48, 16, 24
    w = jnp.asarray(rng.standard_normal((n_rows, dim)), jnp.bfloat16)
    slots = opt.init_slots(n_rows, dim, jnp.bfloat16)
    assert all(v.dtype == jnp.float32 for v in slots.values())
    ids = rng.integers(0, n_rows, n).astype(np.int32)
    g = rng.standard_normal((n, dim)).astype(np.float32)
    new_w, new_s = jax.jit(lambda *a: sparse_apply_dense_table(opt, *a))(
        w, slots, ids, g)
    assert new_w.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.float32 for v in new_s.values())

    w32 = np.asarray(w, np.float32)
    uniq = np.unique(ids)
    b1t, b2t = np.float32(opt.beta_1), np.float32(opt.beta_2)   # the 1st step
    assert jnp.asarray(b2t, jnp.bfloat16) == 1                  # the docstring
    for r in uniq:
        gr = g[ids == r].sum(0)
        m = gr * np.float32(1 - opt.beta_1)
        v = gr * gr * np.float32(1 - opt.beta_2)
        lr_t = np.float32(opt.learning_rate) * np.sqrt(1 - b2t) / (1 - b1t)
        want = w32[r] - lr_t * m / (np.sqrt(v) + np.float32(opt.epsilon))
        np.testing.assert_allclose(np.asarray(new_s["m"])[r], m, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(new_s["v"])[r], v, rtol=1e-5)
        # float32 math, ONE rounding to bf16: within a bf16 ulp of the
        # reference (the float32 results may straddle a rounding boundary)
        got = np.asarray(new_w, np.float32)[r]
        ulp = np.abs(want) * 2.0 ** -7
        assert (np.abs(got - want) <= ulp).all()
        assert (got != w32[r]).any()
    rest = np.setdiff1d(np.arange(n_rows), uniq)
    np.testing.assert_array_equal(np.asarray(new_w, np.float32)[rest],
                                  w32[rest])


# ---------------------------------------------------------------------------
# the rule: one kernel family, chosen from shapes
# ---------------------------------------------------------------------------

def _sources(subdir=""):
    for root, _, files in os.walk(os.path.join(PACKAGE, subdir)):
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    yield os.path.relpath(path, PACKAGE), fh.read()


def test_no_module_of_ops_reads_the_environment_but_the_wire():
    """Which kernel runs is the shape's choice (`takes_row_dmas`,
    `takes_lines`, `flash_attention.tiling`), never a variable's: `ops/`
    reads `os.environ` once, `ops/wire.py`'s `WIRE_ENV` (ROADMAP Design 3)."""
    reads = {}
    for path, text in _sources("ops"):
        for node in ast.walk(ast.parse(text)):
            if (isinstance(node, ast.Attribute)
                    and node.attr in ("environ", "getenv", "environb")):
                reads.setdefault(path, []).append(
                    ast.get_source_segment(text, node))
            if isinstance(node, (ast.Import, ast.ImportFrom)) and any(
                    a.name in ("environ", "getenv") for a in node.names):
                reads.setdefault(path, []).append("import")
    assert reads == {os.path.join("ops", "wire.py"): ["os.environ"]}, reads
    from openembedding_tpu.ops import wire
    assert wire.WIRE_ENV == "OETPU_WIRE"


def test_ops_exports_what_the_package_calls():
    """Every public name `openembedding_tpu.ops` exports is a function some
    module of the package CALLS (by that name, wherever it imports it from),
    and the sub-modules are its only other attributes."""
    from openembedding_tpu import ops
    exported = {n for n, v in vars(ops).items()
                if not n.startswith("_") and not isinstance(v, type(os))}
    assert exported and all(callable(getattr(ops, n)) for n in exported)
    called = set()
    for path, text in _sources():
        if path == os.path.join("ops", "__init__.py"):
            continue
        for node in ast.walk(ast.parse(text)):
            if isinstance(node, ast.Call):
                f = node.func
                called.add(f.id if isinstance(f, ast.Name) else
                           f.attr if isinstance(f, ast.Attribute) else None)
    assert exported <= called, exported - called
