"""Multivalent (ragged) feature pooling: the `combiner` surface.

The reference's `Variable.sparse_read` accepts RaggedTensors
(`tensorflow/exb.py:308-327`) and its consumers pool the ragged rows
(TF `safe_embedding_lookup_sparse` combiners). The TPU-native answer keeps
static shapes: `data.pad_ragged` pads variable-length id lists to a fixed
field width with -1, and `EmbeddingSpec.combiner` ("sum"/"mean"/"sqrtn")
pools the field axis with the pad slots masked out of both the value and the
gradient (`embedding.combine`). These tests pin that equivalence end to end:
value vs numpy varlen pooling, gradient parity, mesh-exchange parity, the
sparse_as_dense path, serving/export, and the ragged host-side helpers."""

import dataclasses
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import openembedding_tpu as embed
from openembedding_tpu.data import is_ragged, pad_ragged
from openembedding_tpu.embedding import EmbeddingSpec, combine, valid_mask

VOCAB, DIM, B, F = 64, 4, 16, 5


class PooledDense(nn.Module):
    """Dense tower over POOLED rows (B, dim) — the module a combiner model
    feeds."""

    @nn.compact
    def __call__(self, embedded, dense_inputs):
        parts = [embedded[k].reshape(embedded[k].shape[0], -1)
                 for k in sorted(embedded)]
        if dense_inputs is not None:
            parts.append(dense_inputs)
        return nn.Dense(1)(jnp.concatenate(parts, axis=-1))[:, 0]


class SumInModule(nn.Module):
    """The no-combiner control: pools (B, F, dim) -> (B, dim) by UNMASKED sum
    inside the module. Because pad slots pull zero rows and -1 grads train no
    row (pinned in test_embedding.py), this trains identically to
    combiner='sum' — the parity that proves the combiner's gradient path."""

    @nn.compact
    def __call__(self, embedded, dense_inputs):
        parts = [embedded[k].sum(axis=-2) for k in sorted(embedded)]
        if dense_inputs is not None:
            parts.append(dense_inputs)
        return nn.Dense(1)(jnp.concatenate(parts, axis=-1))[:, 0]


def ragged_batch(rng, batch=B, width=F, vocab=VOCAB):
    """Variable-length rows (1..width ids) padded to width with -1."""
    lens = rng.integers(1, width + 1, size=(batch,))
    ids = np.full((batch, width), -1, np.int64)
    for r, ln in enumerate(lens):
        ids[r, :ln] = rng.integers(0, vocab, size=(ln,))
    label = (lens % 2).astype(np.float32)
    return {"sparse": {"emb": jnp.asarray(ids)}, "dense": None,
            "label": jnp.asarray(label)}, lens


def ragged_hash_batch(seed, id_space=1 << 62):
    """Ragged 63-bit hash-table batch in the x64-appropriate layout (split
    pairs when x64 is off, plain int64 when on — production feed convention).
    -> (batch, lens)."""
    from openembedding_tpu.ops.id64 import np_split_ids
    r = np.random.default_rng(seed)
    lens = r.integers(1, F + 1, size=(B,))
    ids64 = np.full((B, F), -1, np.int64)
    for row, ln in enumerate(lens):
        ids64[row, :ln] = r.integers(0, id_space, size=(ln,))
    feed = (jnp.asarray(ids64) if jax.config.jax_enable_x64
            else jnp.asarray(np_split_ids(ids64)))
    return {"sparse": {"emb": feed}, "dense": None,
            "label": jnp.asarray((lens % 2).astype(np.float32))}, lens


def np_pool(table, ids, combiner):
    """Numpy oracle: true variable-length pooling over the valid prefix."""
    out = np.zeros((ids.shape[0], table.shape[1]), np.float32)
    for r in range(ids.shape[0]):
        sel = ids[r][ids[r] >= 0]
        if len(sel) == 0:
            continue
        rows = table[sel]
        if combiner == "sum":
            out[r] = rows.sum(0)
        elif combiner == "mean":
            out[r] = rows.mean(0)
        else:
            out[r] = rows.sum(0) / np.sqrt(len(sel))
    return out


# ---------------------------------------------------------------- unit level

def test_pad_ragged_and_is_ragged():
    seqs = [[1, 2, 3], [7], [4, 5]]
    assert is_ragged(seqs)
    padded = pad_ragged(seqs)
    np.testing.assert_array_equal(
        padded, [[1, 2, 3], [7, -1, -1], [4, 5, -1]])
    assert pad_ragged(seqs, width=4).shape == (3, 4)
    with pytest.raises(ValueError):
        pad_ragged(seqs, width=2)  # silent truncation refused
    assert not is_ragged([[1, 2], [3, 4]])          # rectangular
    assert not is_ragged(np.zeros((3, 2), np.int64))
    assert pad_ragged([], width=3).shape == (0, 3)
    assert pad_ragged([[]]).shape == (1, 1)          # all-empty row -> all-pad


@pytest.mark.parametrize("combiner", ["sum", "mean", "sqrtn"])
def test_combine_matches_numpy_varlen(combiner):
    rng = np.random.default_rng(0)
    table = rng.standard_normal((VOCAB, DIM)).astype(np.float32)
    ids = np.full((6, 4), -1, np.int64)
    for r, ln in enumerate([1, 2, 3, 4, 2, 0]):     # incl. an ALL-PAD row
        ids[r, :ln] = rng.integers(0, VOCAB, size=(ln,))
    spec = EmbeddingSpec(name="e", input_dim=VOCAB, output_dim=DIM,
                         combiner=combiner)
    rows = jnp.where(jnp.asarray(ids)[..., None] >= 0,
                     jnp.asarray(table)[jnp.clip(jnp.asarray(ids), 0)], 0.0)
    got = np.asarray(combine(spec, jnp.asarray(ids), rows))
    np.testing.assert_allclose(got, np_pool(table, ids, combiner),
                               rtol=1e-6, atol=1e-6)
    # all-pad row pools to zeros, not NaN (mean/sqrtn clamp the count)
    assert np.all(np.isfinite(got)) and np.all(got[5] == 0.0)


def test_combine_gradient_masks_pad_slots():
    """d(pooled)/d(row) is mask/count — pad slots get EXACTLY zero grad, so a
    pad slot can never train whatever row its -1 scatter might alias."""
    spec = EmbeddingSpec(name="e", input_dim=VOCAB, output_dim=DIM,
                         combiner="mean")
    ids = jnp.asarray([[3, 9, -1, -1]])
    rows = jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, 4, DIM)).astype(np.float32))
    g = jax.grad(lambda r: combine(spec, ids, r).sum())(rows)
    np.testing.assert_allclose(np.asarray(g[0, :2]), 0.5, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(g[0, 2:]), 0.0)


def test_combiner_validation():
    with pytest.raises(ValueError, match="combiner"):
        EmbeddingSpec(name="e", input_dim=8, output_dim=2, combiner="max")
    spec = EmbeddingSpec(name="e", input_dim=8, output_dim=2, combiner="sum")
    again = EmbeddingSpec.from_config(spec.to_config())
    assert again.combiner == "sum" and again == spec
    # pre-combiner configs (older checkpoints) default to no pooling
    cfg = spec.to_config()
    del cfg["combiner"]
    assert EmbeddingSpec.from_config(cfg).combiner == ""
    with pytest.raises(ValueError, match="rank"):
        combine(spec, jnp.asarray([1, 2]), jnp.zeros((2, 2)))


def test_valid_mask_pair_layout():
    from openembedding_tpu.ops.id64 import np_split_ids
    spec = EmbeddingSpec(name="e", input_dim=-1, output_dim=DIM, capacity=64,
                         combiner="mean")
    ids64 = np.asarray([[5, -1], [(1 << 40) + 3, 7]], np.int64)
    m = np.asarray(valid_mask(spec, jnp.asarray(np_split_ids(ids64))))
    np.testing.assert_array_equal(m, ids64 >= 0)


# ------------------------------------------------------------- training path

def test_combiner_sum_trains_identically_to_in_module_pooling():
    """combiner='sum' + PooledDense vs no combiner + SumInModule: same specs
    (same variable_id/seed -> same table init), same dense init, and — because
    pad rows are zero and -1 grads train nothing — the SAME training
    trajectory. This is the gradient-path parity proof."""
    rng = np.random.default_rng(7)
    opt = embed.Adagrad(learning_rate=0.1)

    def build(module, combiner):
        layer = embed.Embedding(VOCAB, DIM, name="emb", combiner=combiner)
        model = embed.EmbeddingModel(module, [layer])
        return embed.Trainer(model, optimizer=opt)

    ta = build(PooledDense(), "sum")
    tb = build(SumInModule(), "")
    batch, _ = ragged_batch(rng)
    sa, sb = ta.init(batch), tb.init(batch)
    np.testing.assert_array_equal(np.asarray(sa.tables["emb"].weights),
                                  np.asarray(sb.tables["emb"].weights))
    stepa, stepb = ta.jit_train_step(), tb.jit_train_step()
    for i in range(3):
        b, _ = ragged_batch(rng)
        sa, ma = stepa(sa, b)
        sb, mb = stepb(sb, b)
        np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                                   rtol=1e-6, err_msg=f"step {i}")
    np.testing.assert_allclose(np.asarray(sa.tables["emb"].weights),
                               np.asarray(sb.tables["emb"].weights),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("combiner", ["mean", "sqrtn"])
def test_combiner_eval_matches_manual_math(combiner):
    """eval logits == numpy varlen pooling pushed through the Dense(1) params
    by hand — the full value path with no jax on the oracle side."""
    rng = np.random.default_rng(3)
    layer = embed.Embedding(VOCAB, DIM, name="emb", combiner=combiner)
    model = embed.EmbeddingModel(PooledDense(), [layer])
    trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.1))
    batch, _ = ragged_batch(rng)
    state = trainer.init(batch)
    got = np.asarray(trainer.jit_eval_step()(state, batch)["logits"])
    table = np.asarray(state.tables["emb"].weights)
    pooled = np_pool(table, np.asarray(batch["sparse"]["emb"]), combiner)
    dense = state.dense_params["Dense_0"]
    want = pooled @ np.asarray(dense["kernel"]) + np.asarray(dense["bias"])
    np.testing.assert_allclose(got, want[:, 0], rtol=1e-5, atol=1e-6)


def test_combiner_mesh_matches_single_device():
    """The sharded exchange (pad ids ride the sentinel-filled buckets) pools
    identically to the single-device oracle. Same pattern as
    test_mesh.test_mesh_trainer_matches_single_device: Constant table init
    (sharding-independent), the oracle scales its loss by S to match the
    mesh's summed local-mean gradients, step-0 row updates must agree."""
    from openembedding_tpu.parallel import (MeshTrainer, deinterleave_rows,
                                            make_mesh)

    S = 8  # conftest's virtual CPU mesh
    rng = np.random.default_rng(11)
    batch, _ = ragged_batch(rng, batch=8 * S)

    def build(cls, loss_scale=1.0, **kw):
        layer = embed.Embedding(VOCAB, DIM, name="emb", combiner="mean",
                                embeddings_initializer=embed.Constant(0.1))
        model = embed.EmbeddingModel(
            PooledDense(), [layer],
            loss_fn=lambda lo, la: loss_scale * embed.model.binary_logloss(
                lo, la))
        return cls(model, optimizer=embed.Adagrad(learning_rate=0.1), **kw)

    single = build(embed.Trainer, loss_scale=float(S))
    ss = single.init(batch)
    ss, _ = jax.jit(single.train_step)(ss, batch)

    meshed = build(MeshTrainer, mesh=make_mesh())
    sm = meshed.init(batch)
    sm, _ = meshed.jit_train_step(batch, sm)(sm, batch)

    w_mesh = np.asarray(deinterleave_rows(sm.tables["emb"].weights, S, VOCAB))
    w_single = np.asarray(ss.tables["emb"].weights)
    np.testing.assert_allclose(w_mesh, w_single, rtol=1e-5, atol=1e-6)
    # pad slots trained nothing on either side: rows no batch id touches
    untouched = np.setdiff1d(np.arange(VOCAB),
                             np.asarray(batch["sparse"]["emb"]))
    np.testing.assert_allclose(w_single[untouched], np.float32(0.1),
                               rtol=0, atol=0)


def test_combiner_sparse_as_dense():
    """sad tables (dense-mirrored 'Cache' mode) pool through the same combine:
    pad slots (-1 take-clamps to row 0) are masked out of value AND grad, so
    row 0 never trains from a pad slot."""
    rng = np.random.default_rng(5)
    layer = embed.Embedding(VOCAB, DIM, name="emb", sparse_as_dense=True,
                            combiner="mean")
    model = embed.EmbeddingModel(PooledDense(), [layer])
    trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.5))
    # no row-0 ids anywhere: if a pad slot leaked grad, row 0 would move
    ids = np.asarray([[1, 2, -1, -1, -1], [3, -1, -1, -1, -1]], np.int64)
    batch = {"sparse": {"emb": jnp.asarray(ids)}, "dense": None,
             "label": jnp.asarray([1.0, 0.0])}
    state = trainer.init(batch)
    t0 = np.asarray(state.dense_params["__embeddings__"]["emb"])
    ev = np.asarray(trainer.jit_eval_step()(state, batch)["logits"])
    pooled = np_pool(t0, ids, "mean")
    dense = state.dense_params["Dense_0"]
    want = pooled @ np.asarray(dense["kernel"]) + np.asarray(dense["bias"])
    np.testing.assert_allclose(ev, want[:, 0], rtol=1e-5, atol=1e-6)
    step = trainer.jit_train_step()
    state, _ = step(state, batch)
    t1 = np.asarray(state.dense_params["__embeddings__"]["emb"])
    np.testing.assert_array_equal(t1[0], t0[0])          # row 0 untouched
    assert not np.allclose(t1[[1, 2, 3]], t0[[1, 2, 3]])  # real rows train


def test_combiner_hash_table_63bit_ids():
    """63-bit hash-table ids with ragged padding (-1 / EMPTY pair): pooled
    lookup matches the numpy oracle on the valid prefix. The id layout follows
    the x64 config exactly like production feeds do: split pairs when x64 is
    off (`ops/id64.py`), plain int64 when on (pair tables don't exist there)."""
    layer = embed.Embedding(-1, DIM, name="emb", capacity=256,
                            combiner="sum")
    model = embed.EmbeddingModel(PooledDense(), [layer])
    trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.1))
    batch, lens = ragged_hash_batch(9)
    state = trainer.init(batch)
    step = trainer.jit_train_step()
    s1, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    # pooled rows via the model == sum over the valid prefix of the raw pull
    raw = np.asarray(trainer.table_lookup(
        model.specs["emb"], s1.tables["emb"], batch["sparse"]["emb"]))
    got = np.asarray(trainer.jit_eval_step()(s1, batch)["logits"])
    dense = s1.dense_params["Dense_0"]
    want = (np.stack([raw[r, :lens[r]].sum(0) for r in range(B)])
            @ np.asarray(dense["kernel"]) + np.asarray(dense["bias"]))
    np.testing.assert_allclose(got, want[:, 0], rtol=1e-5, atol=1e-6)


def test_variable_sparse_read_accepts_ragged():
    """The imperative facade takes the reference's ragged input directly:
    list-of-lists pad to the batch max with -1; pad slots pull zero rows."""
    spec = EmbeddingSpec(name="v", input_dim=VOCAB, output_dim=DIM)
    var = embed.EmbeddingVariable(spec, embed.SGD(learning_rate=0.1))
    rows = np.asarray(var.sparse_read([[1, 2, 3], [5], [7, 8]]))
    assert rows.shape == (3, 3, DIM)
    dense_rows = np.asarray(var.read_only_pull([[1, 2, 3], [5], [7, 8]]))
    np.testing.assert_array_equal(rows, dense_rows)
    assert np.all(rows[1, 1:] == 0.0) and np.all(rows[2, 2:] == 0.0)
    np.testing.assert_array_equal(rows[0, :3],
                                  np.asarray(var.read_only_pull([1, 2, 3])))


def test_np_valid_mask_both_layouts():
    from openembedding_tpu.embedding import np_valid_mask
    from openembedding_tpu.ops.id64 import np_split_ids
    spec = EmbeddingSpec(name="e", input_dim=-1, output_dim=DIM, capacity=64)
    big = (1 << 40) + (1 << 31) + 5  # bit 31 set: int32 truncation goes negative
    ids64 = np.asarray([[big, -1], [7, 3]], np.int64)
    np.testing.assert_array_equal(np_valid_mask(spec, ids64), ids64 >= 0)
    np.testing.assert_array_equal(
        np_valid_mask(spec, np_split_ids(ids64)), ids64 >= 0)


def test_sad_pads_pull_zero_and_train_nothing():
    """sparse_as_dense WITHOUT a combiner: -1 pads must honor the same
    contract as every other lookup path — zero rows, zero grads. A bare
    jnp.take would wrap -1 onto the LAST table row in value and gradient
    (model.sad_rows is the fix)."""
    layer = embed.Embedding(VOCAB, DIM, name="emb", sparse_as_dense=True)
    model = embed.EmbeddingModel(SumInModule(), [layer])
    trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.5))
    # neither row 0 nor the last row appears; only pads could touch them
    ids = np.asarray([[1, 2, -1], [3, -1, -1]], np.int64)
    batch = {"sparse": {"emb": jnp.asarray(ids)}, "dense": None,
             "label": jnp.asarray([1.0, 0.0])}
    state = trainer.init(batch)
    t0 = np.asarray(state.dense_params["__embeddings__"]["emb"])
    got = np.asarray(trainer.jit_eval_step()(state, batch)["logits"])
    dense = state.dense_params["Dense_0"]
    want = (np_pool(t0, ids, "sum") @ np.asarray(dense["kernel"])
            + np.asarray(dense["bias"]))
    np.testing.assert_allclose(got, want[:, 0], rtol=1e-5, atol=1e-6)
    state, _ = trainer.jit_train_step()(state, batch)
    t1 = np.asarray(state.dense_params["__embeddings__"]["emb"])
    np.testing.assert_array_equal(t1[-1], t0[-1])  # -1 pad wrapped nowhere
    np.testing.assert_array_equal(t1[0], t0[0])
    assert not np.allclose(t1[[1, 2, 3]], t0[[1, 2, 3]])


def test_serving_mask_survives_x64_off(tmp_path):
    """Regression: StandaloneModel.predict's combiner mask must come from the
    host int64 ids. Under x64-off (the production default — this suite forces
    x64 ON, so this runs a child interpreter) `jnp.asarray` truncates a 63-bit
    id with bit 31 set to a NEGATIVE int32; a device-derived mask would mark
    it padding and silently drop its row from the pooled sum."""
    import subprocess
    import sys
    import textwrap

    child = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        assert not jax.config.jax_enable_x64
        import flax.linen as nn
        import openembedding_tpu as embed
        from openembedding_tpu.export import StandaloneModel, export_standalone
        from openembedding_tpu.ops.id64 import np_split_ids

        class Tower(nn.Module):
            @nn.compact
            def __call__(self, embedded, dense_inputs):
                return nn.Dense(1)(embedded["emb"])[:, 0]

        BIG = (1 << 40) + (1 << 31) + 5
        layer = embed.Embedding(-1, 4, name="emb", capacity=64,
                                combiner="sum")
        model = embed.EmbeddingModel(Tower(), [layer])
        trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.1))
        ids64 = np.asarray([[BIG, 7]], np.int64)
        batch = {"sparse": {"emb": jnp.asarray(np_split_ids(ids64))},
                 "dense": None, "label": jnp.asarray([1.0])}
        state = trainer.init(batch)
        state, _ = trainer.jit_train_step()(state, batch)
        export_standalone(state, model, r"%(path)s")
        served = StandaloneModel.load(r"%(path)s", model=model)

        def p(ids):
            return np.asarray(served.predict(
                {"sparse": {"emb": np.asarray(ids, np.int64)}}))

        full = p([[BIG, 7]])
        # sum pooling: an explicit pad changes nothing; dropping BIG must
        with np.errstate(all="ignore"):
            assert np.allclose(full, p([[BIG, 7, -1]]), atol=1e-6), "pad leaked"
            assert not np.allclose(full, p([[7, -1]]), atol=1e-4), \\
                "BIG id's row was dropped from the pool (mask truncation)"

        # EmbeddingVariable ragged coercion must split 63-bit ids host-side:
        # truncation would alias BIG and BIG+2^32 onto one row
        spec = embed.embedding.EmbeddingSpec(name="v", input_dim=-1,
                                             output_dim=4, capacity=64)
        var = embed.EmbeddingVariable(spec, embed.SGD(learning_rate=0.1))
        rows = np.asarray(var.sparse_read([[BIG, BIG + (1 << 32)], [7]]))
        assert rows.shape == (2, 2, 4) and (rows[1, 1:] == 0).all()
        assert not np.allclose(rows[0, 0], rows[0, 1]), \\
            "63-bit ragged ids collided mod 2^32 (int64 truncation)"
        again = np.asarray(var.read_only_pull([[BIG]]))
        assert np.allclose(again[0, 0], rows[0, 0])
        print("CHILD OK")
    """) % {"path": str(tmp_path / "m")}
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "CHILD OK" in r.stdout, (
        r.stdout[-2000:], r.stderr[-2000:])


def test_variable_ragged_pull_push_roundtrip():
    """The reference pull/push contract with ragged input end to end:
    sparse_read(ragged) -> grads shaped like the padded rows ->
    push_gradients(SAME ragged ids) -> update_weights. Pad slots' grads go
    nowhere; real rows take exactly their own update."""
    spec = EmbeddingSpec(name="v", input_dim=VOCAB, output_dim=DIM)
    var = embed.EmbeddingVariable(spec, embed.SGD(learning_rate=1.0))
    seqs = [[1, 2, 3], [5]]
    rows = var.sparse_read(seqs)
    w0 = np.asarray(var.state.weights).copy()
    grads = np.ones(np.asarray(rows).shape, np.float32)
    var.push_gradients(seqs, grads)
    var.update_weights()
    w1 = np.asarray(var.state.weights)
    for r in (1, 2, 3, 5):
        np.testing.assert_allclose(w1[r], w0[r] - 1.0, rtol=1e-6)
    touched = np.zeros(VOCAB, bool)
    touched[[1, 2, 3, 5]] = True
    np.testing.assert_array_equal(w1[~touched], w0[~touched])


def test_combiner_export_serving_roundtrip(tmp_path):
    """export_standalone -> StandaloneModel.predict pools multivalent features
    exactly like the trainer's eval step (incl. request-bucket batch padding)."""
    from openembedding_tpu.export import StandaloneModel, export_standalone

    rng = np.random.default_rng(13)
    layer = embed.Embedding(VOCAB, DIM, name="emb", combiner="mean")
    model = embed.EmbeddingModel(PooledDense(), [layer])
    trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.1))
    batch, _ = ragged_batch(rng, batch=6)  # 6 -> pads to the 8-bucket
    state = trainer.init(batch)
    state, _ = trainer.jit_train_step()(state, batch)
    want = np.asarray(trainer.jit_eval_step()(state, batch)["logits"])
    path = str(tmp_path / "standalone")
    export_standalone(state, model, path)
    served = StandaloneModel.load(path, model=model)
    got = np.asarray(served.predict(
        {"sparse": {k: np.asarray(v) for k, v in batch["sparse"].items()}}))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_combiner_composes_with_host_offload():
    """Multivalent pooling over a host-cached (>HBM) hash table: ragged
    batches drive offload_train_many (union admission + fused scan), and the
    eval pooling matches the valid-prefix numpy oracle — a cache path that
    admitted or pooled pad slots would break the equality, not just
    finiteness."""
    layer = embed.Embedding(-1, DIM, name="emb", capacity=512,
                            storage="host_cached", combiner="mean")
    model = embed.EmbeddingModel(PooledDense(), [layer])
    trainer = embed.Trainer(model, optimizer=embed.Adagrad(learning_rate=0.1))

    pairs = [ragged_hash_batch(s, id_space=1 << 40) for s in (1, 2)]
    batches, lens0 = [p[0] for p in pairs], pairs[0][1]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    state = trainer.init(batches[0])
    state, m = trainer.offload_train_many(state, stacked)
    assert np.isfinite(np.asarray(m["loss"])).all()
    assert trainer.offload["emb"].resident_count > 0
    # pooled eval == mean over the valid prefix of the raw cached-table pull
    feed = batches[0]["sparse"]["emb"]
    raw = np.asarray(trainer.table_lookup(
        model.specs["emb"], state.tables["emb"], feed))
    got = np.asarray(trainer.jit_eval_step()(state, batches[0])["logits"])
    dense = state.dense_params["Dense_0"]
    pooled = np.stack([raw[r, :lens0[r]].mean(0) for r in range(B)])
    want = pooled @ np.asarray(dense["kernel"]) + np.asarray(dense["bias"])
    np.testing.assert_allclose(got, want[:, 0], rtol=1e-5, atol=1e-6)


def test_randomized_combiner_parity_sweep():
    """Randomized breadth for the pooling path: {combiner} x {array, hash} x
    random (batch, width, vocab, lengths incl. all-pad rows) — every config's
    eval must match the numpy varlen oracle computed from the raw pull. A
    masking/validity bug anywhere in the lookup->combine->dense chain shows
    up as a value mismatch, not a shape error."""
    rng = np.random.default_rng(2024)
    for trial in range(12):
        combiner = ["sum", "mean", "sqrtn"][trial % 3]
        hashed = bool(trial % 2)
        batch_n = int(rng.integers(2, 12))
        width = int(rng.integers(1, 7))
        vocab = int(rng.integers(16, 200))
        layer = (embed.Embedding(-1, DIM, name="emb", capacity=512,
                                 combiner=combiner) if hashed
                 else embed.Embedding(vocab, DIM, name="emb",
                                      combiner=combiner))
        model = embed.EmbeddingModel(PooledDense(), [layer])
        trainer = embed.Trainer(model, optimizer=embed.SGD(learning_rate=0.1),
                                seed=trial)
        ids = np.full((batch_n, width), -1, np.int64)
        lens = rng.integers(0, width + 1, size=(batch_n,))  # 0 = all-pad row
        if (lens == 0).all():
            lens[0] = 1  # at least one real id in the batch
        for r, ln in enumerate(lens):
            ids[r, :ln] = rng.integers(0, vocab, size=(ln,))
        batch = {"sparse": {"emb": jnp.asarray(ids)}, "dense": None,
                 "label": jnp.asarray((lens % 2).astype(np.float32))}
        state = trainer.init(batch)
        state, m = trainer.jit_train_step()(state, batch)
        assert np.isfinite(float(m["loss"])), (trial, combiner, hashed)
        raw = np.asarray(trainer.table_lookup(
            model.specs["emb"], state.tables["emb"], jnp.asarray(ids)))
        got = np.asarray(trainer.jit_eval_step()(state, batch)["logits"])
        pooled = np_pool_rows(raw, lens, combiner)
        dense = state.dense_params["Dense_0"]
        want = pooled @ np.asarray(dense["kernel"]) + np.asarray(dense["bias"])
        np.testing.assert_allclose(
            got, want[:, 0], rtol=1e-5, atol=1e-6,
            err_msg=f"trial {trial}: {combiner} hashed={hashed} "
                    f"B={batch_n} W={width} V={vocab}")


def np_pool_rows(raw, lens, combiner):
    """Varlen-pool pre-pulled rows (B, W, d) over each row's valid prefix."""
    out = np.zeros((raw.shape[0], raw.shape[-1]), np.float32)
    for r, ln in enumerate(lens):
        if ln == 0:
            continue
        rows = raw[r, :ln]
        out[r] = (rows.sum(0) if combiner == "sum"
                  else rows.mean(0) if combiner == "mean"
                  else rows.sum(0) / np.sqrt(ln))
    return out
