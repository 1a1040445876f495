"""Multi-device mesh tests on the virtual 8-device CPU mesh.

The TPU-native version of the reference's simulated-cluster tests (`entry/c_api_test.h`:
fork-based multi-process cluster, deterministic `test` optimizer, host-side replica
asserting exact equality; SURVEY.md §4)."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import openembedding_tpu as embed
from openembedding_tpu.embedding import EmbeddingSpec, EmbeddingTableState
from openembedding_tpu.parallel import (MeshTrainer, deinterleave_rows,
                                        interleave_rows, make_mesh,
                                        sharded_lookup)
from openembedding_tpu.parallel.sharded import (grouped_apply_gradients,
                                                grouped_lookup_train)

S = 8  # conftest forces 8 virtual CPU devices


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == S
    return make_mesh()


def shard_table(mesh, spec, opt, weights_id_major):
    """Build a sharded EmbeddingTableState from an id-major host array."""
    vocab, dim = weights_id_major.shape
    w = interleave_rows(jnp.asarray(weights_id_major), S)
    slots = opt.init_slots(w.shape[0], dim)
    state = EmbeddingTableState(weights=w, slots=slots, keys=None, overflow=None)
    from jax.sharding import NamedSharding
    shardings = EmbeddingTableState(
        weights=NamedSharding(mesh, P("data", None)),
        slots={k: NamedSharding(mesh, P("data", None)) for k in slots},
        keys=None, overflow=None)
    return jax.device_put(state, shardings)


def test_interleave_roundtrip():
    w = jnp.arange(20 * 3, dtype=jnp.float32).reshape(20, 3)
    inter = interleave_rows(w, 4)
    # shard-major layout: row (s*rps + r) holds id r*4+s; row 5 = shard 1 local 0 = id 1
    np.testing.assert_array_equal(np.asarray(inter[0]), np.asarray(w[0]))
    np.testing.assert_array_equal(np.asarray(inter[5]), np.asarray(w[1]))
    back = deinterleave_rows(inter, 4, 20)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(w))


def test_sharded_lookup_matches_gather(mesh):
    """Pull through the a2a protocol == plain jnp.take on the id-major table."""
    rng = np.random.default_rng(0)
    vocab, dim, B = 64, 4, 16 * S
    table = rng.normal(size=(vocab, dim)).astype(np.float32)
    spec = EmbeddingSpec(name="v", input_dim=vocab, output_dim=dim, variable_id=0)
    opt = embed.SGD(learning_rate=0.1)
    state = shard_table(mesh, spec, opt, table)
    ids = rng.integers(0, vocab, size=(B,))

    def f(state, ids):
        return sharded_lookup(spec, state, ids)

    out = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(EmbeddingTableState(weights=P("data", None),
                                      slots={"moment": P("data", None)},
                                      keys=None, overflow=None), P("data")),
        out_specs=P("data"), check_vma=False))(state, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), table[ids], rtol=1e-6)


@pytest.mark.parametrize("n_tables", [1, 2])
def test_sharded_train_pull_and_update_selfcheck(mesh, n_tables):
    """Reference-style self-checking workload: TestOptimizer + host replica, multiple
    rounds of pull/push/update with duplicate ids across devices, exact equality
    (`entry/c_api_test.h:32-182`). One exchange group of one table, and of two
    tables sharing the wire: each table against its own replica."""
    rng = np.random.default_rng(1)
    dim, per_dev = 4, 12
    vocabs = [48, 40][:n_tables]
    B = per_dev * S
    opt = embed.TestOptimizer(learning_rate=1.0, flip=100.0, init=0.0)
    specs = [EmbeddingSpec(name=f"v{t}", input_dim=vocab, output_dim=dim,
                           variable_id=t) for t, vocab in enumerate(vocabs)]
    host_w = [rng.normal(size=(vocab, dim)).astype(np.float32)
              for vocab in vocabs]
    states = [shard_table(mesh, spec, opt, w) for spec, w in zip(specs, host_w)]
    host_w = [w.copy() for w in host_w]
    host_flip = [np.zeros((vocab, 1), np.float32) for vocab in vocabs]

    table_spec = EmbeddingTableState(
        weights=P("data", None), slots={"flip_state": P("data", None)},
        keys=None, overflow=None)

    def step(states, ids_list, grads_list):
        states, rows, stats, plans = grouped_lookup_train(
            specs, states, ids_list, wire="fp32")
        states, push_stats = grouped_apply_gradients(
            specs, states, [opt] * n_tables, ids_list, grads_list,
            plans=plans, wire="fp32")
        return states, rows, [{**st, **pst}
                              for st, pst in zip(stats, push_stats)]

    jstep = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=([table_spec] * n_tables, P("data"), P("data")),
        out_specs=([table_spec] * n_tables, P("data"), P()),
        check_vma=False))

    for round_i in range(4):
        ids = [rng.integers(0, vocab, size=(B,)) for vocab in vocabs]
        grads = [rng.normal(size=(B, dim)).astype(np.float32) for _ in vocabs]
        states, rows, stats = jstep(states, [jnp.asarray(i) for i in ids],
                                    [jnp.asarray(g) for g in grads])
        for t in range(n_tables):
            # pull must have returned pre-update weights
            np.testing.assert_allclose(
                np.asarray(rows[t]), host_w[t][ids[t]], rtol=1e-5,
                err_msg=f"round {round_i} table {t} pull")
            assert int(stats[t]["pull_overflow"]) == 0
            # host replica update: per unique id, summed grads / count + flip
            for uid in np.unique(ids[t]):
                sel = ids[t] == uid
                g = grads[t][sel].sum(axis=0)
                count = sel.sum()
                host_flip[t][uid] = 100.0 - host_flip[t][uid]
                host_w[t][uid] += 1.0 * g / count + host_flip[t][uid]

    for state, w, vocab in zip(states, host_w, vocabs):
        final = deinterleave_rows(np.asarray(state.weights), S, vocab)
        np.testing.assert_allclose(np.asarray(final), w, rtol=1e-4, atol=1e-4)


def make_batch(rng, vocab, B, fields=3):
    ids = rng.integers(0, vocab, size=(B, fields))
    y = (ids.sum(axis=1) % 2).astype(np.float32)
    return {"sparse": {"emb": jnp.asarray(ids)}, "label": jnp.asarray(y)}


class TinyDense(nn.Module):
    @nn.compact
    def __call__(self, embedded, dense_inputs):
        parts = [embedded[k].reshape(embedded[k].shape[0], -1)
                 for k in sorted(embedded)]
        x = jnp.concatenate(parts, axis=-1)
        return nn.Dense(1)(x)[:, 0]


def test_mesh_trainer_end_to_end(mesh):
    """Full DP+sharded-table training on the mesh: loss decreases; stats flow."""
    rng = np.random.default_rng(0)
    vocab = 200
    layer = embed.Embedding(vocab, 8, name="emb")
    model = embed.EmbeddingModel(TinyDense(), [layer])
    trainer = MeshTrainer(model, optimizer=embed.Adagrad(learning_rate=0.05),
                          mesh=mesh)
    batch = make_batch(rng, vocab, 16 * S)
    state = trainer.init(batch)
    step = trainer.jit_train_step(batch, state)
    losses = []
    for _ in range(40):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses[::10]
    assert int(metrics["stats"]["emb/pull_indices"]) == 16 * S * 3
    ev = trainer.jit_eval_step(batch, state)(state, batch)
    assert np.isfinite(float(ev["loss"]))


def test_mesh_trainer_matches_single_device():
    """One-step exact equivalence of the Trainer composition: with identical initial
    dense params, the first step's embedding-row updates must be identical between
    the single-device Trainer and the MeshTrainer (the dense psum only diverges the
    dense params AFTER their own update, so step-0 row grads match exactly)."""
    rng = np.random.default_rng(3)
    vocab, dim, B = 32, 4, 8 * S
    ids = rng.integers(0, vocab, size=(B, 2))
    labels = rng.random(B).round().astype(np.float32)
    b = {"sparse": {"emb": jnp.asarray(ids)}, "label": jnp.asarray(labels)}

    def build(trainer_cls, loss_scale=1.0, **kw):
        layer = embed.Embedding(vocab, dim, name="emb",
                                embeddings_initializer=embed.Constant(0.1))
        model = embed.EmbeddingModel(
            TinyDense(), [layer],
            loss_fn=lambda lo, la: loss_scale * embed.model.binary_logloss(lo, la))
        return trainer_cls(model, optimizer=embed.Adagrad(learning_rate=0.1), **kw)

    # Mesh semantics (reference parity): each worker normalizes by its LOCAL batch and
    # grads are summed across workers — S x the global-mean gradient. The equivalent
    # single-device run scales its loss by S.
    tr1 = build(embed.Trainer, loss_scale=float(S))
    st1 = tr1.init(b)
    st1, m1 = jax.jit(tr1.train_step)(st1, b)

    tr2 = build(MeshTrainer, mesh=make_mesh())
    st2 = tr2.init(b)
    # same flax seed -> identical initial dense params (verify, then step)
    st2, m2 = tr2.jit_train_step(b, st2)(st2, b)

    w1 = np.asarray(st1.tables["emb"].weights)
    w2 = np.asarray(deinterleave_rows(st2.tables["emb"].weights, S, vocab))
    np.testing.assert_allclose(w2, w1, rtol=1e-5, atol=1e-6)
    a1 = np.asarray(st1.tables["emb"].slots["accum"])
    a2 = np.asarray(deinterleave_rows(st2.tables["emb"].slots["accum"], S, vocab))
    np.testing.assert_allclose(a2, a1, rtol=1e-5, atol=1e-6)
    # per-device loss pmean == global mean == (single-device scaled loss) / S
    np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]) / S, rtol=1e-5)


@pytest.mark.parametrize("seed,opt_name,dim,hashed,dup_heavy", [
    (11, "adam", 4, False, False),
    (12, "ftrl", 8, False, True),
    (13, "rmsprop", 4, True, False),
    (14, "adagrad", 8, True, True),
    (15, "momentum", 4, False, False),
    (16, "adamax", 4, True, True),
])
def test_mesh_matches_single_device_randomized(seed, opt_name, dim, hashed,
                                               dup_heavy):
    """Randomized breadth for the step-0 exchange parity: optimizer family ×
    row width × table kind × duplicate pressure, all seeded. Any mis-routed
    row, broken dedup-count, or optimizer-semantics drift in the sharded
    protocol shows up as a row mismatch against the single-device oracle."""
    opts = {"adam": lambda: embed.Adam(learning_rate=0.05),
            "ftrl": lambda: embed.Ftrl(learning_rate=0.1),
            "rmsprop": lambda: embed.RMSprop(learning_rate=0.05),
            "adagrad": lambda: embed.Adagrad(learning_rate=0.1),
            "momentum": lambda: embed.SGD(learning_rate=0.1, momentum=0.9),
            "adamax": lambda: embed.Adamax(learning_rate=0.05)}
    rng = np.random.default_rng(seed)
    vocab, B, F = 64, 8 * S, int(rng.integers(2, 5))
    id_pool = 6 if dup_heavy else vocab  # heavy duplicates stress counts
    ids = rng.integers(0, id_pool, size=(B, F))
    labels = rng.random(B).round().astype(np.float32)
    b = {"sparse": {"emb": jnp.asarray(ids)}, "label": jnp.asarray(labels)}

    def build(trainer_cls, loss_scale=1.0, **kw):
        layer = embed.Embedding(
            -1 if hashed else vocab, dim, name="emb",
            capacity=256 if hashed else 0,
            embeddings_initializer=embed.Constant(0.05))
        model = embed.EmbeddingModel(
            TinyDense(), [layer],
            loss_fn=lambda lo, la: loss_scale * embed.model.binary_logloss(
                lo, la))
        return trainer_cls(model, optimizer=opts[opt_name](), **kw)

    tr1 = build(embed.Trainer, loss_scale=float(S))
    st1 = tr1.init(b)
    st1, m1 = jax.jit(tr1.train_step)(st1, b)

    tr2 = build(MeshTrainer, mesh=make_mesh())
    st2 = tr2.init(b)
    st2, m2 = tr2.jit_train_step(b, st2)(st2, b)

    uniq = np.unique(ids.reshape(-1))
    r1 = np.asarray(tr1.table_lookup(
        tr1.model.specs["emb"], st1.tables["emb"], jnp.asarray(uniq)))

    from functools import partial
    from jax.sharding import PartitionSpec as P
    from openembedding_tpu.parallel.sharded import sharded_lookup
    spec2 = tr2.model.specs["emb"]
    pull = jax.jit(jax.shard_map(
        partial(sharded_lookup, spec2, axis=tr2.axis), mesh=tr2.mesh,
        in_specs=(tr2._table_pspec(spec2), P()), out_specs=P(),
        check_vma=False))
    ids2 = jnp.asarray(uniq)
    if st2.tables["emb"].keys is not None and st2.tables["emb"].keys.ndim == 2:
        from openembedding_tpu.ops.id64 import np_split_ids
        ids2 = jnp.asarray(np_split_ids(uniq.astype(np.int64)))
    r2 = np.asarray(pull(st2.tables["emb"], ids2))
    np.testing.assert_allclose(r2, r1, rtol=1e-5, atol=1e-6,
                               err_msg=f"{opt_name} dim{dim} hashed={hashed}")
    np.testing.assert_allclose(float(m2["loss"]), float(m1["loss"]) / S,
                               rtol=1e-5)


def test_mesh_hash_table_train(mesh):
    """Sharded hash-table variable trains end to end and surfaces overflow."""
    rng = np.random.default_rng(0)
    layer = embed.Embedding(-1, 8, name="emb", capacity=4096)
    model = embed.EmbeddingModel(TinyDense(), [layer])
    trainer = MeshTrainer(model, optimizer=embed.Adagrad(learning_rate=0.05),
                          mesh=mesh)
    # 63-bit-ish hashed ids
    ids = rng.integers(0, 2**62, size=(16 * S, 3), dtype=np.int64)
    batch = {"sparse": {"emb": jnp.asarray(ids)},
             "label": jnp.asarray((ids.sum(axis=1) % 2).astype(np.float32))}
    state = trainer.init(batch)
    step = trainer.jit_train_step(batch, state)
    losses = []
    for _ in range(30):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses[::10]
    assert int(state.tables["emb"].overflow) == 0
    inserted = int((np.asarray(state.tables["emb"].keys) >= 0).sum())
    expected_unique = len(np.unique(ids))
    assert inserted == expected_unique
