"""Async persistence (PMem-equivalent) tests: commit protocol, crash consistency,
pending-window backpressure, policy, restore (reference: `pmem_c_api_test.cpp`,
`pmem_embedding_table_test.cpp`, AutoPersist in `test/benchmark/criteo_deepctr.py`)."""

import os
import shutil
import time

import jax
import numpy as np
import pytest

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.persist import (AsyncPersister, PersistPolicy,
                                       latest_persist, list_persists,
                                       restore_server_model)

VOCAB = 1 << 10


@pytest.fixture()
def setup():
    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=6, seed=1))
    state = trainer.init(batches[0])
    return model, trainer, state, batches


def test_policy_steps_and_seconds():
    p = PersistPolicy(every_steps=10)
    assert not p.should_persist(5)
    assert p.should_persist(10)
    p.mark(10)
    assert not p.should_persist(15)
    assert p.should_persist(20)
    pt = PersistPolicy(every_seconds=0.05)
    assert not pt.should_persist(1)
    time.sleep(0.06)
    assert pt.should_persist(1)
    with pytest.raises(ValueError):
        PersistPolicy()


def test_persist_restore_round_trip(setup, tmp_path):
    model, trainer, state, batches = setup
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    with AsyncPersister(trainer, model, root, window=2, keep=10,
                        policy=PersistPolicy(every_steps=2)) as p:
        persisted_steps = []
        for b in batches:
            state, _ = step(state, b)
            if p.maybe_persist(state):
                persisted_steps.append(int(state.step))
        p.wait()
        expect_w = np.asarray(state.tables["categorical"].weights)
    assert persisted_steps == [2, 4, 6]
    assert [s for s, _ in list_persists(root)] == [2, 4, 6]

    fresh = trainer.init(batches[0])
    restored = restore_server_model(fresh, model, root, trainer=trainer)
    assert int(restored.step) == 6
    np.testing.assert_array_equal(
        np.asarray(restored.tables["categorical"].weights), expect_w)


def test_uncommitted_persist_ignored(setup, tmp_path):
    model, trainer, state, batches = setup
    root = str(tmp_path / "persist")
    step = trainer.jit_train_step()
    state, _ = step(state, batches[0])
    with AsyncPersister(trainer, model, root, window=1,
                        policy=PersistPolicy(every_steps=1)) as p:
        p.persist(state)
    # fake a crash mid-write: newer dir without COMMIT marker
    committed = latest_persist(root)
    crashed = os.path.join(root, "persist_000000000099")
    shutil.copytree(committed, crashed)
    os.unlink(os.path.join(crashed, "COMMIT"))
    assert latest_persist(root) == committed  # step 99 not eligible
    restored = restore_server_model(trainer.init(batches[0]), model, root,
                                    trainer=trainer)
    assert int(restored.step) == 1


def test_gc_keeps_last_k(setup, tmp_path):
    model, trainer, state, batches = setup
    root = str(tmp_path / "persist")
    step = trainer.jit_train_step()
    with AsyncPersister(trainer, model, root, window=1, keep=2,
                        policy=PersistPolicy(every_steps=1)) as p:
        for b in batches[:5]:
            state, _ = step(state, b)
            p.persist(state)
            p.wait()  # serialize so gc sees each commit
    steps = [s for s, _ in list_persists(root)]
    assert steps == [4, 5]


def test_repersist_same_step_supersedes(setup, tmp_path):
    """A restarted run re-reaching a step must overwrite the old persist of that
    step (committed or crash-leftover), not die with ENOTEMPTY."""
    model, trainer, state, batches = setup
    root = str(tmp_path / "persist")
    step = trainer.jit_train_step()
    state, _ = step(state, batches[0])
    for _ in range(2):  # second pass hits the existing committed persist_1 dir
        with AsyncPersister(trainer, model, root, window=1,
                            policy=PersistPolicy(every_steps=1)) as p:
            p.persist(state)
    assert [s for s, _ in list_persists(root)] == [1]
    restored = restore_server_model(trainer.init(batches[0]), model, root,
                                    trainer=trainer)
    assert int(restored.step) == 1


def test_restore_without_persist_raises(setup, tmp_path):
    model, trainer, state, _ = setup
    with pytest.raises(FileNotFoundError):
        restore_server_model(state, model, str(tmp_path / "empty"),
                             trainer=trainer)


def test_writer_error_propagates(setup, tmp_path):
    model, trainer, state, batches = setup
    root = str(tmp_path / "persist")
    step = trainer.jit_train_step()
    state, _ = step(state, batches[0])
    p = AsyncPersister(trainer, model, root, window=1,
                       policy=PersistPolicy(every_steps=1))
    try:
        # poison the root: writer's os.replace onto a file must fail
        p.persist(state)
        p._q.join()
        target = os.path.join(root, "persist_000000000002")
        os.makedirs(os.path.dirname(target), exist_ok=True)
        state, _ = step(state, batches[1])
        with open(target, "w") as f:
            f.write("in the way")
        p.persist(state)
        p._q.join()
        with pytest.raises(RuntimeError, match="async persist failed"):
            p._raise_pending_error()
    finally:
        p._error = None
        p.close()


def test_snapshot_isolated_from_donation(setup, tmp_path):
    """persist() must copy to host before returning: the next step donates the
    state's buffers, and the async write must still see the OLD values."""
    model, trainer, state, batches = setup
    root = str(tmp_path / "persist")
    step = trainer.jit_train_step()
    state, _ = step(state, batches[0])
    want = np.asarray(state.tables["categorical"].weights).copy()
    with AsyncPersister(trainer, model, root, window=2,
                        policy=PersistPolicy(every_steps=1)) as p:
        p.persist(state)
        for b in batches[1:]:  # donates + mutates the tables while write runs
            state, _ = step(state, b)
        p.wait()
    restored = restore_server_model(trainer.init(batches[0]), model, root,
                                    trainer=trainer)
    # the persist captured step-1 state, untouched by later steps
    assert int(restored.step) == 1
    np.testing.assert_array_equal(
        np.asarray(restored.tables["categorical"].weights), want)


# -- incremental (dirty-window) persistence ----------------------------------


def _state_equal(a, b):
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _dir_bytes(path):
    total = 0
    for dirpath, _, files in os.walk(path):
        for f in files:
            total += os.path.getsize(os.path.join(dirpath, f))
    return total


def test_incremental_restore_equals_live_state(setup, tmp_path):
    """base + delta replay == the live state, bit for bit (rows, slots, dense
    params, dense optimizer slots, step, model_version)."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    model, trainer, state, batches = setup
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2, keep=10,
                              policy=PersistPolicy(every_steps=2),
                              full_every=100) as p:
        for b in batches:
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()
    # first persist is the full base; the rest are deltas
    assert [s for s, _ in list_persists(root)] == [2]
    assert [s for s, _ in list_deltas(root)] == [4, 6]

    fresh = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, model, root, trainer=fresh)
    _state_equal(fstate, state)


def test_incremental_bytes_proportional_to_touched(tmp_path):
    """The round-4 review's acceptance: delta bytes scale with TOUCHED rows, not the
    table. A 2^16-row table trained on batches touching ~64 ids must produce
    deltas orders of magnitude smaller than the full base persist."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    big_vocab = 1 << 16
    model = make_deepfm(vocabulary=big_vocab, dim=4, hidden=(8,))
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    # every batch draws from a 64-id hot set: the dirty window stays tiny
    rng = np.random.default_rng(7)
    hot = rng.integers(0, big_vocab, size=64)
    batches = []
    for i in range(4):
        ids = hot[rng.integers(0, 64, size=(16, 26))].astype(np.int32)
        batches.append({"sparse": {"categorical": ids},
                        "label": rng.random(16).astype(np.float32)})
    state = trainer.init(batches[0])
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2, keep=10,
                              policy=PersistPolicy(every_steps=1),
                              full_every=100) as p:
        for b in batches:
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()

    fulls = list_persists(root)
    deltas = list_deltas(root)
    assert len(fulls) == 1 and len(deltas) == 3
    full_bytes = _dir_bytes(fulls[0][1])
    for _, dpath in deltas:
        dbytes = _dir_bytes(dpath)
        # 64 rows x (4 weights + 4 slots + id) vs 2^16 rows: >100x smaller
        assert dbytes * 100 < full_bytes, (dbytes, full_bytes)

    fresh = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, model, root, trainer=fresh)
    _state_equal(fstate, state)


def test_incremental_uncommitted_delta_ignored(setup, tmp_path):
    """Crash consistency down the chain: a delta without COMMIT (and anything
    after it) is not replayed — restore lands on the last consistent prefix."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    model, trainer, state, batches = setup
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    states = {}
    with IncrementalPersister(trainer, model, root, window=2, keep=10,
                              policy=PersistPolicy(every_steps=2),
                              full_every=100) as p:
        for b in batches:
            state, _ = step(state, b)
            if p.maybe_persist(state, batch=b):
                p.wait()
                states[int(state.step)] = jax.device_get(state)
    # simulate a crash mid-write of the last delta: drop its COMMIT
    last_step, last_path = list_deltas(root)[-1]
    os.remove(os.path.join(last_path, "COMMIT"))

    fresh = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, model, root, trainer=fresh)
    assert int(fstate.step) == 4  # the consistent prefix: base(2) + delta(4)
    _state_equal(fstate, states[4])


def test_incremental_full_every_and_gc(setup, tmp_path):
    """A scheduled full persist supersedes the chain: older deltas are GC'd,
    restore uses the new base alone."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    model, trainer, state, batches = setup
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2, keep=10,
                              policy=PersistPolicy(every_steps=1),
                              full_every=2) as p:
        for b in batches:  # persists at steps 1..6; fulls at 1, 4 (2 deltas each)
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()
    full_steps = [s for s, _ in list_persists(root)]
    delta_steps = [s for s, _ in list_deltas(root)]
    assert full_steps[-1] == 4
    assert all(d > 4 for d in delta_steps), (full_steps, delta_steps)

    fresh = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, model, root, trainer=fresh)
    assert int(fstate.step) == 6
    _state_equal(fstate, jax.device_get(state))


def test_incremental_unobserved_window_falls_back_to_full(setup, tmp_path):
    """Steps advancing without observe() must NOT silently persist stale
    deltas: warn + full persist."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    model, trainer, state, batches = setup
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2,
                              policy=PersistPolicy(every_steps=1),
                              full_every=100) as p:
        state, _ = step(state, batches[0])
        p.maybe_persist(state, batch=batches[0])  # full base
        state, _ = step(state, batches[1])
        with pytest.warns(RuntimeWarning, match="observed"):
            p.maybe_persist(state)  # no batch, no observe -> full + warning
        p.wait()
    assert [s for s, _ in list_persists(root)] == [1, 2]
    assert list_deltas(root) == []


def test_incremental_pair_keys_x64_off(tmp_path):
    """The dirty window under the default config (x64 off, split-pair hash
    keys): tracker ids are int64 host-side, the row reader/writer speak the
    pair layout."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas
    from openembedding_tpu.initializers import Constant
    import dataclasses

    with jax.enable_x64(False):
        model = make_deepfm(vocabulary=-1, dim=4, hidden=(8,), hashed=True,
                            capacity=4096)
        model.specs["categorical"] = dataclasses.replace(
            model.specs["categorical"], initializer=Constant(0.0))
        trainer = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
        batches = list(synthetic_criteo(16, id_space=1 << 62, steps=4, seed=2,
                                        ids_dtype="pair"))
        state = trainer.init(batches[0])
        assert state.tables["categorical"].keys.ndim == 2
        step = trainer.jit_train_step()
        root = str(tmp_path / "persist")
        with IncrementalPersister(trainer, model, root, window=2,
                                  policy=PersistPolicy(every_steps=1),
                                  full_every=100) as p:
            for b in batches:
                state, _ = step(state, b)
                p.maybe_persist(state, batch=b)
            p.wait()
        assert len(list_deltas(root)) == 3

        fresh = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=0)
        fstate = fresh.init(batches[0])
        fstate = restore_server_model(fstate, model, root, trainer=fresh)
        assert int(fstate.step) == 4
        # rows must match by id (slot layouts may differ between the restored
        # insert order and the live table's) — read through the model's pull
        from openembedding_tpu.embedding import lookup
        from openembedding_tpu.ops.id64 import np_ids_as_int64, np_split_ids
        ids = np.unique(np.concatenate(
            [np_ids_as_int64(b["sparse"]["categorical"]) for b in batches]))
        pair = jax.numpy.asarray(np_split_ids(ids))
        spec = model.specs["categorical"]
        np.testing.assert_array_equal(
            np.asarray(lookup(spec, fstate.tables["categorical"], pair)),
            np.asarray(lookup(spec, state.tables["categorical"], pair)))


def test_incremental_mesh_array_table(tmp_path):
    """Dirty-window persist on an 8-device mesh (array table): delta rows
    address through the shard-major layout, restore replays onto the sharded
    state bit-for-bit."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                          mesh=make_mesh())
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=6, seed=1))
    state = trainer.init(batches[0])
    step = trainer.jit_train_step(batches[0], state)
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2, keep=10,
                              policy=PersistPolicy(every_steps=2),
                              full_every=100) as p:
        for b in batches:
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()
    assert [s for s, _ in list_persists(root)] == [2]
    assert [s for s, _ in list_deltas(root)] == [4, 6]

    fresh = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                        mesh=make_mesh())
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, model, root, trainer=fresh)
    _state_equal(fstate, state)
    # the restored state really trains (shardings intact)
    fstep = fresh.jit_train_step(batches[0], fstate)
    fstate, m = fstep(fstate, batches[0])
    assert np.isfinite(float(m["loss"]))


def test_incremental_mesh_hash_table(tmp_path):
    """Same on a HASHED model: per-shard probe for the touched-row read,
    sharded find-or-insert on replay. Rows must match by id (slot layouts
    may differ between live insertion order and replay order)."""
    import dataclasses
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from openembedding_tpu.initializers import Constant
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.parallel.sharded import sharded_lookup
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    def build():
        m = make_deepfm(vocabulary=-1, dim=4, hidden=(8,), hashed=True,
                        capacity=4096)
        m.specs["categorical"] = dataclasses.replace(
            m.specs["categorical"], initializer=Constant(0.0))
        return m

    model = build()
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                          mesh=make_mesh())
    batches = list(synthetic_criteo(16, id_space=1 << 40, steps=6, seed=2))
    state = trainer.init(batches[0])
    step = trainer.jit_train_step(batches[0], state)
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2,
                              policy=PersistPolicy(every_steps=2),
                              full_every=100) as p:
        for b in batches:
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()
    assert len(list_deltas(root)) == 2

    fresh_model = build()
    fresh = MeshTrainer(fresh_model, embed.Adagrad(learning_rate=0.05),
                        seed=0, mesh=make_mesh())
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, fresh_model, root, trainer=fresh)
    assert int(np.asarray(fstate.step)) == 6

    ids = np.unique(np.concatenate(
        [b["sparse"]["categorical"].reshape(-1) for b in batches]))
    spec = model.specs["categorical"]

    def pull_rows(tr, st):
        pull = jax.jit(jax.shard_map(
            partial(sharded_lookup, spec, axis=tr.axis),
            mesh=tr.mesh,
            in_specs=(tr._table_pspec(spec), P()),
            out_specs=P(), check_vma=False))
        import jax.numpy as jnp
        return np.asarray(pull(st.tables["categorical"], jnp.asarray(ids)))

    np.testing.assert_array_equal(pull_rows(fresh, fstate),
                                  pull_rows(trainer, state))


def test_sharded_delta_restore_without_trainer(tmp_path):
    """Serving-side restore: a delta chain replays onto a SHARDED state with
    NO trainer in the process — the mesh/axis/pspecs are recovered from the
    state's own NamedShardings (`persist._StateMeshShim`), and the result is
    bit-identical to the trainer-driven restore. (Until round 5 this case
    raised; the reference restores per server node with no worker attached,
    `EmbeddingRestoreOperator.cpp:108-152`.)"""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.persist import IncrementalPersister

    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                          mesh=make_mesh())
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=4, seed=3))
    state = trainer.init(batches[0])
    step = trainer.jit_train_step(batches[0], state)
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2,
                              policy=PersistPolicy(every_steps=2),
                              full_every=100) as p:
        for b in batches:
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()

    fresh = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                        mesh=make_mesh())
    fstate = fresh.init(batches[0])
    fstate = restore_server_model(fstate, model, root)  # trainer omitted
    _state_equal(fstate, state)
    oracle = restore_server_model(
        MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                    mesh=make_mesh()).init(batches[0]),
        model, root, trainer=trainer)
    _state_equal(fstate, oracle)


def test_shard_row_reader_matches_direct_read(tmp_path):
    """`_make_shard_row_reader` (the multi-process delta read: per-shard
    outputs, no cross-shard psum) must agree with the replicated-output
    mesh reader on the same table — every touched row found exactly once,
    in the shard that owns it."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.persist import (_make_mesh_row_reader,
                                           _make_shard_row_reader)

    model = make_deepfm(vocabulary=-1, dim=4, hidden=(8,), hashed=True,
                        capacity=4096)
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=0,
                          mesh=make_mesh())
    batches = list(synthetic_criteo(16, id_space=1 << 40, steps=3, seed=4))
    state = trainer.init(batches[0])
    step = trainer.jit_train_step(batches[0], state)
    for b in batches:
        state, _ = step(state, b)
    spec = model.specs["categorical"]
    ts = state.tables["categorical"]

    ids64 = np.unique(np.concatenate(
        [b["sparse"]["categorical"].reshape(-1) for b in batches]))
    n = ids64.size
    padded = 1 << (n - 1).bit_length()
    ids_h = np.concatenate([ids64, np.full((padded - n,), -1, np.int64)])
    ids_dev = ids_h.astype(ts.keys.dtype) if ts.keys.ndim == 1 else None
    if ids_dev is None:
        from openembedding_tpu.ops.id64 import np_split_ids
        ids_dev = np_split_ids(ids_h)

    pspec = trainer._table_pspec(spec)
    found_r, w_r, s_r = _make_mesh_row_reader(
        trainer.mesh, trainer.axis, pspec)(ts, ids_dev)
    found_s, w_s, s_s = _make_shard_row_reader(
        trainer.mesh, trainer.axis, pspec, True, spec.input_dim)(ts, ids_dev)

    S = trainer.num_shards
    fs = np.asarray(found_s).reshape(S, padded)
    ws = np.asarray(w_s).reshape(S, padded, -1)
    assert (fs.sum(axis=0) <= 1).all(), "an id found in more than one shard"
    np.testing.assert_array_equal(fs.any(axis=0), np.asarray(found_r))
    np.testing.assert_array_equal(ws.sum(axis=0), np.asarray(w_r))
    for k in s_r:
        np.testing.assert_array_equal(
            np.asarray(s_s[k]).reshape(S, padded, -1).sum(axis=0),
            np.asarray(s_r[k]))


def test_dirty_tracker_applies_batch_transform():
    """A model with `batch_transform` (shared-Embedding Keras conversions)
    synthesizes its table feature inside jit; the HOST-side tracker must run
    the same transform or its feature lookup KeyErrors (round-5 review
    regression)."""
    import jax.numpy as jnp

    from openembedding_tpu.persist import DirtyTracker

    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    feat = model.specs["categorical"].feature_name

    def transform(batch, _feat=feat):
        sp = dict(batch["sparse"])
        sp[_feat] = jnp.concatenate(
            [jnp.asarray(sp["site_a"]), jnp.asarray(sp["site_b"])], axis=1)
        return {**batch, "sparse": sp}

    model.batch_transform = transform
    tracker = DirtyTracker(model)
    batch = {"sparse": {"site_a": np.array([[1, 2]], np.int64),
                        "site_b": np.array([[3, 2, 7]], np.int64)},
             "dense": None, "label": np.zeros((1,), np.float32)}
    tracker.observe(batch)
    ids = tracker.take()["categorical"]
    np.testing.assert_array_equal(ids, [1, 2, 3, 7])


def test_dirty_tracker_window_semantics():
    """observe() accumulates per-batch uniques cheaply; take() returns the
    sorted cross-batch union and resets the window."""
    from openembedding_tpu.persist import DirtyTracker

    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    t = DirtyTracker(model)
    t.observe({"sparse": {"categorical": np.asarray([[5, 3], [9, 5]])}})
    t.observe({"sparse": {"categorical": np.asarray([[3, -1], [7, 7]])}})
    got = t.take()
    np.testing.assert_array_equal(got["categorical"], [3, 5, 7, 9])  # no -1
    assert t.take()["categorical"].size == 0  # window reset


def test_superseded_delta_gc_opt_out(setup, tmp_path):
    """`prune_deltas=False` keeps deltas a newer full has superseded — the
    retention opt-out for sync publishers that serve history to slow
    subscribers; the default prunes them (long online runs must not leak one
    directory per persist interval)."""
    from openembedding_tpu.persist import IncrementalPersister, list_deltas

    model, trainer, _state, batches = setup
    step = trainer.jit_train_step()
    for prune, expect_old_deltas in ((True, False), (False, True)):
        root = str(tmp_path / f"persist_{prune}")
        with IncrementalPersister(trainer, model, root, window=2, keep=10,
                                  policy=PersistPolicy(every_steps=1),
                                  full_every=2,
                                  prune_deltas=prune) as p:
            s = trainer.init(batches[0])  # the step donates its input state
            for b in batches:  # fulls at 1, 4; deltas at 2, 3, 5, 6
                s, _ = step(s, b)
                p.maybe_persist(s, batch=b)
                p.wait()  # serialize so gc sees each commit
        newest_full = list_persists(root)[-1][0]
        assert newest_full == 4
        old = [d for d, _ in list_deltas(root) if d <= newest_full]
        assert bool(old) == expect_old_deltas, (prune, old)
        # either way the replayable chain restores to the newest state
        restored = restore_server_model(trainer.init(batches[0]), model,
                                        root, trainer=trainer)
        assert int(restored.step) == 6


def test_delta_chain_broken_link_replays_prefix(setup, tmp_path):
    """Deleting a MIDDLE delta breaks the parent chain: restore replays only
    the consistent prefix (base + first delta), never skipping a link."""
    import shutil
    from openembedding_tpu.persist import (IncrementalPersister, delta_chain,
                                           list_deltas)

    model, trainer, state, batches = setup
    step = trainer.jit_train_step()
    root = str(tmp_path / "persist")
    with IncrementalPersister(trainer, model, root, window=2,
                              policy=PersistPolicy(every_steps=1),
                              full_every=100) as p:
        for b in batches[:4]:  # full base at 1, deltas at 2, 3, 4
            state, _ = step(state, b)
            p.maybe_persist(state, batch=b)
        p.wait()
    deltas = list_deltas(root)
    assert [s for s, _ in deltas] == [2, 3, 4]
    shutil.rmtree(deltas[1][1])  # delta_3 vanishes

    base, chain = delta_chain(root)
    assert base is not None
    assert [os.path.basename(c) for c in chain] == ["delta_000000000002"]
    restored = restore_server_model(trainer.init(batches[0]), model, root,
                                    trainer=trainer)
    assert int(restored.step) == 2  # the consistent prefix, not 4
