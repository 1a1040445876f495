"""Scan-fused multi-step training (jit_train_many) must equal step-by-step."""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.ops.sparse import apply_ladder
from openembedding_tpu.parallel import MeshTrainer, make_mesh
from openembedding_tpu.utils import metrics as _metrics

VOCAB = 1 << 10
K = 4


def _stack(batches):
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)


def test_train_many_matches_step_by_step():
    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=K, seed=3))

    tr = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    state_a = tr.init(batches[0])
    step = tr.jit_train_step()
    losses_a = []
    for b in batches:
        state_a, m = step(state_a, b)
        losses_a.append(float(m["loss"]))

    state_b = tr.init(batches[0])
    state_b, metrics = tr.jit_train_many()(state_b, _stack(batches))
    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses_a,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(state_a.tables["categorical"].weights),
        np.asarray(state_b.tables["categorical"].weights))
    assert int(state_b.step) == K


def test_mesh_train_many_matches_step_by_step():
    mesh = make_mesh()
    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=K, seed=5))

    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    state_a = tr.init(batches[0])
    step = tr.jit_train_step(batches[0], state_a)
    losses_a = []
    for b in batches:
        state_a, m = step(state_a, b)
        losses_a.append(float(m["loss"]))

    tr2 = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    state_b = tr2.init(batches[0])
    stacked = _stack(batches)
    state_b, metrics = tr2.jit_train_many(stacked, state_b)(state_b, stacked)
    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses_a,
                               rtol=1e-6, atol=1e-6)
    # scan body and standalone step may fuse differently (observed 7.5e-9
    # max abs on this container's CPU XLA) — near-ulp, not a protocol skew
    np.testing.assert_allclose(
        np.asarray(state_a.tables["categorical"].weights),
        np.asarray(state_b.tables["categorical"].weights),
        rtol=1e-5, atol=1e-7)


# -- the apply's load (ops/sparse.py "WHAT THE APPLY WORKS OVER"): each table's
# `apply_fill` / `apply_full_steps` ride the step's stats and the window's
# metrics, and fold to `sparse.apply_fill{table=}` / `sparse.apply_full_steps
# {table=}` --------------------------------------------------------------------

B_LOAD = 64
N_LOAD = B_LOAD * 26       # 1,664 positions: apply_ladder = (512, 896, 1280, 1664)
V_LOAD = 1 << 12


@pytest.fixture
def fresh_metrics(monkeypatch):
    """An empty registry, and the ladder's gate lifted: tables under
    `FAST_MEMORY_BYTES` (every table here) are otherwise left alone."""
    from openembedding_tpu.ops import sparse
    monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", 0)
    _metrics._REGISTRY.clear()
    yield _metrics
    _metrics._REGISTRY.clear()


def _batches_with_unique(counts, seed=0, vocab=V_LOAD):
    """One batch of 64 examples a count: its 1,664 ids hold exactly that many
    distinct rows."""
    rng = np.random.default_rng(seed)
    out = []
    for u in counts:
        pool = rng.permutation(vocab)[:u]
        ids = rng.permutation(np.concatenate([pool, rng.choice(pool, N_LOAD - u)]))
        out.append({"sparse": {"categorical": ids.reshape(B_LOAD, 26).astype(np.int32)},
                    "dense": rng.normal(size=(B_LOAD, 13)).astype(np.float32),
                    "label": rng.integers(0, 2, (B_LOAD,)).astype(np.float32)})
    return out


def _same_tables(sa, sb):
    assert set(sa.tables) == set(sb.tables)
    for name in sa.tables:
        a, b = sa.tables[name], sb.tables[name]
        np.testing.assert_array_equal(np.asarray(a.weights), np.asarray(b.weights))
        assert set(a.slots) == set(b.slots)
        for k in a.slots:
            np.testing.assert_array_equal(np.asarray(a.slots[k]),
                                          np.asarray(b.slots[k]))


@pytest.mark.parametrize("dim", [9, 64, 33])
def test_train_many_over_different_rungs_equals_the_step_loop(dim, fresh_metrics):
    """K batches that land on the four rungs (one id; 0.4; 0.7; all distinct):
    the packed scan (dim 9: one table; dim 64: two), the split scan (dim 33:
    not packable) and K `train_step` calls leave the same tables bit for bit,
    and the window's load is the host's count."""
    assert apply_ladder(N_LOAD) == (512, 896, 1280, N_LOAD)
    counts = [1, 666, 1165, N_LOAD]
    batches = _batches_with_unique(counts, seed=dim)
    model = make_deepfm(vocabulary=V_LOAD, dim=dim, hidden=(8,))
    tr = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    assert bool(tr._packed_layouts(tr.init(batches[0]))) == (dim != 33)

    state_a = tr.init(batches[0])
    step = tr.jit_train_step()
    for b, u in zip(batches, counts):
        state_a, m = step(state_a, b)
        for table in model.ps_specs():
            np.testing.assert_array_equal(
                np.asarray(m["stats"][f"{table}/apply_fill"]),
                np.float32(u) / np.float32(N_LOAD))
            assert int(m["stats"][f"{table}/apply_full_steps"]) == int(u > 1280)
    tr.record_step_stats(m)  # the last step: all distinct, the last rung
    rep = fresh_metrics.report()
    for table in model.ps_specs():
        assert rep[f'sparse.apply_fill{{table="{table}"}}'] == 1.0
        assert rep[f'sparse.apply_full_steps{{table="{table}"}}'] == 1.0

    state_b, mm = tr.jit_train_many()(tr.init(batches[0]), _stack(batches))
    _same_tables(state_a, state_b)
    assert set(mm["apply_fill"]) == set(model.ps_specs())
    for table in model.ps_specs():
        assert float(mm["apply_fill"][table]) == 1.0       # the fullest step
        assert int(mm["apply_full_steps"][table]) == 1     # steps on the last rung
    fresh_metrics._REGISTRY.clear()
    tr.record_window_stats(mm)
    rep = fresh_metrics.report()
    for table in model.ps_specs():
        assert rep[f'sparse.apply_fill{{table="{table}"}}'] == 1.0
        assert rep[f'sparse.apply_full_steps{{table="{table}"}}'] == 1.0


def test_apply_full_steps_counts_all_distinct_batches(fresh_metrics):
    model = make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,))
    tr = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    batches = _batches_with_unique([N_LOAD] * K, seed=4)
    many = tr.jit_train_many()
    state, mm = many(tr.init(batches[0]), _stack(batches))
    assert int(mm["apply_full_steps"]["categorical"]) == K
    tr.record_window_stats(mm)
    tr.record_window_stats(mm)  # the same window again: folded once
    assert fresh_metrics.report()[
        'sparse.apply_full_steps{table="categorical"}'] == K
    _, mm2 = many(state, _stack(batches))
    tr.record_window_stats(mm2)  # a counter: windows add up
    rep = fresh_metrics.report()
    assert rep['sparse.apply_full_steps{table="categorical"}'] == 2 * K
    assert rep['trainer.windows{fn="train_many"}'] == 2


def test_apply_load_on_the_benchmark_generator(fresh_metrics):
    """The benchmark's own generator at small size (Zipf 1.05): no step on
    the last rung, and the window's fill is the fullest step's host count."""
    from benchmark import generators
    batches = generators.zipf_criteo_batches(
        batch_size=B_LOAD, steps=K, id_space=V_LOAD, seed=2147483659,
        alpha=1.05, num_fields=26, dense_dim=13)
    shares = [np.unique(b["sparse"]["categorical"]).size / N_LOAD
              for b in batches]
    assert max(shares) <= 1280 / N_LOAD
    model = make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,))
    tr = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    _, mm = tr.jit_train_many()(tr.init(batches[0]), _stack(batches))
    assert int(mm["apply_full_steps"]["categorical"]) == 0
    np.testing.assert_allclose(float(mm["apply_fill"]["categorical"]),
                               max(shares), rtol=1e-6)
    tr.record_window_stats(mm)
    rep = fresh_metrics.report()
    assert rep['sparse.apply_fill{table="categorical"}'] == \
        pytest.approx(max(shares), rel=1e-6)
    assert rep['sparse.apply_full_steps{table="categorical"}'] == 0


@pytest.mark.parametrize("capacity_factor", [0.0, 1.0])
def test_mesh_window_carries_the_apply_load_beside_owner_fill(
        capacity_factor, fresh_metrics):
    """Four devices, 16 examples each (416 positions a device; exact mode
    hands the owner 4 x 416 slots and it compacts them to 416): every owner's
    re-dedup'd unique rows over its buffer, the fullest shard of the fullest
    step, next to `owner_fill`."""
    S, per = 4, 16
    n = per * 26
    assert len(apply_ladder(n)) == 4
    rng = np.random.default_rng(7)
    # ids under 1,024: an owner receives about 340 slots of its 416, every step
    ids = rng.integers(0, 1 << 10, (K, S * per, 26)).astype(np.int32)
    stacked = {"sparse": {"categorical": ids},
               "dense": rng.normal(size=(K, S * per, 13)).astype(np.float32),
               "label": rng.integers(0, 2, (K, S * per)).astype(np.float32)}
    model = make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,))
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                     mesh=make_mesh(jax.devices()[:S]),
                     capacity_factor=capacity_factor)
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    state = tr.init(one)
    state, mm = tr.jit_train_many(stacked, state)(state, stacked)
    fills = [max(np.unique(ids[k][ids[k] % S == d]).size for d in range(S)) / n
             for k in range(K)]
    if capacity_factor == 0.0:
        assert 0 < float(mm["owner_fill"]["categorical"]) <= 1.0
        assert int(mm["owner_full_steps"]["categorical"]) == 0
        np.testing.assert_allclose(float(mm["apply_fill"]["categorical"]),
                                   max(fills), rtol=1e-6)
    else:  # nothing to compact: the owner's buffer is the S buckets of the wire
        assert mm["owner_fill"] == {}
        assert 0 < float(mm["apply_fill"]["categorical"]) <= max(fills) + 1e-6
    assert int(mm["apply_full_steps"]["categorical"]) == 0
    # ... and the K steps leave the tables of the program with no ladder
    # (under PR 27's `lax.cond(view.fits, ...)` in exact mode), bit for bit
    from openembedding_tpu.ops import sparse
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sparse, "apply_ladder", lambda n: (n,))
        tr0 = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                          mesh=make_mesh(jax.devices()[:S]),
                          capacity_factor=capacity_factor)
        state0 = tr0.init(one)
        state0, m0 = tr0.jit_train_many(stacked, state0)(state0, stacked)
    assert int(jax.device_get(m0)["apply_full_steps"]["categorical"]) == 0
    _same_tables(state, state0)
    tr.record_window_stats(mm)
    rep = fresh_metrics.report()
    assert rep['sparse.apply_fill{table="categorical"}'] == \
        pytest.approx(float(mm["apply_fill"]["categorical"]))
    assert rep['sparse.apply_full_steps{table="categorical"}'] == 0
    # the step loop: a per-shard vector in the step's stats, folded the same
    step = tr.jit_train_step(one, state)
    state, ms = step(state, one)
    vec = np.asarray(ms["stats"]["categorical/apply_fill"])
    assert vec.shape == (S,) and 0 < vec.max() <= 1.0
    tr.record_step_stats(ms)
    assert fresh_metrics.report()['sparse.apply_fill{table="categorical"}'] == \
        pytest.approx(float(vec.max()))


class _ConcatTower(nn.Module):
    """A dense layer over every table's rows, flattened and concatenated."""

    @nn.compact
    def __call__(self, embedded, dense_inputs):
        x = jnp.concatenate([embedded[k].reshape(embedded[k].shape[0], -1)
                             for k in sorted(embedded)], axis=-1)
        return nn.Dense(1)(x)[:, 0]


def _two_table_batches():
    """Two batches for an array table "rows" and a hash table "keys", and
    the two stacked."""
    rng = np.random.default_rng(0)
    batches = [{"sparse": {"rows": rng.integers(0, VOCAB, (16, 4)).astype(np.int32),
                           "keys": rng.integers(0, 10_000, (16, 4)).astype(np.int64)},
                "dense": None,
                "label": rng.integers(0, 2, (16,)).astype(np.float32)}
               for _ in range(2)]
    return batches, jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if xs[0] is not None else None, *batches,
        is_leaf=lambda x: x is None)


def test_sparse_pulls_counts_each_table_once_a_trace(fresh_metrics):
    """`sparse.pulls{path=}`, counted where the pull is traced: the packed
    scan's array table shares one plan with its apply ("shared"), its hash
    table probes per position, and so does every table of the un-packed
    step."""
    model = embed.EmbeddingModel(_ConcatTower(), [
        embed.Embedding(VOCAB, 8, name="rows"),
        embed.Embedding(-1, 8, name="keys", capacity=512)])
    tr = Trainer(model, embed.Adagrad(learning_rate=0.05), seed=1)
    batches, stacked = _two_table_batches()
    state = tr.init(batches[0])
    assert set(tr._packed_layouts(state)) == {"rows", "keys"}
    shared, each = 'sparse.pulls{path="shared"}', 'sparse.pulls{path="per_position"}'
    assert shared not in fresh_metrics.report()     # `init` pulls nothing
    tr.jit_train_many().lower(state, stacked)
    assert (fresh_metrics.report()[shared], fresh_metrics.report()[each]) == (1, 1)
    tr.jit_train_many().lower(state, stacked)       # a second trace counts again
    assert (fresh_metrics.report()[shared], fresh_metrics.report()[each]) == (2, 2)
    tr.jit_train_step().lower(state, batches[0])    # the split layout: no plan
    assert (fresh_metrics.report()[shared], fresh_metrics.report()[each]) == (2, 4)


def test_owner_plans_counts_each_table_once_a_trace_on_the_mesh(
        fresh_metrics, monkeypatch):
    """`exchange.owner_plans{path=}`, counted where the owner's serve decides
    (`parallel/sharded.py` "THE OWNER PLANS ONCE A STEP"): the packed scan's
    array table plans at the serve and its apply takes the plan ("shared");
    its hash table probes per slot; so does every table of the un-packed
    step, of a pipelined scan (its rows are served a step early: a plan would
    be stale) and of a read-only pull. No plan is made but on the shared
    path."""
    from openembedding_tpu.ops import sparse
    from openembedding_tpu.parallel import sharded

    def trainer(**kw):
        model = embed.EmbeddingModel(_ConcatTower(), [
            embed.Embedding(VOCAB, 8, name="rows"),
            embed.Embedding(-1, 8, name="keys", capacity=2048)])
        return MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                           mesh=make_mesh(jax.devices()[:4]), **kw)
    batches, stacked = _two_table_batches()
    plans, taken = [], []
    real_plan, real_apply = sharded.plan_packed_rows, \
        sparse.sparse_apply_packed_table
    monkeypatch.setattr(sharded, "plan_packed_rows", lambda *a, **kw: (
        plans.append(1), real_plan(*a, **kw))[1])
    monkeypatch.setattr(sparse, "sparse_apply_packed_table", lambda *a, **kw: (
        taken.append(kw.get("plan") is not None), real_apply(*a, **kw))[1])

    def counts():
        rep = fresh_metrics.report()
        return tuple(rep.get('exchange.owner_plans{path="%s"}' % path, 0)
                     for path in ("shared", "per_slot"))

    tr = trainer()
    state = tr.init(batches[0])
    assert set(tr._packed_layouts(state)) == {"rows", "keys"}
    assert counts() == (0, 0)                       # `init` serves nothing
    tr.jit_train_many(stacked, state).lower(state, stacked)
    assert counts() == (1, 1)
    # "rows": its compact apply took the plan, its full-size one and the
    # hash table's two dedup for themselves
    assert plans and sorted(taken) == [False, False, False, True]
    trainer().jit_train_many(stacked, state).lower(state, stacked)
    assert counts() == (2, 2)                       # a second trace counts again
    made = len(plans)
    tr.jit_train_step(batches[0], state).lower(state, batches[0])
    assert counts() == (2, 4)                       # the split layout: no plan
    tr.jit_eval_step(batches[0], state).lower(state, batches[0])
    assert counts() == (2, 6)                       # `_serve_rows(train=False)`
    del taken[:]
    piped = trainer(pipeline_steps=True)
    piped.jit_train_many(stacked, state).lower(state, stacked)
    shared, per_slot = counts()
    assert shared == 2 and per_slot > 6             # prologue and body prefetch
    assert len(plans) == made and taken and not any(taken)


# -- the entry point accounts for itself: `jit_train_many()` returns the
# program's dispatch object (`model.TrainManyDispatch`), which publishes every
# window's counters without being asked and never waits on the device for one
# (`model._PendingWindows`) -----------------------------------------------------


class _CountingTower(nn.Module):
    """A tower that counts: per-step stats the trainer folds over a window."""

    window_stats = (("toy.rows", "sum"), ("toy.largest_logit", "max"))

    @nn.compact
    def __call__(self, embedded, dense_inputs=None, *, with_stats=False):
        x = jnp.concatenate([embedded[k].reshape(embedded[k].shape[0], -1)
                             for k in sorted(embedded)], axis=-1)
        logits = nn.Dense(1)(x)[:, 0]
        if not with_stats:
            return logits
        return logits, {"toy.rows": jnp.float32(x.shape[0]),
                        "toy.largest_logit": jnp.max(logits)}

    def apply_with_stats(self, variables, embedded, dense_inputs=None):
        return self.apply(variables, embedded, dense_inputs, with_stats=True)


def _window_series(rep):
    return {k: v for k, v in rep.items()
            if k.startswith(("sparse.apply_", "toy.", "trainer.windows"))}


def _three_windows(seed):
    """Three windows whose loads differ: all-distinct steps (the last rung)
    in the first and the last."""
    counts = ([N_LOAD, 700, N_LOAD, 1], [600, 5, 900, 40], [N_LOAD, 2, 3, 4])
    return [_stack(_batches_with_unique(c, seed=seed + i))
            for i, c in enumerate(counts)], 3


def _toy_or_deepfm(kind):
    if kind == "deepfm":
        return make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,))
    return embed.EmbeddingModel(_CountingTower(), [
        embed.Embedding(V_LOAD, 8, name="categorical")])


@pytest.mark.parametrize("kind", ["deepfm", "module_stats"])
def test_entry_point_publishes_every_window_without_being_asked(
        kind, fresh_metrics):
    """After dispatches of `jit_train_many()` and NO call of
    `record_window_stats`, the registry holds what an explicit fold of every
    window of the bare jitted scan gives."""
    windows, full_steps = _three_windows(seed=11)
    one = jax.tree_util.tree_map(lambda x: x[0], windows[0])

    def run(through_entry_point):
        fresh_metrics._REGISTRY.clear()
        tr = Trainer(_toy_or_deepfm(kind), embed.Adagrad(learning_rate=0.05),
                     seed=1)
        state = tr.init(one)
        many = tr.jit_train_many() if through_entry_point else \
            jax.jit(tr.train_many, donate_argnums=(0,))
        for w in windows:
            state, m = many(state, w)
            if not through_entry_point:
                tr.record_window_stats(m)
        return _window_series(fresh_metrics.report())

    asked, unasked = run(False), run(True)
    assert unasked == asked
    assert unasked['trainer.windows{fn="train_many"}'] == 3
    assert unasked['sparse.apply_full_steps{table="categorical"}'] == full_steps
    if kind == "module_stats":
        assert unasked["toy.rows"] == 3 * K * B_LOAD
        assert "toy.largest_logit" in unasked


def test_a_window_is_folded_once_whoever_asks(fresh_metrics):
    """The entry point folds, the caller asks too (before the fold and after
    it): `*_full_steps` and `trainer.windows` count each window once."""
    windows, full_steps = _three_windows(seed=21)
    tr = Trainer(make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,)),
                 embed.Adagrad(learning_rate=0.05), seed=1)
    state = tr.init(jax.tree_util.tree_map(lambda x: x[0], windows[0]))
    many = tr.jit_train_many()
    state, m1 = many(state, windows[0])
    tr.record_window_stats(m1)            # asked while it is pending
    state, m2 = many(state, windows[1])
    state, m3 = many(state, windows[2])
    rep = fresh_metrics.report()          # the read folds what is pending
    assert rep['trainer.windows{fn="train_many"}'] == 3
    for m in (m1, m2, m3):                # asked again, after the fold
        tr.record_window_stats(m)
    rep = fresh_metrics.report()
    assert rep['trainer.windows{fn="train_many"}'] == 3
    assert rep['sparse.apply_full_steps{table="categorical"}'] == full_steps
    assert full_steps == sum(int(m["apply_full_steps"]["categorical"])
                             for m in (m1, m2, m3))


class _NotReadyYet:
    """A device array of a window still running."""

    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready


def test_a_dispatch_does_not_wait_for_an_unready_window(fresh_metrics):
    from openembedding_tpu import model as model_mod
    pending = model_mod._WINDOWS
    assert len(pending) == 0
    folded = []
    leaf = _NotReadyYet()
    unready = {"apply_fill": {"categorical": leaf}}
    pending.sent(lambda w: folded.append(w), unready)
    windows, _ = _three_windows(seed=31)
    tr = Trainer(make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,)),
                 embed.Adagrad(learning_rate=0.05), seed=1)
    state = tr.init(jax.tree_util.tree_map(lambda x: x[0], windows[0]))
    many = tr.jit_train_many()
    try:
        state, m1 = many(state, windows[0])
        state, m2 = many(state, windows[1])
        jax.block_until_ready((m1, m2))
        # the oldest window is not ready: nothing was read, nothing folded,
        # the later (ready) windows wait their turn behind it
        assert folded == [] and len(pending) == 3
        assert 'trainer.windows{fn="train_many"}' not in fresh_metrics._REGISTRY
        leaf.ready = True
        state, m3 = many(state, windows[2])
        # ... and once it is, a dispatch folds it, and AT MOST that one
        assert folded == [unready] and len(pending) == 3
    finally:
        pending.drain()
    assert len(pending) == 0
    assert fresh_metrics.report()['trainer.windows{fn="train_many"}'] == 4


def test_the_queue_of_pending_windows_is_bounded(fresh_metrics, monkeypatch):
    """Nothing ever reads the registry and no window ever reads ready: the
    queue holds `LIMIT` windows, the oldest were folded (blocking) to make
    room."""
    from openembedding_tpu import model as model_mod
    pending = model_mod._WINDOWS
    assert len(pending) == 0
    monkeypatch.setattr(model_mod, "_leaves_ready", lambda window: False)
    windows, _ = _three_windows(seed=41)
    tr = Trainer(make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,)),
                 embed.Adagrad(learning_rate=0.05), seed=1)
    state = tr.init(jax.tree_util.tree_map(lambda x: x[0], windows[0]))
    many = tr.jit_train_many()
    sent = pending.LIMIT + 3
    for i in range(sent):
        state, _ = many(state, windows[i % 3])
        assert len(pending) <= pending.LIMIT
    assert len(pending) == pending.LIMIT
    acc = fresh_metrics._REGISTRY['trainer.windows{fn="train_many"}']
    assert acc.value() == 3
    assert fresh_metrics.report()['trainer.windows{fn="train_many"}'] == sent
    assert len(pending) == 0


def test_lower_and_traces_go_through_the_dispatch_object(fresh_metrics):
    from openembedding_tpu import model as model_mod
    windows, _ = _three_windows(seed=51)
    tr = Trainer(make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,)),
                 embed.Adagrad(learning_rate=0.05), seed=1)
    state = tr.init(jax.tree_util.tree_map(lambda x: x[0], windows[0]))
    many = tr.jit_train_many()
    assert isinstance(many, model_mod.TrainManyDispatch)
    text = many.lower(state, windows[0]).compile().as_text()
    assert "sparse.apply" in text
    # a trace of the object sends no window
    shapes = jax.eval_shape(many, state, windows[0])
    assert shapes[1]["loss"].shape == (K,)
    assert len(model_mod._WINDOWS) == 0
    assert 'trainer.windows{fn="train_many"}' not in fresh_metrics.report()


def test_a_window_that_cannot_be_read_costs_no_dispatch(fresh_metrics):
    """A window whose arrays were deleted under the queue goes uncounted, with
    an event in the flight recorder; the dispatch and the read go on."""
    from openembedding_tpu.utils import trace
    windows, _ = _three_windows(seed=61)
    tr = Trainer(make_deepfm(vocabulary=V_LOAD, dim=9, hidden=(8,)),
                 embed.Adagrad(learning_rate=0.05), seed=1)
    state = tr.init(jax.tree_util.tree_map(lambda x: x[0], windows[0]))
    many = tr.jit_train_many()
    state, m1 = many(state, windows[0])
    jax.block_until_ready(m1)
    for leaf in jax.tree_util.tree_leaves(m1):
        leaf.delete()
    state, m2 = many(state, windows[1])   # folds m1, which is gone
    assert float(m2["loss"][0]) == float(m2["loss"][0])  # the call came back
    rep = fresh_metrics.report()
    assert rep['trainer.windows{fn="train_many"}'] == 1
    errors = [e for e in trace.RECORDER.events()
              if (e.group, e.name) == ("trainer", "window_fold_error")]
    assert errors and "delete" in errors[-1].attrs["error"].lower()
