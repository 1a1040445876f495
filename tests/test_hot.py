"""Skew-aware hot-row replication (round 10): replicated heavy-hitter cache
on the sharded exchange (`parallel/sharded.py` "HOT-ROW REPLICATION",
`MeshTrainer(hot_rows=...)`).

Acceptance (ISSUE 5):
- fp32 parity: with OETPU_WIRE=fp32 a hot-enabled train step is BIT-EXACT vs
  hot-disabled on the same batches — losses, pulled rows, and (after
  `hot_sync`) weights and optimizer slots — on the per-table protocol, the
  fused grouped exchange, AND pair-key hash tables;
- persistence oblivious: checkpoints written by a hot-enabled trainer are
  byte-identical to the hot-off world's;
- Zipf e2e: `hot.hit_ratio` tracks the sketch-predicted coverage of the
  promoted set and `exchange.shard_imbalance` drops when the cache turns on;
- the default path stays free: hot_rows=0 attaches no cache state and traces
  no extra collectives (same 3-a2a-per-group program as before the feature).
"""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.model import EmbeddingModel
from openembedding_tpu.parallel import MeshTrainer, make_mesh
from openembedding_tpu.utils import metrics

S = 8  # conftest forces 8 virtual CPU devices
B = 64


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics._REGISTRY.clear()
    yield
    metrics._REGISTRY.clear()


class _Tower(nn.Module):
    """Two dim-8 tables (array + hash) -> logits (B,)."""

    @nn.compact
    def __call__(self, embedded, dense):
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        out = (jnp.sum(embedded["a"].astype(jnp.float32), axis=(1, 2))
               + jnp.sum(embedded["b"].astype(jnp.float32), axis=(1, 2)))
        return out + bias[0]


def _model(vocab=256):
    return EmbeddingModel(_Tower(), [
        embed.Embedding(vocab, 8, name="a"),
        embed.Embedding(-1, 8, name="b", capacity=4096),
    ])


def _batch(rng, vocab=256, hash_space=1 << 40, hash_dtype=np.int64):
    a = rng.integers(0, vocab, (B, 4)).astype(np.int32)
    b = rng.integers(0, hash_space, (B, 3)).astype(hash_dtype)
    # planted heavy hitters (duplicate-heavy so counts > 1 cross the push)
    a[:, 0] = np.array([7, 13])[rng.integers(0, 2, B)]
    b[:, 0] = hash_space - 13
    return {"sparse": {"a": a, "b": b},
            "label": rng.integers(0, 2, (B,)).astype(np.float32)}


_HOT_IDS = {"a": np.array([7, 13], np.int64),
            "b": np.array([(1 << 40) - 13], np.int64)}


def _train(trainer, batches, refresh_at=None, hot_ids=None):
    state = trainer.init(batches[0])
    step = trainer.jit_train_step(batches[0], state)
    losses, stats = [], None
    for i, b in enumerate(batches):
        if refresh_at is not None and i == refresh_at:
            state = trainer.refresh_hot_rows(state, hot_ids=hot_ids)
        state, m = step(state, b)
        losses.append(float(m["loss"]))
        stats = jax.device_get(m["stats"])
    return state, losses, stats


def _probe(trainer, state, name, probe_ids):
    """Read rows by id through the hot-aware sharded lookup."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from openembedding_tpu.parallel.sharded import sharded_lookup
    spec = trainer.model.specs[name]
    fn = jax.jit(jax.shard_map(
        partial(sharded_lookup, spec, axis=trainer.axis),
        mesh=trainer.mesh,
        in_specs=(trainer._table_pspec(spec), P()), out_specs=P(),
        check_vma=False))
    return np.asarray(fn(state.tables[name], jnp.asarray(probe_ids)))


def _assert_synced_tables_equal(s_off, s_on):
    for name in s_off.tables:
        t0, t1 = s_off.tables[name], s_on.tables[name]
        np.testing.assert_array_equal(np.asarray(t0.weights),
                                      np.asarray(t1.weights), err_msg=name)
        for k in t0.slots:
            np.testing.assert_array_equal(
                np.asarray(t0.slots[k]), np.asarray(t1.slots[k]),
                err_msg=f"{name}/{k}")
        if t0.keys is not None:
            np.testing.assert_array_equal(np.asarray(t0.keys),
                                          np.asarray(t1.keys), err_msg=name)


class _OneGroupPerTable(MeshTrainer):
    """The same exchange with every table alone on its wire (the model's two
    tables share one dim-group otherwise)."""

    def _exchange_groups(self, ps_specs):
        return [[n] for g in super()._exchange_groups(ps_specs) for n in g]


@pytest.mark.parametrize("grouped", [True, False])
def test_fp32_parity_hot_on_vs_off(grouped):
    """THE acceptance pin: hot-enabled training (promote mid-run, train
    across the refresh) is bit-exact vs hot-disabled at fp32 wire — losses
    every step, row reads by id, and the shard arrays (weights + optimizer
    slots + hash keys) after writeback. Covers the two tables fused on one
    wire AND each alone in its group."""
    rng = np.random.default_rng(1)
    batches = [_batch(rng) for _ in range(4)]
    trainer_cls = MeshTrainer if grouped else _OneGroupPerTable

    def run(hot_rows):
        tr = trainer_cls(_model(), embed.Adagrad(learning_rate=0.1),
                         mesh=make_mesh(), wire="fp32", hot_rows=hot_rows)
        state, losses, stats = _train(
            tr, batches, refresh_at=2 if hot_rows else None,
            hot_ids=_HOT_IDS)
        if hot_rows:
            state = tr.hot_sync(state)
        return tr, state, losses, stats

    tr0, s_off, l_off, _ = run(0)
    tr1, s_on, l_on, st_on = run(64)
    assert l_off == l_on
    # the cache actually served traffic (planted ids dominate the batches)
    assert int(st_on["a/hot_hits"]) > 0 and int(st_on["b/hot_hits"]) > 0
    assert float(st_on["a/hot_bytes_saved"]) > 0
    probes = {"a": np.arange(256, dtype=np.int32),
              "b": np.unique(np.concatenate(
                  [b["sparse"]["b"].reshape(-1) for b in batches]))}
    for name, ids in probes.items():
        np.testing.assert_array_equal(_probe(tr0, s_off, name, ids),
                                      _probe(tr1, s_on, name, ids),
                                      err_msg=name)
    _assert_synced_tables_equal(s_off, s_on)


def test_fp32_parity_pair_key_hash_tables():
    """x64-off: hash tables key in the split-pair uint32 layout; the hot
    probe, local gather, reduced push and writeback must all ride the pair
    machinery bit-exactly."""
    with jax.enable_x64(False):
        rng = np.random.default_rng(2)
        batches = [_batch(rng, hash_space=1 << 20, hash_dtype=np.int32)
                   for _ in range(3)]
        hot_ids = {"a": np.array([7, 13], np.int64),
                   "b": np.array([(1 << 20) - 13], np.int64)}

        def run(hot_rows):
            tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                             mesh=make_mesh(), wire="fp32",
                             hot_rows=hot_rows)
            state, losses, _ = _train(
                tr, batches, refresh_at=1 if hot_rows else None,
                hot_ids=hot_ids)
            assert state.tables["b"].keys.ndim == 2  # pair-keyed
            if hot_rows:
                assert state.tables["b"].hot.keys.ndim == 2
                state = tr.hot_sync(state)
            return tr, state, losses

        tr0, s_off, l_off = run(0)
        tr1, s_on, l_on = run(32)
        assert l_off == l_on
        _assert_synced_tables_equal(s_off, s_on)


def test_checkpoint_byte_identical_and_load_reattaches(tmp_path):
    """Persistence obliviousness: a hot-enabled trainer's checkpoint equals
    the hot-off world's byte for byte (hot rows write back into owner shards
    at save time); `MeshTrainer.load` re-attaches + re-gathers the cache, and
    training continues bit-exactly."""
    rng = np.random.default_rng(3)
    batches = [_batch(rng) for _ in range(4)]

    def run(hot_rows, path):
        tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                         mesh=make_mesh(), wire="fp32", hot_rows=hot_rows)
        state, _, _ = _train(tr, batches[:2],
                             refresh_at=1 if hot_rows else None,
                             hot_ids=_HOT_IDS)
        tr.save(state, str(path), model_sign="t")
        return tr, state

    tr0, s_off = run(0, tmp_path / "off")
    tr1, s_on = run(64, tmp_path / "on")
    import os
    for root, _dirs, files in os.walk(tmp_path / "off"):
        for fn in files:
            p_off = os.path.join(root, fn)
            p_on = p_off.replace(str(tmp_path / "off"), str(tmp_path / "on"))
            with open(p_off, "rb") as fa, open(p_on, "rb") as fb:
                a, b = fa.read(), fb.read()
            if fn == "model_meta":
                continue  # carries the save-time uuid sign; payloads matter
            assert a == b, f"checkpoint file differs: {fn}"

    # load into a FRESH hot-enabled trainer: cache re-attaches (empty set —
    # the pre-load state here is fresh) and refresh + training keep parity
    def resume(hot_rows, path):
        tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                         mesh=make_mesh(), wire="fp32", hot_rows=hot_rows)
        state = tr.init(batches[0])
        state = tr.load(state, str(path))
        if hot_rows:
            assert state.tables["a"].hot is not None
            state = tr.refresh_hot_rows(state, hot_ids=_HOT_IDS)
        step = tr.jit_train_step(batches[0], state)
        losses = []
        for b in batches[2:]:
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        return losses

    assert resume(0, tmp_path / "off") == resume(64, tmp_path / "on")


def test_incremental_persist_deltas_byte_identical(tmp_path):
    """The sync/delta feed stays oblivious too: `IncrementalPersister` deltas
    (touched-row payloads read straight off the shards) are byte-identical
    hot-on vs hot-off — the persister's hot_sync hook writes the cache back
    before every snapshot."""
    import os

    from openembedding_tpu.persist import IncrementalPersister, PersistPolicy
    rng = np.random.default_rng(4)
    batches = [_batch(rng) for _ in range(3)]

    def run(hot_rows, root):
        tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                         mesh=make_mesh(), wire="fp32", hot_rows=hot_rows)
        state = tr.init(batches[0])
        if hot_rows:
            state = tr.refresh_hot_rows(state, hot_ids=_HOT_IDS)
        step = tr.jit_train_step(batches[0], state)
        with IncrementalPersister(tr, tr.model, str(root), window=1,
                                  policy=PersistPolicy(every_steps=1),
                                  full_every=100) as p:
            for b in batches:
                state, _m = step(state, b)
                p.maybe_persist(state, batch=b)
            p.wait()

    run(0, tmp_path / "off")
    run(64, tmp_path / "on")
    delta_tables = []
    for root, _dirs, files in os.walk(tmp_path / "off"):
        for fn in files:
            if not fn.startswith("table_"):
                continue
            delta_tables.append(fn)
            p_off = os.path.join(root, fn)
            p_on = p_off.replace(str(tmp_path / "off"), str(tmp_path / "on"))
            a = np.load(p_off)
            b = np.load(p_on)
            assert sorted(a.files) == sorted(b.files), fn
            for k in a.files:
                np.testing.assert_array_equal(a[k], b[k],
                                              err_msg=f"{fn}:{k}")
    assert delta_tables  # the runs actually produced delta payloads


def test_zipf_hit_ratio_matches_sketch_coverage_and_imbalance_drops():
    """Zipf e2e acceptance: promote the sketch's top-K; the live
    `hot.hit_ratio` gauge must track the sketch-predicted coverage of that
    set, and `exchange.shard_imbalance` must drop vs cache-off (the hot mass
    leaves `shard_positions`)."""
    from openembedding_tpu.utils.sketch import SkewMonitor
    rng = np.random.default_rng(5)
    vocab = 1 << 12
    # heavy head, all landing on shard 5 (ids = 8k + 5): the round-9 planted
    # skew case — cache-off imbalance is unambiguous
    hot_pool = (np.arange(16) * S + 5).astype(np.int64)
    ids = rng.integers(0, vocab, (B, 26))
    mask = rng.random((B, 26)) < 0.6
    ids[mask] = hot_pool[rng.integers(0, 16, mask.sum())]

    model = EmbeddingModel(_Tower(), [
        embed.Embedding(vocab, 8, name="a"),
        embed.Embedding(-1, 8, name="b", capacity=4096),
    ])
    batch = {"sparse": {"a": ids.astype(np.int32),
                        "b": (ids + 1).astype(np.int64)},
             "label": rng.integers(0, 2, (B,)).astype(np.float32)}

    mon = SkewMonitor(k=64, sync=True)
    mon.observe("a", batch["sparse"]["a"])
    H = 16
    predicted = dict(mon.sketch("a").coverage([H]))[H]

    def run(hot_rows):
        metrics._REGISTRY.clear()
        tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.1),
                         mesh=make_mesh(), wire="fp32", hot_rows=hot_rows)
        state = tr.init(batch)
        if hot_rows:
            state = tr.refresh_hot_rows(state, monitor=mon)
        step = tr.jit_train_step(batch, state)
        _state, m = step(state, batch)
        metrics.record_step_stats(m["stats"])
        return metrics.report()

    rep_off = run(0)
    rep_on = run(H)
    imb_off = rep_off['exchange.shard_imbalance{table="a"}']
    imb_on = rep_on['exchange.shard_imbalance{table="a"}']
    hit = rep_on['hot.hit_ratio{table="a"}']
    # the sketch saw exactly this stream, so coverage is near-exact here
    assert abs(hit - predicted) < 0.05, (hit, predicted)
    assert hit > 0.5
    assert imb_on < imb_off - 0.5, (imb_on, imb_off)
    assert rep_on['hot.bytes_saved{table="a"}'] > 0
    # gauges survive a periodic report(reset=True) like other exchange gauges
    metrics.report(reset=True)
    rep2 = metrics.report()
    assert rep2['hot.hit_ratio{table="a"}'] == hit


def test_hot_off_traces_no_extra_collectives():
    """The default path stays free: hot_rows=0 attaches no cache state and
    compiles the SAME collective set as before the feature (3 a2a per
    dim-group, no all-gather); hot-on keeps the a2a count and adds only the
    backward all_gathers."""
    import re
    rng = np.random.default_rng(6)
    b = _batch(rng)

    def hlo(hot_rows):
        tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                         mesh=make_mesh(), wire="fp32", hot_rows=hot_rows)
        state = tr.init(b)
        if hot_rows:
            assert state.tables["a"].hot is not None
        else:
            assert state.tables["a"].hot is None
        lowered = tr.jit_train_step(b, state).lower(state, b)
        return lowered.as_text(), lowered.compile().as_text()

    traced_off, txt_off = hlo(0)
    traced_on, txt_on = hlo(64)

    def count(pat, txt):
        return len(re.findall(pat, txt))

    a2a = r" all-to-all(?:-start)?\("
    assert count(a2a, txt_off) == 3  # one dim-8 group: ids, rows, grads
    assert count(a2a, txt_on) == 3   # hot removes payload, not collectives
    # the default path adds NO collectives; hot-on adds only the dense
    # psums of the hot grad/count aggregates (all-reduce, never a2a).
    # Counted in the module as TRACED: the compiler's all-reduce combiner
    # merges them into the stats psum, whatever their number
    ar = r"stablehlo\.all_reduce"
    assert count(ar, traced_on) > count(ar, traced_off) > 0


def test_refresh_is_static_shapes_no_rejit():
    """Promote/demote swaps array contents, never shapes: the SAME jitted
    step keeps running across refreshes with different hot sets (and the
    lifecycle fns compile once per mode). The never-re-jit rule is asserted
    EXECUTABLY via utils/guards.assert_no_recompile: any retrace raises."""
    from openembedding_tpu.utils.guards import assert_no_recompile
    rng = np.random.default_rng(7)
    batches = [_batch(rng) for _ in range(3)]
    tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), wire="fp32", hot_rows=32)
    state = tr.init(batches[0])
    step = assert_no_recompile(tr.jit_train_step(batches[0], state),
                               label="hot_train_step")
    state, _ = step(state, batches[0])
    state = tr.refresh_hot_rows(state, hot_ids={"a": np.array([7], np.int64)})
    state, _ = step(state, batches[1])
    state = tr.refresh_hot_rows(
        state, hot_ids={"a": np.array([13, 21], np.int64),
                        "b": _HOT_IDS["b"]})
    state, m = step(state, batches[2])
    assert np.isfinite(float(m["loss"]))
    assert step.trace_count() == 1  # three steps, two refreshes, ONE program
    assert set(tr._hot_fns) == {"refresh"}  # one compiled refresh, reused
    # demoted id 7 must have been written back: reads still see its training
    rows = _probe(tr, tr.hot_sync(state), "a", np.array([7, 13], np.int32))
    assert np.abs(rows).sum() > 0


def test_hot_rows_inert_on_one_device_mesh():
    """hot_rows on a 1-device mesh is silently inert (the shard IS local);
    the protocol itself rejects a stray hot cache at S=1 loudly."""
    rng = np.random.default_rng(8)
    b = _batch(rng)
    tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(jax.devices()[:1]), hot_rows=64)
    assert not tr.hot_enabled
    state = tr.init(b)
    assert state.tables["a"].hot is None
    state = tr.refresh_hot_rows(state)  # no-op, not an error
    step = tr.jit_train_step(b, state)
    _state, m = step(state, b)
    assert np.isfinite(float(m["loss"]))
