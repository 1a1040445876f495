"""The client sends what it has, not S x capacity scatters
(`parallel/sharded.py` "WHAT THE CLIENT SENDS"): `unique_and_route` sorts by
(owner, id), so owner s's unique ids are one contiguous range of the unique
buffer, and the outgoing buckets — ids and gradient payload — and the rows
that come back move by S block copies of that range (`_to_buckets`,
`_from_buckets`) where the parent scattered and gathered slot by slot over
S x capacity positions.

(a) `bucket_ids`, the payload buckets, the rows read back, `overflow` and the
per-owner counts against a NumPy reference written here from (id % S, rank
within the owner); (b) K steps of `MeshTrainer` on the virtual-device CPU mesh
leave the state of a reference that buckets by per-slot scatter, bit for bit;
(c) the step's load counters are the host's counts; (d) the traced module
holds no scatter or gather over S x capacity rows on the client's side.

The per-slot reference is the parent's code, kept HERE and patched over the
three seams (`sharded.unique_and_route`, `_to_buckets`, `_from_buckets`): the
(owner, slot) of every unique slot, a scatter into a filled S x cap array,
and `tests/dedup_reference.unbucket`'s gather back.
"""

from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dedup_reference
import openembedding_tpu as embed
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.ops import dedup
from openembedding_tpu.ops.id64 import (PAIR_EMPTY, np_join_ids, np_split_ids,
                                        pair_mod)
from openembedding_tpu.parallel import MeshTrainer, make_mesh, sharded
from openembedding_tpu.utils import guards, metrics

K = 3
PER_CHIP = 4            # examples a device: n = 4 x 26 = 104 positions
N = PER_CHIP * 26
VOCAB = 96


# -- (a) against a NumPy reference from (id % S, rank within the owner) -------

CASES = ["zipf", "one_owner", "empty_owner", "all_invalid", "id_zero", "pair",
         "overflow", "hot", "owner"]


def _case(case, S, n=208, seed=0):
    """-> (ids int64 (n,), valid (n,), owner-or-None (n,), capacity, pair)."""
    rng = np.random.default_rng(seed + S)
    ids = (rng.zipf(1.05, n) % 4096).astype(np.int64)
    valid = np.ones(n, bool)
    owner, cap, pair = None, n, False
    if case == "one_owner":
        ids = ids // S * S                  # every id to owner 0
    elif case == "empty_owner":
        ids = np.where(ids % S == 1, ids + 1, ids)  # nothing for owner 1
    elif case == "all_invalid":
        ids[:] = -1
        valid[:] = False
    elif case == "id_zero":
        ids[rng.random(n) < 0.4] = 0        # id 0 is a real id, owner 0
        ids[rng.random(n) < 0.1] = -1
        valid = ids >= 0
    elif case == "pair":
        ids = ids + (1 << 40)
        ids[rng.random(n) < 0.1] = -1
        valid = ids >= 0
        pair = True
    elif case == "overflow":
        cap = 6                             # every owner has more than that
    elif case == "hot":
        valid = ~np.isin(ids, np.arange(1, 9))  # the head is carved out
    elif case == "owner":
        owner = ((ids * 7 + 3) % S).astype(np.int32)  # a function of the id
    return ids, valid, owner, cap, pair


def _numpy_route(ids, valid, owner, S, cap):
    """The reference: per owner the sorted unique ids (their index = the
    bucket slot), the owner-major unique order with the pseudo-owner S last,
    what each owner's positions count, and what a bucket of `cap` drops."""
    own = np.where(valid, ids % S if owner is None else owner, S)
    groups = [np.unique(ids[own == s]) for s in range(S + 1)]
    order = np.concatenate(groups)
    start = np.cumsum([0] + [g.size for g in groups])[:S]
    positions = np.array([(own == s).sum() for s in range(S)])
    overflow = sum(max(g.size - cap, 0) for g in groups[:S])
    return groups[:S], order, start, positions, overflow


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("S", [2, 4, 8])
def test_buckets_equal_the_numpy_reference(S, case):
    ids, valid, owner, cap, pair = _case(case, S)
    n = ids.size
    dev_ids = jnp.asarray(np_split_ids(ids)) if pair \
        else jnp.asarray(ids.astype(np.int32))
    rng = np.random.default_rng(1)
    payload = rng.integers(1, 1 << 15, (n, 5)).astype(np.uint16)
    # what comes back holds garbage in the empty slots: reading it back must
    # not depend on what an owner put there
    back = rng.normal(size=(S, cap, 3)).astype(np.float32)

    @jax.jit
    def run(i, v, o, p, b):
        uniq, buckets = dedup.unique_and_route(i, v, S, cap, owner=o)
        plan = sharded.ExchangePlan(uniq, buckets, None, None, cap)
        return (uniq, buckets, buckets.bucket_valid,
                sharded._to_buckets(p, plan), sharded._from_buckets(b, plan))
    uniq, buckets, bvalid, sent, got = jax.device_get(run(
        dev_ids, jnp.asarray(valid),
        None if owner is None else jnp.asarray(owner), jnp.asarray(payload),
        jnp.asarray(back)))

    groups, order, start, positions, overflow = _numpy_route(
        ids, valid, owner, S, cap)
    # the contract: uniques in owner-major order, each owner's ascending
    u = np_join_ids(uniq.unique_ids) if pair else uniq.unique_ids
    assert int(uniq.num_unique) == order.size
    np.testing.assert_array_equal(u[:order.size], order)
    np.testing.assert_array_equal(buckets.start, start)
    np.testing.assert_array_equal(buckets.count,
                                  [min(g.size, cap) for g in groups])
    np.testing.assert_array_equal(buckets.positions, positions)
    assert int(buckets.overflow) == overflow
    want_ids = np.full((S, cap), -1, np.int64)
    want_sent = np.zeros((S, cap, 5), np.uint16)
    want_got = np.zeros((n, 3), np.float32)
    for s, g in enumerate(groups):
        r = min(g.size, cap)
        want_ids[s, :r] = g[:r]
        want_sent[s, :r] = payload[start[s]:start[s] + r]
        want_got[start[s]:start[s] + r] = back[s, :r]
    if pair:
        np.testing.assert_array_equal(
            buckets.bucket_ids, np_split_ids(want_ids.reshape(-1)).reshape(
                S, cap, 2))
        assert np.all(buckets.bucket_ids[want_ids < 0] == PAIR_EMPTY)
    else:
        np.testing.assert_array_equal(buckets.bucket_ids, want_ids)
    np.testing.assert_array_equal(bvalid, want_ids >= 0)
    np.testing.assert_array_equal(sent, want_sent)
    np.testing.assert_array_equal(got, want_got)
    if case == "overflow":
        assert overflow > 0
    if case == "id_zero":
        assert want_ids[0, 0] == 0 and bvalid[0, 0]


# -- the per-slot reference: the parent's scatter and gather -------------------


class _SlotBuckets(NamedTuple):
    """`RoutedBuckets` with the parent's (owner, slot) of every unique slot."""
    bucket_ids: jax.Array
    start: jax.Array
    count: jax.Array
    positions: jax.Array
    overflow: jax.Array
    owner: jax.Array    # (n,) int32 in [0, S]
    slot: jax.Array     # (n,) int32, capacity = dropped

    @property
    def bucket_valid(self):
        return dedup.bucket_validity(self.bucket_ids)


def _slot_unique_and_route(ids, valid, S, cap, owner=None):
    """The package's sort and unique buffer (not under test here), and the
    buckets built the parent's way: the owner of every unique slot, its rank
    within the owner, one scatter into an EMPTY-filled S x cap array."""
    uniq, _ = dedup.unique_and_route(ids, valid, S, cap, owner=owner)
    n = ids.shape[0]
    if owner is None:
        owner = pair_mod(ids, S) if ids.ndim == 2 else ids % S
    owner_in = jnp.where(valid, owner.astype(jnp.int32), S)
    u_owner = jnp.full((n,), S, jnp.int32).at[uniq.inverse].set(owner_in)
    real = (u_owner < S) & (uniq.counts > 0)
    per_owner = jax.ops.segment_sum(real.astype(jnp.int32), u_owner,
                                    num_segments=S + 1)[:S]
    start = (jnp.cumsum(per_owner) - per_owner).astype(jnp.int32)
    slot_u = jnp.where(real, jnp.arange(n, dtype=jnp.int32)
                       - start[jnp.clip(u_owner, 0, S - 1)], cap)
    in_cap = real & (slot_u < cap)
    flat_pos = jnp.where(in_cap, u_owner * cap + slot_u, S * cap)
    lanes = ids.shape[1:]
    empty = jnp.full((S * cap,) + lanes, PAIR_EMPTY if lanes else -1,
                     ids.dtype)
    bucket_ids = empty.at[flat_pos].set(
        uniq.unique_ids, mode="drop").reshape((S, cap) + lanes)
    positions = jax.ops.segment_sum(
        jnp.where(real, uniq.counts, 0), u_owner, num_segments=S + 1)[:S]
    return uniq, _SlotBuckets(
        bucket_ids, start, jnp.minimum(per_owner, cap),
        positions.astype(jnp.int32),
        jnp.sum(real & (slot_u >= cap)).astype(jnp.int32),
        jnp.where(real, u_owner, S), jnp.where(in_cap, slot_u, cap))


def _slot_to_buckets(payload, plan):
    buckets, cap = plan.buckets, plan.cap
    S = buckets.count.shape[0]
    flat_pos = jnp.where((buckets.owner < S) & (buckets.slot < cap),
                         buckets.owner * cap + buckets.slot, S * cap)
    return jnp.zeros((S * cap,) + payload.shape[1:], payload.dtype).at[
        flat_pos].set(payload, mode="drop").reshape(
            (S, cap) + payload.shape[1:])


def _slot_from_buckets(x, plan):
    return dedup_reference.unbucket(x, plan.buckets.owner, plan.buckets.slot)


@pytest.fixture
def per_slot(monkeypatch):
    """Patches the three seams with the per-slot reference for the traces
    made while it is on."""
    def on():
        monkeypatch.setattr(sharded, "unique_and_route",
                            _slot_unique_and_route)
        monkeypatch.setattr(sharded, "_to_buckets", _slot_to_buckets)
        monkeypatch.setattr(sharded, "_from_buckets", _slot_from_buckets)
    yield on
    monkeypatch.undo()


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics._REGISTRY.clear()
    yield
    metrics._REGISTRY.clear()


def _batches(S, *, vocab=VOCAB, seed=0, pool=None, invalid=False):
    rng = np.random.default_rng(seed)
    B = PER_CHIP * S
    if pool:   # hash tables: 36-bit ids, so split-pair keys with x64 off
        ids = (1 << 35) + rng.integers(0, pool, (K, B, 26))
    else:
        ids = rng.integers(0, vocab, (K, B, 26))
    if invalid:
        ids = np.where(rng.random(ids.shape) < 0.2, -1, ids)
    return {"sparse": {"categorical": ids.astype(np.int64 if pool
                                                 else np.int32)},
            "dense": rng.normal(size=(K, B, 13)).astype(np.float32),
            "label": rng.integers(0, 2, (K, B)).astype(np.float32)}


def _train(S, stacked, *, many=True, vocab=VOCAB, hash_capacity=0, hot=0,
           mig=0, **kw):
    """K steps of a tiny DeepFM on S devices -> (trainer, state, metrics)."""
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    if hash_capacity:
        model = make_deepfm(vocabulary=-1, dim=9, hidden=(8,), hashed=True,
                            capacity=hash_capacity)
    else:
        model = make_deepfm(vocabulary=vocab, dim=9, hidden=(8,))
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                     mesh=make_mesh(jax.devices()[:S]), hot_rows=hot,
                     mig_rows=mig, **kw)
    state = tr.init(one)
    if hot:
        state = tr.refresh_hot_rows(
            state, hot_ids={"categorical": np.arange(4, dtype=np.int64)})
    if mig:
        state = tr.migrate_rows(state, moves={"categorical": (
            np.array([8, 16, 24], np.int64), np.array([1, 0, 1], np.int32))})
    if many:
        state, m = tr.jit_train_many(stacked, state)(state, stacked)
    else:
        step = tr.jit_train_step(one, state)
        for k in range(K):
            state, m = step(state, jax.tree_util.tree_map(
                lambda x: x[k], stacked))
    return tr, jax.device_get(state), jax.device_get(m)


def _assert_same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- (b) the state of the per-slot reference, bit for bit ---------------------


@pytest.mark.parametrize("schedule", ["serial", "pipelined"])
@pytest.mark.parametrize("wire", ["fp32", "bf16"])
@pytest.mark.parametrize("S", [2, 4, 8])
def test_k_steps_leave_the_state_of_per_slot_buckets(S, wire, schedule,
                                                     per_slot):
    kw = dict(wire=wire, pipeline_steps=schedule == "pipelined")
    stacked = _batches(S, invalid=True)
    _, sa, ma = _train(S, stacked, **kw)
    per_slot()
    _, sb, mb = _train(S, stacked, **kw)
    _assert_same(sa, sb)
    _assert_same(ma, mb)


_FEATURES = {
    "hot": dict(hot=8),
    "hot_pipelined_bf16": dict(hot=8, wire="bf16", pipeline_steps=True),
    "migrated": dict(mig=8),
    "hot_migrated_bf16": dict(hot=8, mig=8, wire="bf16"),
    "capacity_0.5_overflows": dict(capacity_factor=0.5, vocab=4 * VOCAB),
    "capacity_0.5_pipelined_bf16": dict(capacity_factor=0.5, wire="bf16",
                                        vocab=4 * VOCAB,
                                        pipeline_steps=True),
    "capacity_2": dict(capacity_factor=2.0),
    "pair_ids_hash": dict(hash_capacity=1 << 12),
    "pair_ids_hash_bf16": dict(hash_capacity=1 << 12, wire="bf16"),
    "int8_error_feedback": dict(wire="int8"),
    "int8_pipelined": dict(wire="int8", pipeline_steps=True),
    "step_loop": dict(many=False),
}


@pytest.mark.parametrize("feature", sorted(_FEATURES))
def test_every_feature_on_the_route_matches_per_slot_buckets(feature,
                                                             per_slot):
    """Whatever feeds `unique_and_route` or reads a bucket: an explicit owner
    (migrated rows), hot positions carved out, split-pair ids, a capacity
    that drops ids, error feedback, the step loop's stats."""
    S = 4
    kw = dict({"wire": "fp32"}, **_FEATURES[feature])
    stacked = _batches(S, vocab=kw.get("vocab", VOCAB), invalid=True,
                       pool=VOCAB if "hash_capacity" in kw else None)
    _, sa, ma = _train(S, stacked, **kw)
    per_slot()
    _, sb, mb = _train(S, stacked, **kw)
    _assert_same(sa, sb)
    _assert_same(ma, mb)   # the step loop's: every stat of the last step
    if feature.startswith("capacity_0.5"):
        assert int(ma["overflow"]) > 0


# -- (c) the load counters are the host's counts ------------------------------


@pytest.mark.parametrize("capacity_factor", [0.0, 0.5],
                         ids=["exact", "capacity_0.5"])
@pytest.mark.parametrize("S", [2, 4, 8])
def test_load_counters_equal_the_host_counts(S, capacity_factor):
    vocab = 4 * VOCAB
    stacked = _batches(S, vocab=vocab, seed=3, invalid=True)
    tr, _, m = _train(S, stacked, many=False, vocab=vocab, wire="fp32",
                      capacity_factor=capacity_factor)
    tr.record_step_stats(m)
    cap = sharded._bucket_capacity(N, S, capacity_factor)
    ids = stacked["sparse"]["categorical"][K - 1]       # the last step's
    rows = np.zeros(S, np.int64)
    positions = np.zeros(S, np.int64)
    fill = np.zeros(S)
    overflow = 0
    for d in range(S):
        mine = ids[d * PER_CHIP:(d + 1) * PER_CHIP].reshape(-1)
        mine = mine[mine >= 0]
        per_owner = np.bincount(np.unique(mine) % S, minlength=S)
        rows += np.minimum(per_owner, cap)
        overflow += int(np.maximum(per_owner - cap, 0).sum())
        positions += np.bincount(mine % S, minlength=S)
        fill[d] = min(per_owner.max(), cap) / cap
    st = m["stats"]
    np.testing.assert_array_equal(st["categorical/shard_rows"], rows)
    np.testing.assert_array_equal(st["categorical/shard_positions"],
                                  positions)
    np.testing.assert_allclose(st["categorical/bucket_fill"], fill,
                               rtol=1e-6)
    assert int(st["categorical/pull_overflow"]) == overflow
    assert (overflow > 0) == (capacity_factor > 0)
    rep = metrics.report()
    for d in range(S):      # served by SOURCE shard
        key = 'exchange.bucket_fill{shard="%d",table="categorical"}' % d
        assert rep[key] == pytest.approx(fill[d], rel=1e-6)


# -- (d) no per-slot scatter or gather over S x capacity rows -----------------

PER_SLOT_OPS = ("scatter", "scatter-add", "scatter_add", "gather",
                "dynamic_gather")


def per_slot_ops_over(many, rows, *args):
    """Every scatter / gather `many` traces to with an operand or a result of
    `rows` leading rows, but for those of a step that does not fit the
    owner's working size (`exchange.full_size`)."""
    return [(name, stack) for name, stack, shapes
            in guards.primitive_sites(many, PER_SLOT_OPS, *args)
            if "exchange.full_size" not in stack
            and any(s and s[0] == rows for s in shapes)]


@pytest.mark.parametrize("schedule", ["serial", "pipelined"])
def test_no_scatter_or_gather_over_s_x_capacity_rows(schedule, per_slot):
    """The four-device `jit_train_many` as traced, exact mode (cap = n): the
    client builds and reads its buckets without one scatter or gather over
    S x cap rows. The owner's full-size branch keeps its own (a step that
    does not fit), and the pipelined conflict patch stages what it received
    by position (`grouped_conflict_patch`: not a bucket of the route)."""
    S = 4
    stacked = _batches(S)
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)

    def traced():
        tr = MeshTrainer(make_deepfm(vocabulary=VOCAB, dim=9, hidden=(8,)),
                         embed.Adagrad(learning_rate=0.05), wire="bf16",
                         mesh=make_mesh(jax.devices()[:S]),
                         pipeline_steps=schedule == "pipelined")
        state = tr.init(one)
        return [f for f in per_slot_ops_over(
            tr.jit_train_many(stacked, state), S * N, state, stacked)
            if "conflict_patch" not in f[1]]
    assert traced() == []
    per_slot()      # the reference does hold them: the pin can see them
    names = {f[0] for f in traced()}
    assert "gather" in names and names & {"scatter", "scatter-add"}
