"""Unit tests for the static-shape dedup / bucketing primitives (the counterparts of
the reference's client-side hot loops, `EmbeddingPullOperator.cpp:60-112`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedup_reference import bucket_by_owner, unbucket
from openembedding_tpu.ops.dedup import unique_with_counts


@pytest.mark.parametrize("n,vocab", [(16, 5), (128, 1000), (64, 2)])
def test_unique_with_counts_matches_numpy(n, vocab):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=n)
    res = jax.jit(unique_with_counts)(jnp.asarray(ids))
    expect_u, expect_c = np.unique(ids, return_counts=True)
    k = int(res.num_unique)
    assert k == len(expect_u)
    np.testing.assert_array_equal(np.asarray(res.unique_ids)[:k], expect_u)
    np.testing.assert_array_equal(np.asarray(res.counts)[:k], expect_c)
    # padding slots have count 0
    assert np.all(np.asarray(res.counts)[k:] == 0)
    # inverse maps each id back to its unique slot
    np.testing.assert_array_equal(np.asarray(res.unique_ids)[np.asarray(res.inverse)], ids)


def test_unique_single_value():
    ids = jnp.full((32,), 7, jnp.int32)
    res = unique_with_counts(ids)
    assert int(res.num_unique) == 1
    assert int(res.counts[0]) == 32
    assert int(res.unique_ids[0]) == 7


def test_bucket_unbucket_roundtrip():
    rng = np.random.default_rng(1)
    n, shards = 64, 4
    ids = jnp.asarray(rng.integers(0, 1000, size=n))
    valid = jnp.asarray(rng.random(n) > 0.2)
    res = bucket_by_owner(ids, valid, shards, capacity=n)
    assert int(res.overflow) == 0
    # every valid id landed in its owner bucket
    b_ids = np.asarray(res.bucket_ids)
    b_valid = np.asarray(res.bucket_valid)
    for s in range(shards):
        got = sorted(b_ids[s][b_valid[s]].tolist())
        expect = sorted(int(i) for i, v in zip(np.asarray(ids), np.asarray(valid))
                        if v and i % shards == s)
        assert got == expect
    # unbucket returns each element's own payload
    payload = b_ids[..., None].astype(np.float32)  # payload = the id itself
    back = unbucket(jnp.asarray(payload), res.owner, res.slot)
    back = np.asarray(back)[:, 0]
    np.testing.assert_array_equal(
        back[np.asarray(valid)], np.asarray(ids)[np.asarray(valid)].astype(np.float32))
    # invalid elements read back zeros
    assert np.all(back[~np.asarray(valid)] == 0)


def test_bucket_overflow_counted():
    ids = jnp.zeros((16,), jnp.int32)  # all owner 0
    valid = jnp.ones((16,), bool)
    res = bucket_by_owner(ids, valid, num_shards=4, capacity=4)
    assert int(res.overflow) == 12
    assert int(res.bucket_valid.sum()) == 4


@pytest.mark.parametrize("pair", [False, True])
@pytest.mark.parametrize("cap_frac", [1.0, 0.3])
def test_unique_and_route_matches_split_pipeline(pair, cap_frac):
    """The fused single-sort plan must agree with unique_with_counts +
    bucket_by_owner on everything order-independent: the unique id SET, the
    inverse mapping contract (unique_ids[inverse[i]] == ids[i]), counts per
    id, per-owner bucket CONTENT, and the overflow count."""
    from openembedding_tpu.ops.dedup import unique_and_route

    rng = np.random.default_rng(0)
    n, S = 257, 4
    raw = rng.integers(0, 64, size=n)
    # validity is a function of the id VALUE (negative = padding), exactly
    # like `_id_valid` in the protocol — never a per-occurrence coin flip
    invalid_values = {3, 17, 42}
    raw = np.where(np.isin(raw, list(invalid_values)), -1, raw)
    mask = raw < 0
    if pair:
        from openembedding_tpu.ops.id64 import np_split_ids
        ids64 = np.where(raw < 0, -1, raw.astype(np.int64) + (1 << 40))
        ids = jnp.asarray(np_split_ids(ids64))
    else:
        ids = jnp.asarray(raw.astype(np.int32))
        ids64 = raw.astype(np.int64)
    valid = jnp.asarray(~mask)
    cap = max(1, int(cap_frac * n / S))

    uniq, buckets = jax.jit(
        lambda i, v: unique_and_route(i, v, S, cap))(ids, valid)

    # oracle: the split pipeline (validity recomputed on the unique ids, the
    # way make_plan's old path did)
    o_uniq = unique_with_counts(ids)
    if pair:
        from openembedding_tpu.ops.id64 import pair_valid
        o_valid_u = (o_uniq.counts > 0) & pair_valid(o_uniq.unique_ids)
    else:
        o_valid_u = (o_uniq.counts > 0) & (o_uniq.unique_ids >= 0)
    o_buckets = bucket_by_owner(o_uniq.unique_ids, o_valid_u, S, cap)

    # inverse contract on the fused result
    u = np.asarray(uniq.unique_ids)
    inv = np.asarray(uniq.inverse)
    got_back = u[inv]
    np.testing.assert_array_equal(got_back, np.asarray(ids))

    # counts per id agree (compare as {id: count} dicts over valid slots)
    def count_map(uq, cnts):
        uq, cnts = np.asarray(uq), np.asarray(cnts)
        out = {}
        for i in range(len(cnts)):
            if cnts[i] > 0:
                key = tuple(uq[i]) if uq.ndim == 2 else int(uq[i])
                out[key] = int(cnts[i])
        return out

    assert count_map(uniq.unique_ids, uniq.counts) == \
        count_map(o_uniq.unique_ids, o_uniq.counts)

    # bucket content per owner agrees as SETS (order within a bucket differs)
    def bucket_sets(b):
        ids_np, valid_np = np.asarray(b.bucket_ids), np.asarray(b.bucket_valid)
        out = []
        for s in range(S):
            rows = ids_np[s][valid_np[s]]
            out.append({tuple(r) if rows.ndim == 2 else int(r) for r in rows})
        return out

    g, o = bucket_sets(buckets), bucket_sets(o_buckets)
    if cap_frac >= 1.0:
        assert g == o
        assert int(buckets.overflow) == int(o_buckets.overflow) == 0
    else:
        # under capacity pressure both drop the same NUMBER of ids per owner
        # (which ids differ by intra-bucket order)
        assert [len(x) for x in g] == [len(x) for x in o]
        assert int(buckets.overflow) == int(o_buckets.overflow)


def test_mesh_training_with_id_zero_matches_single_device():
    """REGRESSION for the sentinel-filled exchange: id 0 is a real id and an
    all-zeros bucket slot must NOT alias it. Train a stream saturated with
    id 0 (plus shard-boundary ids) on the mesh and on one device — losses
    and the id-0 row must match exactly."""
    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo  # noqa: F401
    from openembedding_tpu.embedding import lookup
    from openembedding_tpu.initializers import Constant
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    import dataclasses

    S = 8
    rng = np.random.default_rng(0)

    def build(cls, loss_scale=1.0, **kw):
        m = make_deepfm(vocabulary=64, dim=4, hidden=(8,))
        m.specs["categorical"] = dataclasses.replace(
            m.specs["categorical"], initializer=Constant(0.0))
        lf = m.loss_fn
        m.loss_fn = lambda lo, la, *a: loss_scale * lf(lo, la, *a)
        return cls(m, embed.Adagrad(learning_rate=0.1), **kw)

    # every batch drowns in id 0 and the shard-boundary ids 0..S
    batches = []
    for i in range(3):
        ids = rng.integers(0, 64, (16, 4)).astype(np.int32)
        ids[:, 0] = 0
        ids[: S + 1, 1] = np.arange(S + 1)
        batches.append({"sparse": {"categorical": ids},
                        "dense": rng.standard_normal((16, 13)).astype(np.float32),
                        "label": rng.integers(0, 2, (16,)).astype(np.float32)})

    single = build(Trainer, loss_scale=float(S))
    s_state = single.init(batches[0])
    sstep = single.jit_train_step()
    s_losses = []
    for b in batches:
        s_state, m = sstep(s_state, b)
        s_losses.append(float(m["loss"]))

    mesh_tr = build(MeshTrainer, mesh=make_mesh())
    m_state = mesh_tr.init(batches[0])
    mstep = mesh_tr.jit_train_step(batches[0], m_state)
    m_losses = []
    for b in batches:
        m_state, m = mstep(m_state, b)
        m_losses.append(float(m["loss"]))

    # 3 steps of Adagrad compound float-order differences between the
    # psum'd-grad and scaled-loss formulations; an aliasing bug would be
    # gross (zeroed/duplicated rows), not 1e-3 (observed drift on the CPU
    # XLA in this container is 1.3e-3 — platform-dependent reduction order,
    # same reasoning as the test_planted_auc platform gating)
    np.testing.assert_allclose(m_losses, np.asarray(s_losses) / S, rtol=3e-3)
    spec = single.model.specs["categorical"]
    probe = jnp.asarray(np.arange(S + 1, dtype=np.int32))
    want = np.asarray(lookup(spec, s_state.tables["categorical"], probe))
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from openembedding_tpu.parallel.sharded import sharded_lookup
    pull = jax.jit(jax.shard_map(
        partial(sharded_lookup, spec, axis=mesh_tr.axis),
        mesh=mesh_tr.mesh,
        in_specs=(mesh_tr._table_pspec(spec), P()),
        out_specs=P(), check_vma=False))
    got = np.asarray(pull(m_state.tables["categorical"], probe))
    # bf16 dense towers + 3 steps of reduction-order drift bound parity
    # near 1e-4 abs; an aliased/missed id-0 update would be O(0.05+)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-3)


def test_mesh_step_compiles_three_all_to_alls():
    """Structural pin on the exchange wire: one full train step moves exactly
    THREE all_to_alls per DIM-GROUP — ids out, rows back, grads+counts out
    (the validity mask rides the id sentinel, the counts ride the grad
    payload). deepfm's folded layout is one table = one group, so the budget
    here is 3; the multi-group fusion pin (3 tables, 2 groups -> 6, not 9)
    lives in tests/test_wire.py. A fourth collective reappearing per group is
    a protocol regression."""
    import re
    import openembedding_tpu as embed
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    model = make_deepfm(vocabulary=1 << 12, dim=4, hidden=(8,))
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=make_mesh())
    b = next(synthetic_criteo(32, id_space=1 << 12, steps=1, seed=0))
    state = tr.init(b)
    step = tr.jit_train_step(b, state)
    txt = step.lower(state, b).compile().as_text()
    # op instantiations only; async backends emit start/done pairs — count
    # the starts
    n = len(re.findall(r" all-to-all(?:-start)?\(", txt))
    assert n == 3, f"expected 3 all-to-alls in the step, found {n}"


def test_mesh_bf16_table_counts_ride_two_lanes():
    """bfloat16 tables push bf16 payloads: the duplicate count bitcasts into
    TWO bf16 lanes and must round-trip exactly. TestOptimizer is the only
    count-DIVIDING optimizer, so a corrupted count shows up as a grossly
    wrong update, not a rounding blip."""
    import dataclasses
    import openembedding_tpu as embed
    from openembedding_tpu.embedding import lookup
    from openembedding_tpu.initializers import Constant
    from openembedding_tpu.model import EmbeddingModel, Trainer
    from openembedding_tpu.models import make_lr
    from openembedding_tpu.optimizers import TestOptimizer
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    def build(cls, **kw):
        e = embed.Embedding(64, 4, name="categorical", datatype="bfloat16",
                            embeddings_initializer=Constant(0.0))
        lr = make_lr(vocabulary=64)
        m = EmbeddingModel(lr.module, [e], loss_fn=lr.loss_fn)
        return cls(m, TestOptimizer(learning_rate=0.5), **kw)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (32, 4)).astype(np.int32)
    ids[:, 0] = 7  # 32 duplicates of id 7: count division must see 32
    batch = {"sparse": {"categorical": ids}, "label":
             rng.integers(0, 2, (32,)).astype(np.float32)}

    single = build(Trainer)
    s_state = single.init(batch)
    s_state, _ = single.jit_train_step()(s_state, batch)

    mesh_tr = build(MeshTrainer, mesh=make_mesh())
    m_state = mesh_tr.init(batch)
    m_state, _ = mesh_tr.jit_train_step(batch, m_state)(m_state, batch)

    spec = single.model.specs["categorical"]
    probe = jnp.asarray(np.unique(ids).astype(np.int32))
    want = np.asarray(lookup(spec, s_state.tables["categorical"],
                             probe)).astype(np.float32)
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from openembedding_tpu.parallel.sharded import sharded_lookup
    pull = jax.jit(jax.shard_map(
        partial(sharded_lookup, spec, axis=mesh_tr.axis),
        mesh=mesh_tr.mesh,
        in_specs=(mesh_tr._table_pspec(spec), P()),
        out_specs=P(), check_vma=False))
    got = np.asarray(pull(m_state.tables["categorical"],
                          probe)).astype(np.float32)
    # a mangled count would divide by garbage (flip-state updates are
    # O(flip/count)); bf16 rounding is the only legitimate difference
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)
    assert np.abs(got).max() > 0  # the step really updated rows


@pytest.mark.parametrize("case", ["single", "all_invalid", "all_same"])
def test_unique_and_route_edges(case):
    """Degenerate inputs through the fused plan: one id, nothing valid, one
    id duplicated across the whole batch."""
    from openembedding_tpu.ops.dedup import bucket_validity, unique_and_route

    S, cap = 4, 8
    if case == "single":
        ids = jnp.asarray(np.asarray([5], np.int32))
        valid = jnp.asarray([True])
    elif case == "all_invalid":
        ids = jnp.asarray(np.full((16,), -1, np.int32))
        valid = jnp.zeros((16,), bool)
    else:
        ids = jnp.asarray(np.full((16,), 7, np.int32))
        valid = jnp.ones((16,), bool)
    uniq, buckets = jax.jit(
        lambda i, v: unique_and_route(i, v, S, cap))(ids, valid)

    occupancy = int(np.asarray(bucket_validity(buckets.bucket_ids)).sum())
    if case == "single":
        assert int(uniq.num_unique) == 1
        assert occupancy == 1
        assert int(buckets.count[5 % S]) == 1
    elif case == "all_invalid":
        assert occupancy == 0
        assert int(buckets.overflow) == 0
        # every element routed to the invalid pseudo-owner
        assert np.all(np.asarray(buckets.count) == 0)
    else:
        assert int(uniq.num_unique) == 1
        assert occupancy == 1
        assert int(np.asarray(uniq.counts)[0]) == 16
        np.testing.assert_array_equal(np.asarray(uniq.inverse), 0)


# -- PR 37: the dedup by sorts, held to the scatter-based bodies it replaced ---
# (`tests/dedup_reference.py`: the parent's `unique_with_counts` and
# `unique_and_route`, verbatim). Every field, every integer, every dtype.

N_BENCH = 106_496       # the benchmark's positions a step (4096 x 26)


def _zipf(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.zipf(1.05, size=n) % vocab).astype(np.int32)


def _pairs(raw):
    from openembedding_tpu.ops.id64 import np_split_ids
    return np_split_ids(np.where(raw < 0, -1, raw.astype(np.int64) + (1 << 40)))


_UNIQUE_CASES = {
    "zipf_106496": lambda: _zipf(N_BENCH, 1 << 25),
    "zipf_small": lambda: _zipf(257, 64),
    "all_equal": lambda: np.full((64,), 7, np.int32),
    "all_distinct": lambda: np.random.default_rng(1).permutation(300)
    .astype(np.int32),
    "n_1": lambda: np.asarray([5], np.int32),
    # what `_route_unique` sends: padding and negative ids under the key n_rows
    "sentinel_heavy": lambda: np.where(
        np.random.default_rng(2).random(512) < 0.7, 1000,
        _zipf(512, 1000, seed=2)).astype(np.int32),
    "all_sentinel": lambda: np.full((128,), 1000, np.int32),
    "int64_ids": lambda: _zipf(257, 64).astype(np.int64) + (1 << 40),
    "split_pair": lambda: _pairs(_zipf(513, 100, seed=3)),
    "split_pair_low_word_ties": lambda: np.stack(
        [np.random.default_rng(4).integers(0, 3, 200),
         np.random.default_rng(5).integers(0, 3, 200)], -1).astype(np.uint32),
}


def _assert_fields_equal(got, want):
    assert type(got)._fields == type(want)._fields
    for name in type(want)._fields:
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("case", sorted(_UNIQUE_CASES))
def test_unique_with_counts_equals_the_scatter_reference(case):
    import dedup_reference
    ids = jnp.asarray(_UNIQUE_CASES[case]())
    _assert_fields_equal(jax.jit(unique_with_counts)(ids),
                         jax.jit(dedup_reference.unique_with_counts)(ids))


def _route_case(raw, S, cap=None, *, invalid=0.0, owner=None, pair=False):
    """-> (ids, valid, S, cap, owner): validity and an explicit owner are
    functions of the id VALUE, as the protocol's are."""
    raw = np.asarray(raw)
    if invalid:
        bad = np.random.default_rng(0).random(int(raw.max()) + 1) < invalid
        raw = np.where(bad[raw], -1, raw)
    valid = raw >= 0
    own = None
    if owner == "assigned":     # in [0, S]: S = carved out of the exchange
        own = jnp.asarray(((raw.astype(np.int64) * 7 + 3) % (S + 1))
                          .astype(np.int32))
    ids = _pairs(raw) if pair else raw.astype(np.int32)
    return (jnp.asarray(ids), jnp.asarray(valid), S,
            raw.shape[0] if cap is None else cap, own)


_ROUTE_CASES = {
    "zipf_106496": lambda: _route_case(_zipf(N_BENCH, 1 << 27), 4),
    "zipf_106496_capacity_half": lambda: _route_case(
        _zipf(N_BENCH, 1 << 27), 4, 13_312, invalid=0.1),
    "zipf_small": lambda: _route_case(_zipf(257, 64), 4),
    "zipf_small_8_shards": lambda: _route_case(_zipf(257, 64), 8),
    "all_equal": lambda: _route_case(np.full((64,), 7), 4),
    "all_distinct": lambda: _route_case(
        np.random.default_rng(1).permutation(300), 4),
    "n_1": lambda: _route_case(np.asarray([5]), 4, 8),
    "valid_mask": lambda: _route_case(_zipf(257, 64), 4, invalid=0.3),
    "all_invalid": lambda: _route_case(np.full((32,), -1), 4),
    "explicit_owner": lambda: _route_case(_zipf(257, 64), 4,
                                          owner="assigned"),
    "explicit_owner_valid_mask": lambda: _route_case(
        _zipf(513, 200), 4, invalid=0.2, owner="assigned"),
    "full_bucket": lambda: _route_case(_zipf(257, 64), 4, 5),
    "full_bucket_one_owner": lambda: _route_case(np.arange(64) * 4, 4, 4),
    "split_pair": lambda: _route_case(_zipf(513, 100, seed=3), 4, pair=True),
    "split_pair_valid_mask_full_bucket": lambda: _route_case(
        _zipf(513, 100, seed=3), 4, 9, invalid=0.2, pair=True),
    "split_pair_explicit_owner": lambda: _route_case(
        _zipf(257, 64), 4, pair=True, owner="assigned"),
}


@pytest.mark.parametrize("case", sorted(_ROUTE_CASES))
def test_unique_and_route_equals_the_scatter_reference(case):
    import dedup_reference
    from openembedding_tpu.ops.dedup import unique_and_route
    ids, valid, S, cap, owner = _ROUTE_CASES[case]()

    def run(f):     # `owner` None is an empty pytree to `jit`
        return jax.jit(lambda i, v, o: f(i, v, S, cap, owner=o))(
            ids, valid, owner)

    (uniq, buckets), (r_uniq, r_buckets) = (
        run(unique_and_route), run(dedup_reference.unique_and_route))
    _assert_fields_equal(uniq, r_uniq)
    _assert_fields_equal(buckets, r_buckets)
    if case.startswith("full_bucket") or "capacity_half" in case:
        assert int(buckets.overflow) > 0       # the case is what it says


def _n_sized_scatters_and_gathers(text, n):
    """Scatter ops, and gather ops with an operand of n or more rows, in a
    lowered (StableHLO) text."""
    import re
    found = []
    for line in text.splitlines():
        op = re.search(r"stablehlo\.(scatter|gather|dynamic_gather)\b", line)
        if op is None:
            continue
        dims = [int(d) for d in re.findall(r"tensor<(\d+)[x>]", line)]
        if op.group(1) == "scatter" or any(d >= n for d in dims):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("layout", ["single_lane", "split_pair"])
@pytest.mark.parametrize("fn", ["unique_with_counts", "unique_and_route"])
def test_the_dedup_lowers_to_no_scatter_or_gather_over_the_positions(
        fn, layout):
    """The pin on the mechanism (CPU lowering): run heads compacted by a sort,
    counts by differences, ids out of the one sort. The reference's text holds
    the passes this one must not, which keeps the probe honest."""
    import dedup_reference
    from openembedding_tpu.ops import dedup
    n = 4096
    ids = (jax.ShapeDtypeStruct((n, 2), jnp.uint32) if layout == "split_pair"
           else jax.ShapeDtypeStruct((n,), jnp.int32))
    valid = jax.ShapeDtypeStruct((n,), jnp.bool_)

    def lowered(mod):
        if fn == "unique_with_counts":
            return jax.jit(mod.unique_with_counts).lower(ids).as_text()
        return jax.jit(lambda i, v: mod.unique_and_route(i, v, 4, n)).lower(
            ids, valid).as_text()

    assert _n_sized_scatters_and_gathers(lowered(dedup), n) == []
    assert "stablehlo.sort" in lowered(dedup)
    assert _n_sized_scatters_and_gathers(lowered(dedup_reference), n)
