"""The owner works over the slots it received, not over S x capacity
(`parallel/sharded.py` "WHAT THE OWNER WORKS OVER"): the receive side of the
exchange is compacted to a static working size W = n before the serve and the
apply, and a step whose received ids do not fit takes the full-size path.

On the CPU mesh (4 and 8 virtual devices): (a) every received bucket's valid
slots are a prefix, whatever rides the route; (b) K steps leave the state of
the full-size path, bit for bit, on every feature of `MeshTrainer` that reads
the received slots; (c) a crowded owner takes the full-size path, counts it,
and drops nothing; (d) where the receive side is no larger than W the program
holds no compaction and `Trainer`'s scan is untouched; (e) the collectives of
a step are the same; (f) `owner_fill` is the count taken on the host.

The full-size path is the program with `sharded._owner_view` nulled: every
plan then carries `owner=None`, and serve and apply run the parent's code.
"""

import functools
import re

import numpy as np
import pytest

import jax

import openembedding_tpu as embed
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.parallel import MeshTrainer, make_mesh, sharded
from openembedding_tpu.utils import metrics

from hlo_hash import strip

K = 3
PER_CHIP = 4            # examples a device: n = 4 x 26 = 104 positions
N = PER_CHIP * 26       # the working size W
VOCAB = 96              # <= W: what one owner receives always fits


@pytest.fixture(autouse=True)
def _fresh_metrics(monkeypatch):
    """An empty registry; and the apply's own choice of a working size
    (`ops/sparse.py`) switched on at these sizes, so that it nests inside the
    owner's conditional wherever the owner's buffer is long enough to split
    (the full-size path's 4 x 104 slots): tables under `FAST_MEMORY_BYTES`
    are otherwise left alone."""
    from openembedding_tpu.ops import sparse
    monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", 0)
    metrics._REGISTRY.clear()
    yield
    metrics._REGISTRY.clear()


def _batches(S, *, vocab=VOCAB, seed=0, pool=None, stride=1):
    """K stacked batches of 4 examples a device. `pool`: draw from that many
    consecutive 36-bit ids (hash tables; consecutive, so every owner owns
    pool / S of them); `stride`: every id a multiple of it (stride = S crowds
    owner 0)."""
    rng = np.random.default_rng(seed)
    B = PER_CHIP * S
    if pool:
        ids = (1 << 35) + rng.integers(0, pool, (K, B, 26))
    else:
        ids = rng.integers(0, vocab // stride, (K, B, 26)) * stride
    return {"sparse": {"categorical": ids.astype(np.int64 if pool
                                                 else np.int32)},
            "dense": rng.normal(size=(K, B, 13)).astype(np.float32),
            "label": rng.integers(0, 2, (K, B)).astype(np.float32)}


_trainers = {}
_OWNER_VIEW = sharded._owner_view


def _no_view(*a):
    """In `sharded._owner_view`'s place: the full-size path, the program as
    it was before the owner compacted."""
    return None


def _trainer(S, *, dim=9, vocab=VOCAB, hash_capacity=0, hot=0, mig=0, **kw):
    """A tiny DeepFM's `MeshTrainer` on S devices. A `MeshTrainer` keeps its
    jitted step and scan, so two cases that drive the same program compile it
    once: ONE trainer a process for a configuration AND what stands in
    `sharded._owner_view`'s place while it traces (the mechanism or
    `_no_view`; `FAST_MEMORY_BYTES` is this module's 0 throughout). A case's
    own spy, or rows placed by hand, get a trainer of their own: a program
    traced before the spy would never call it. A case's state is its own
    (`init`)."""
    from openembedding_tpu.ops import sparse
    view = sharded._owner_view
    key = (S, view, sparse.FAST_MEMORY_BYTES, dim, vocab, hash_capacity,
           tuple(sorted(kw.items())))
    shared = not (hot or mig) and view in (_OWNER_VIEW, _no_view)
    if shared and key in _trainers:
        return _trainers[key]
    if hash_capacity:
        model = make_deepfm(vocabulary=-1, dim=dim, hidden=(8,),
                            hashed=True, capacity=hash_capacity)
    else:
        model = make_deepfm(vocabulary=vocab, dim=dim, hidden=(8,))
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                     mesh=make_mesh(jax.devices()[:S]), hot_rows=hot,
                     mig_rows=mig, **kw)
    if shared:
        _trainers[key] = tr
    return tr


def _train(S, stacked, *, full_size=False, many=True, hot=0, mig=0, **kw):
    """K steps of a tiny DeepFM on S devices -> (trainer, state on the host,
    metrics as the call returned them: `record_window_stats` knows a window
    by its arrays). `full_size` nulls the mechanism for the run's traces."""
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    kw.setdefault("wire", "fp32")  # the suite's default (tests/conftest.py)
    orig = sharded._owner_view
    if full_size:
        sharded._owner_view = _no_view
    try:
        tr = _trainer(S, hot=hot, mig=mig, **kw)
        state = tr.init(one)
        if hot:
            state = tr.refresh_hot_rows(
                state, hot_ids={"categorical": np.arange(4, dtype=np.int64)})
        if mig:
            state = tr.migrate_rows(state, moves={"categorical": (
                np.array([8, 16, 24], np.int64),
                np.array([1, 2, 3], np.int32))})
        if many:
            state, m = tr.jit_train_many(stacked, state)(state, stacked)
        else:
            step = tr.jit_train_step(one, state)
            for k in range(K):
                state, m = step(state, jax.tree_util.tree_map(
                    lambda x: x[k], stacked))
                tr.record_step_stats(m)
        return tr, jax.device_get(state), m
    finally:
        sharded._owner_view = orig


def _assert_same_state(sa, sb):
    la, ta = jax.tree_util.tree_flatten(sa)
    lb, tb = jax.tree_util.tree_flatten(sb)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- (a) the valid slots of every received bucket are a prefix ----------------


@pytest.mark.parametrize("case", ["plain", "hot", "mig", "hot_mig",
                                  "capacity_1.5", "capacity_0.5", "hash"])
@pytest.mark.parametrize("S", [4, 8])
def test_received_buckets_hold_their_valid_ids_first(S, case, monkeypatch):
    kw = {"plain": {}, "hot": {"hot": 8}, "mig": {"mig": 8},
          "hot_mig": {"hot": 8, "mig": 8},
          "capacity_1.5": {"capacity_factor": 1.5},
          "capacity_0.5": {"capacity_factor": 0.5},
          "hash": {"hash_capacity": 1 << 12}}[case]
    seen = []
    orig = sharded._owner_view

    def spy(recv_ids, recv_valid, n):
        jax.debug.callback(
            lambda i, v: seen.append((np.asarray(i), np.asarray(v))),
            recv_ids, recv_valid)
        return orig(recv_ids, recv_valid, n)
    monkeypatch.setattr(sharded, "_owner_view", spy)
    stacked = _batches(S, pool=VOCAB if case == "hash" else None,
                       vocab=4 * VOCAB)  # wide enough to fill a 0.5 bucket
    _, _, m = _train(S, stacked, many=False, vocab=4 * VOCAB, **kw)
    jax.effects_barrier()
    assert len(seen) >= S * K
    for ids, valid in seen:
        assert valid.shape[0] == S
        # a prefix: no valid slot after an empty one
        assert not np.any(valid[:, 1:] & ~valid[:, :-1])
        # and the payload says the same (EMPTY after the prefix)
        lane = ids[..., 0] if ids.ndim == 3 else ids
        np.testing.assert_array_equal(
            valid, lane != np.array(-1).astype(lane.dtype))
    if case == "capacity_0.5":
        assert int(m["stats"]["categorical/pull_overflow"]) > 0


# -- (b) the state of the full-size path, bit for bit -------------------------

_FEATURES = {
    "packed_dim9_fp32": dict(dim=9, wire="fp32"),
    "packed_dim9_bf16": dict(dim=9, wire="bf16"),
    "split_dim64_fp32": dict(dim=64, wire="fp32"),
    "split_dim64_bf16": dict(dim=64, wire="bf16"),
    "step_loop_dim9": dict(dim=9, many=False),
    "hash": dict(dim=9, hash_capacity=1 << 12),
    "annex": dict(dim=9, mig=8),
    "hot": dict(dim=9, hot=8),
    "int8_ef": dict(dim=9, wire="int8"),
    "pipelined": dict(dim=9, pipeline_steps=True),
    "pipelined_int8_ef": dict(dim=9, wire="int8", pipeline_steps=True),
    "capacity_2": dict(dim=9, capacity_factor=2.0),
}


@pytest.mark.parametrize("feature", sorted(_FEATURES))
@pytest.mark.parametrize("S", [4, 8])
def test_k_steps_leave_the_state_of_the_full_size_path(S, feature):
    kw = dict(_FEATURES[feature])
    stacked = _batches(S, pool=VOCAB if "hash_capacity" in kw else None)
    tr, sa, ma = _train(S, stacked, **kw)
    _, sb, mb = _train(S, stacked, full_size=True, **kw)
    np.testing.assert_array_equal(ma["loss"], mb["loss"])
    _assert_same_state(sa, sb)
    if kw.get("wire") == "int8":
        assert sa.tables["categorical"].ef is not None
    # the compact path did the work: every step fitted (VOCAB <= W) ...
    if kw.get("many", True):
        assert int(ma["owner_full_steps"]["categorical"]) == 0
        assert 0 < float(ma["owner_fill"]["categorical"]) <= VOCAB / N
        assert mb["owner_fill"] == {} and mb["owner_full_steps"] == {}
        tr.record_window_stats(ma)
    rep = metrics.report()
    assert rep['exchange.owner_full_steps{table="categorical"}'] == 0
    assert 0 < rep['exchange.owner_fill{table="categorical"}'] <= VOCAB / N


# -- (c) a crowded owner takes the full-size path and drops nothing -----------


@pytest.mark.parametrize("many", [True, False], ids=["scan", "step_loop"])
@pytest.mark.parametrize("S", [4, 8])
def test_crowded_owner_takes_the_full_size_path(S, many):
    """Every id is a multiple of S, so owner 0 receives what every source
    holds: about S x 100 unique ids for a working size of 104."""
    vocab = 1 << 16
    stacked = _batches(S, vocab=vocab, stride=S)
    ids = stacked["sparse"]["categorical"]
    received = [sum(np.unique(ids[k, d * PER_CHIP:(d + 1) * PER_CHIP]).size
                    for d in range(S)) for k in range(K)]
    assert min(received) > N
    tr, sa, ma = _train(S, stacked, vocab=vocab, many=many)
    _, sb, mb = _train(S, stacked, vocab=vocab, many=many, full_size=True)
    np.testing.assert_array_equal(ma["loss"], mb["loss"])
    _assert_same_state(sa, sb)
    if many:
        assert int(ma["overflow"]) == 0
        assert int(ma["owner_full_steps"]["categorical"]) == K
        np.testing.assert_allclose(float(ma["owner_fill"]["categorical"]),
                                   max(received) / N, rtol=1e-6)
        tr.record_window_stats(ma)
    else:
        assert int(ma["stats"]["categorical/pull_overflow"]) == 0
        assert int(ma["stats"]["categorical/push_overflow"]) == 0
    rep = metrics.report()
    assert rep['exchange.owner_full_steps{table="categorical"}'] == K
    # nothing dropped: every touched row of owner 0 moved
    w0 = jax.device_get(tr.init(jax.tree_util.tree_map(
        lambda x: x[0], stacked))).tables["categorical"].weights
    rows = np.unique(ids) // S  # owner 0's local rows: the table's first
    moved = np.any(np.asarray(sa.tables["categorical"].weights)[rows]
                   != np.asarray(w0)[rows], axis=1)
    assert moved.all()


@pytest.mark.parametrize("S", [4])
def test_mesh_entry_point_publishes_the_owner_counters_unasked(S):
    """Two windows of a crowded owner through `MeshTrainer.jit_train_many`
    and NO call of `record_window_stats`: the registry holds what the
    windows' own metrics say, and asking afterwards adds nothing."""
    vocab = 1 << 16
    stacked = _batches(S, vocab=vocab, stride=S)
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    tr = _trainer(S, vocab=vocab, wire="fp32")  # the crowded scan's program
    state = tr.init(one)
    many = tr.jit_train_many(stacked, state)
    assert tr.jit_train_many() is many  # ONE dispatch object a trainer
    state, m1 = many(state, stacked)
    state, m2 = many(state, stacked)
    want = {
        'exchange.owner_full_steps{table="categorical"}': 2 * K,
        'exchange.owner_fill{table="categorical"}':
            float(m2["owner_fill"]["categorical"]),
        'sparse.apply_fill{table="categorical"}':
            float(m2["apply_fill"]["categorical"]),
        'sparse.apply_full_steps{table="categorical"}': float(
            int(m1["apply_full_steps"]["categorical"])
            + int(m2["apply_full_steps"]["categorical"])),
        'trainer.windows{fn="train_many"}': 2}
    rep = metrics.report()
    assert {k: rep[k] for k in want} == want
    assert rep["trainer.dispatch.ms"] > 0  # the span around each call
    tr.record_window_stats(m1)
    tr.record_window_stats(m2)
    rep = metrics.report()
    assert {k: rep[k] for k in want} == want
    # the text of the scan comes through the object
    assert "exchange.owner_apply" in many.lower(state, stacked).as_text(
        debug_info=True)


# -- (d) no compaction where the receive side is no larger than W -------------


@functools.lru_cache(maxsize=None)
def _scan_text(kind, full_size=False):
    """The compiled `train_many` of a tiny DeepFM: `Trainer`, or `MeshTrainer`
    on 1 device, on 4 at `capacity_factor` 1, or on 4 in exact mode."""
    S = {"single": 1, "mesh1": 1}.get(kind, 4)
    stacked = _batches(S)
    one = jax.tree_util.tree_map(lambda x: x[0], stacked)
    model = make_deepfm(vocabulary=VOCAB, dim=9, hidden=(8,))
    opt = embed.Adagrad(learning_rate=0.05)
    orig = sharded._owner_view
    if full_size:
        sharded._owner_view = _no_view
    try:
        if kind == "single":
            tr = embed.Trainer(model, opt)
            many = tr.jit_train_many()
        else:
            tr = MeshTrainer(
                model, opt, mesh=make_mesh(jax.devices()[:S]),
                capacity_factor=1.0 if kind == "mesh4_capacity_1" else 0.0)
            many = tr.jit_train_many(stacked, tr.init(one))
        return many.lower(tr.init(one), stacked).compile().as_text()
    finally:
        sharded._owner_view = orig


def _owner_conditionals(text):
    """The conditionals of `lax.cond(view.fits, ...)`: those with a branch
    whose ops sit under `exchange.full_size` and that are not under it
    themselves. (The apply's own choice of a working size, `ops/sparse.py`,
    is a conditional too: under `sparse.apply`, once in each branch of the
    owner's where the buffer is long enough to split.)"""
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M)}
    found = []
    for line in text.splitlines():
        if " conditional(" not in line:
            continue
        own = re.search(r'op_name="([^"]*)"', line)
        if own and "exchange.full_size" in own.group(1):
            continue
        names = re.findall(r"(?:true_computation|false_computation)=%?([\w.\-]+)",
                           line)
        group = re.search(r"branch_computations=\{([^}]*)\}", line)
        if group:
            names += [n.strip().lstrip("%") for n in group.group(1).split(",")]
        if any("exchange.full_size" in bodies.get(n, "") for n in names):
            found.append(line)
    return found


@pytest.mark.parametrize("kind", ["single", "mesh1", "mesh4_capacity_1"])
def test_no_compaction_where_the_receive_side_is_small(kind):
    text = _scan_text(kind)
    assert "sparse.apply" in text  # the text does carry stage names
    assert "exchange.compact" not in text
    assert "exchange.full_size" not in text
    assert not _owner_conditionals(text)
    # and it is the program with the mechanism nulled, instruction for
    # instruction (`Trainer` never enters parallel/sharded.py)
    assert strip(text) == strip(_scan_text(kind, full_size=True))


def test_exact_mode_on_four_devices_compacts():
    text = _scan_text("mesh4_exact")
    assert "exchange.compact" in text and "exchange.full_size" in text
    # serve and apply: one conditional each, the full-size branch under its
    # own stage name
    assert len(_owner_conditionals(text)) == 2
    assert "exchange.compact" not in _scan_text("mesh4_exact", full_size=True)


# -- (e) the collectives of a step are the same -------------------------------


def _collectives(text):
    ops = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
           "collective-permute")
    return {op: len(re.findall(r"= [^=]*\b%s(?:-start)?\(" % op, text))
            for op in ops}


def test_collectives_per_step_unchanged():
    on = _collectives(_scan_text("mesh4_exact"))
    off = _collectives(_scan_text("mesh4_exact", full_size=True))
    assert on == off
    assert on["all-to-all"] == 3  # ids, rows, grads: one dim-group


# -- (f) owner_fill is the count taken on the host ----------------------------


@pytest.mark.parametrize("S", [4, 8])
def test_owner_fill_equals_the_host_count(S):
    stacked = _batches(S, seed=3)
    ids = stacked["sparse"]["categorical"]
    tr, _, m = _train(S, stacked)
    fills = []
    for k in range(K):
        per_owner = np.zeros(S, np.int64)
        for d in range(S):
            u = np.unique(ids[k, d * PER_CHIP:(d + 1) * PER_CHIP])
            per_owner += np.bincount(u % S, minlength=S)
        fills.append(per_owner.max() / N)
    np.testing.assert_allclose(float(m["owner_fill"]["categorical"]),
                               max(fills), rtol=1e-6)
    # nobody folded the window: the entry point did, at the latest when the
    # registry is read
    rep = metrics.report()
    assert rep['exchange.owner_fill{table="categorical"}'] == \
        pytest.approx(max(fills), rel=1e-6)
    assert rep['exchange.owner_full_steps{table="categorical"}'] == 0
    assert rep['trainer.windows{fn="train_many"}'] == 1
    # the step loop serves the last step's reading
    _, _, ms = _train(S, stacked, many=False)
    vec = np.asarray(ms["stats"]["categorical/owner_fill"])
    assert vec.shape == (S,)
    np.testing.assert_allclose(vec.max(), fills[-1], rtol=1e-6)
    assert metrics.report()['exchange.owner_fill{table="categorical"}'] == \
        pytest.approx(fills[-1], rel=1e-6)
