"""Software-pipelined train_many (round 18): the dependency-graph overlap
must be FREE in fp32 — bit-exact losses, weights, and optimizer slots vs the
serial scan on every exchange path — and structurally real: batch t+1's
id-plane collectives carry no data dependency on batch t's apply (the jaxpr
pin), the conflict patch repairs deliberately overlapping batches, and the
whole program survives a placement-controller cycle without re-tracing or
changing its collective sequence.

The host-offload stage ring (`offload_stage_depth > 1`) rides along: staging
D batches ahead must stay bit-identical to the synchronous path, with the
per-slot occupancy gauges published.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flax.linen as nn

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.initializers import Constant
from openembedding_tpu.model import EmbeddingModel, Trainer
from openembedding_tpu.models import make_deepfm, make_lr
from openembedding_tpu.parallel import MeshTrainer, make_mesh
from openembedding_tpu.utils import metrics
from openembedding_tpu.utils.guards import (assert_no_recompile,
                                            collective_fingerprint)

VOCAB = 1 << 10
K = 3


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics._REGISTRY.clear()
    yield
    metrics._REGISTRY.clear()


def _stack(batches):
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)


def _run_pair(hot=0, mig=0, k=K, seed=5, overlap=False, wire="fp32"):
    """Train the same window serial and pipelined; return both (state,
    metrics) pairs. `overlap` plants heavy id overlap between consecutive
    batches so the speculative prefetch is guaranteed stale (the conflict
    patch must repair it). `wire` selects the exchange codec — narrow wires
    exercise the round-23 error-feedback replay in the patch."""
    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=k, seed=seed))
    if overlap:
        for b in batches[1:]:
            for f in b["sparse"]:
                b["sparse"][f][:8] = batches[0]["sparse"][f][:8]
    stacked = _stack(batches)
    hot_ids = {"categorical": np.arange(4, dtype=np.int64)} if hot else None

    outs = []
    for pipe in (False, True):
        tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                         hot_rows=hot, mig_rows=mig, wire=wire,
                         pipeline_steps=pipe)
        state = tr.init(batches[0])
        if hot:
            state = tr.refresh_hot_rows(state, hot_ids=hot_ids)
        if mig:
            moves = {"categorical": (np.array([8, 16, 24], np.int64),
                                     np.array([3, 5, 7], np.int32))}
            state = tr.migrate_rows(state, moves=moves)
        state, m = tr.jit_train_many(stacked, state)(state, stacked)
        outs.append((tr, state, m))
    return outs


def _assert_bit_exact(sa, ma, sb, mb):
    np.testing.assert_array_equal(np.asarray(ma["loss"]),
                                  np.asarray(mb["loss"]))
    for n in sa.tables:
        np.testing.assert_array_equal(np.asarray(sa.tables[n].weights),
                                      np.asarray(sb.tables[n].weights))
        for s in sa.tables[n].slots:
            np.testing.assert_array_equal(np.asarray(sa.tables[n].slots[s]),
                                          np.asarray(sb.tables[n].slots[s]))
        if sa.tables[n].hot is not None:
            np.testing.assert_array_equal(
                np.asarray(sa.tables[n].hot.weights),
                np.asarray(sb.tables[n].hot.weights))
        if sa.tables[n].mig is not None:
            np.testing.assert_array_equal(
                np.asarray(sa.tables[n].mig.weights),
                np.asarray(sb.tables[n].mig.weights))
        # narrow wires: the per-row error-feedback residuals must match
        # too — the patch's EF replay rewrites them, not just the weights
        if sa.tables[n].ef is not None:
            np.testing.assert_array_equal(np.asarray(sa.tables[n].ef),
                                          np.asarray(sb.tables[n].ef))


# ---------------------------------------------------------------------------
# bit-exactness: every exchange path, pipelined == serial
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", ["fused", "hot", "mig"])
def test_pipelined_bit_exact(case):
    kw = {"fused": {}, "hot": {"hot": 8}, "mig": {"mig": 8}}[case]
    (_, sa, ma), (_, sb, mb) = _run_pair(**kw)
    _assert_bit_exact(sa, ma, sb, mb)


def test_pipelined_k1_skips_the_scan():
    """A one-batch window has nothing to overlap — the pipelined path must
    degenerate to the serial result with zero conflict repairs."""
    (_, sa, ma), (_, sb, mb) = _run_pair(k=1)
    _assert_bit_exact(sa, ma, sb, mb)
    assert sum(int(np.asarray(v)) for v in mb["conflict"].values()) == 0


def test_conflict_patch_repairs_overlapping_batches():
    """Consecutive batches share ids, so batch t+1's speculative gather is
    stale the moment batch t applies — the patch must both FIRE (nonzero
    repaired rows, published to the gauge) and restore bit-exactness."""
    (_, sa, ma), (tr, sb, mb) = _run_pair(hot=8, mig=8, overlap=True)
    _assert_bit_exact(sa, ma, sb, mb)
    patched = sum(int(np.asarray(v)) for v in mb["conflict"].values())
    assert patched > 0
    assert int(np.asarray(mb["conflict_overflow"])) == 0
    tr.record_window_stats(mb)
    rep = metrics.report()
    assert rep['exchange.conflict_rows{table="categorical"}'] > 0


@pytest.mark.parametrize("case", ["disjoint", "overlap", "overlap_hot_mig"])
def test_pipelined_bit_exact_int8_wire(case):
    """Round 23's EF replay pin. With the int8 exchange wire every served
    row ships q(w + ef) and rewrites the residual — so a speculatively
    prefetched row is stale in BOTH planes. The conflict patch must replay
    the quantizer against the post-apply weights plus the PRE-serve
    residual stash (`ExchangePlan.ef_stash`), restoring bit-exactness of
    losses, weights, optimizer slots AND the `state.ef` residuals vs the
    serial int8 scan. Overlapping batches force the patch to fire; the
    hot-cache and migration annexes ride the same window."""
    kw = {"disjoint": {}, "overlap": {"overlap": True},
          "overlap_hot_mig": {"overlap": True, "hot": 8, "mig": 8}}[case]
    (_, sa, ma), (_, sb, mb) = _run_pair(wire="int8", **kw)
    _assert_bit_exact(sa, ma, sb, mb)
    for n in sa.tables:
        assert sa.tables[n].ef is not None  # the pin is not vacuous
    patched = sum(int(np.asarray(v)) for v in mb["conflict"].values())
    if case != "disjoint":
        assert patched > 0


# ---------------------------------------------------------------------------
# the jaxpr pin: prefetch is data-independent of the apply
# ---------------------------------------------------------------------------


def _find_scan(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            return eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    found = _find_scan(inner)
                    if found is not None:
                        return found
                elif hasattr(sub, "eqns"):
                    found = _find_scan(sub)
                    if found is not None:
                        return found
    return None


def test_prefetch_has_no_data_dependency_on_apply():
    """THE overlap pin. In the pipelined scan body, batch t+1's exchange
    collectives must be schedulable under batch t's compute — i.e. carry no
    data dependency on anything downstream of batch t's loss. Taint batch
    t's label (every gradient, apply, push, and patch transitively depends
    on it; the id/weight prefetch plane must not) and walk the body jaxpr:
    the first all_to_all is the prefetch and must be clean, while the
    patch/push all_to_alls must be tainted (proving the taint walk itself
    reaches the collectives)."""
    model = make_deepfm(vocabulary=VOCAB, dim=4, hidden=(8,))
    batches = list(synthetic_criteo(16, id_space=VOCAB, steps=K, seed=7))
    stacked = _stack(batches)
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), seed=1,
                     wire="fp32", pipeline_steps=True)
    state = tr.init(batches[0])
    many = tr.jit_train_many(stacked, state)

    closed = jax.make_jaxpr(many)(state, stacked)
    scan = _find_scan(closed.jaxpr)
    assert scan is not None, "pipelined train_many lost its scan"
    body = scan.params["jaxpr"].jaxpr
    nc = scan.params["num_consts"]
    nk = scan.params["num_carry"]

    # the scan xs are (head, nxt) slices of the stacked batches — locate
    # batch t's (head's) label leaf to seed the taint
    paths, _ = jax.tree_util.tree_flatten_with_path((stacked, stacked))
    taint_idx = [i for i, (path, _leaf) in enumerate(paths)
                 if path[0] == jax.tree_util.SequenceKey(0)
                 and any(getattr(k, "key", None) == "label" for k in path)]
    assert len(taint_idx) == 1
    x_invars = body.invars[nc + nk:]
    assert len(x_invars) == len(paths)

    tainted = {id(x_invars[taint_idx[0]])}
    for eqn in body.eqns:
        if any(id(v) in tainted for v in eqn.invars):
            tainted.update(id(v) for v in eqn.outvars)

    a2a = [e for e in body.eqns if e.primitive.name == "all_to_all"]
    assert a2a, "no top-level all_to_all in the scan body"
    clean = [e for e in a2a
             if not any(id(v) in tainted for v in e.invars)]
    dirty = [e for e in a2a if e not in clean]
    # the body opens with the prefetch — independent of batch t's loss
    assert a2a[0] in clean
    # id plane + speculative weight return both precede any tainted a2a
    first_dirty = body.eqns.index(dirty[0]) if dirty else len(body.eqns)
    lead = [e for e in clean if body.eqns.index(e) < first_dirty]
    assert len(lead) >= 2, [e.primitive.name for e in a2a]
    # ...and the push/patch plane IS downstream of the loss (the taint
    # walk genuinely reaches collectives; the pin is not vacuous)
    assert dirty, "expected the conflict-patch gather to depend on the apply"


# ---------------------------------------------------------------------------
# placement-controller cycle with pipelining on: no retrace, stable program
# ---------------------------------------------------------------------------

S = 8
POOL = 24
HOT_SHARE = 0.6


class _Tower(nn.Module):
    @nn.compact
    def __call__(self, embedded, dense):
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return jnp.sum(embedded["a"].astype(jnp.float32), axis=(1, 2)) \
            + bias[0]


def _drift_batches(steps_per_phase, vocab, batch, seed=5):
    """Two-phase drifting-Zipf stream (see tests/test_placement.py): a heavy
    pool homed on shard 5 rotates to shard 3 at half time; the tail cycles
    deterministically so residual imbalance is placement error, not noise."""
    rng = np.random.default_rng(seed)
    pool_a = (np.arange(POOL) * S + 5).astype(np.int64)
    pool_b = (np.arange(POOL) * S + 3).astype(np.int64)
    w = 1.0 / (np.arange(POOL) + 1.0)
    w /= w.sum()
    tail = np.arange(vocab, dtype=np.int64)
    t_off, batches = 0, []
    for i in range(2 * steps_per_phase):
        pool = pool_a if i < steps_per_phase else pool_b
        ids = np.empty((batch, 26), np.int64)
        flat = ids.reshape(-1)
        n = flat.size
        flat[:] = tail[(t_off + np.arange(n)) % vocab]
        t_off += n
        mask = rng.random(n) < HOT_SHARE
        flat[mask] = pool[rng.choice(POOL, size=int(mask.sum()), p=w)]
        batches.append({
            "sparse": {"a": ids.astype(np.int32)},
            "label": rng.integers(0, 2, (batch,)).astype(np.float32)})
    return batches


def test_controller_cycle_keeps_pipelined_program_stable():
    """Prime a controller, let it refresh the hot cache and migrate rows
    across a drift, with the PIPELINED window fn alive the whole time: zero
    re-traces of either fn and an unchanged collective fingerprint — the
    overlap machinery must be as content-swap-invariant as the serial path.
    The controller's per-table adaptive annex sizing (policy.size_mig)
    rides the same cycle: prime installs a dict and publishes the gauge."""
    from openembedding_tpu.placement import (PlacementController,
                                             PlacementPolicy)
    from openembedding_tpu.placement.policy import row_bytes
    from openembedding_tpu.utils.sketch import SkewMonitor

    steps_per_phase = 12
    vocab, batch, dim = 1 << 12, 64, 8
    batches = _drift_batches(steps_per_phase, vocab, batch)
    model = EmbeddingModel(_Tower(), [embed.Embedding(vocab, dim, name="a")])
    mon = SkewMonitor(k=64, sync=True, decay=0.85)
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), wire="fp32", pipeline_steps=True)
    policy = PlacementPolicy(8 * row_bytes(dim, 1), mig_rows=32,
                             refresh_cooldown_steps=3, imbalance_target=1.05)
    ctrl = PlacementController(tr, policy, monitor=mon, interval_steps=3)

    for b in batches[:3]:
        mon.observe("a", b["sparse"]["a"])
    state = tr.init(batches[0])
    state = ctrl.prime(state)
    # satellite pin: prime sized the annex per table and published it
    assert isinstance(tr.mig_rows, dict) and "a" in tr.mig_rows
    assert tr.mig_rows["a"] >= 1
    assert 'placement.mig_rows{table="a"}' in metrics.report()

    window = _stack(batches[:2])
    step = assert_no_recompile(tr.jit_train_step(batches[0], state),
                               label="pipelined_step")
    many = assert_no_recompile(tr.jit_train_many(window, state),
                               label="pipelined_many")
    fp = collective_fingerprint(many, state, window)
    state, _ = many(state, window)  # execute once before the cycle

    for i, b in enumerate(batches):
        mon.observe("a", b["sparse"]["a"])
        state, m = step(state, b)
        metrics.record_step_stats(m["stats"])
        state = ctrl.on_step(state, step=i + 1)
    st = ctrl.status()
    assert st["migrations_applied"] >= 1
    assert st["last_refresh_step"]["a"] > 0

    # the controller refreshed + migrated; the pipelined window must still
    # be the SAME compiled program with the SAME collective sequence
    state, _ = many(state, window)
    assert many.trace_count() == 1
    assert step.trace_count() == 1
    assert collective_fingerprint(many, state, window) == fp


# ---------------------------------------------------------------------------
# host-offload stage ring: depth > 1 staging stays bit-identical
# ---------------------------------------------------------------------------

DIM = 4
CACHE = 4096
ID_SPACE = 1 << 40


def _offload_batches(steps=10, batch=16, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        ids = rng.integers(0, ID_SPACE, size=(batch, 2)).astype(np.int64)
        labels = (rng.random(batch) < 0.5).astype(np.float32)
        out.append({"sparse": {"categorical": ids}, "label": labels})
    return out


def _offload_model():
    e = embed.Embedding(-1, DIM, name="categorical", capacity=CACHE,
                        storage="host_cached",
                        embeddings_initializer=Constant(0.0))
    lr = make_lr(vocabulary=-1, hashed=True, capacity=CACHE)
    return EmbeddingModel(lr.module, [e], loss_fn=lr.loss_fn,
                          config=lr.config)


def _offload_run(depth, pipeline=True, stage_ahead=None):
    stage_ahead = depth if stage_ahead is None else stage_ahead
    batches = _offload_batches()
    tr = Trainer(_offload_model(), embed.Adagrad(learning_rate=0.3),
                 offload_pipeline=pipeline, offload_stage_depth=depth)
    state = tr.init(batches[0])
    step = tr.jit_train_step()
    losses = []
    if pipeline:
        for d in range(min(stage_ahead, len(batches))):
            tr.offload_stage(batches[d])
    for i, b in enumerate(batches):
        state = tr.offload_prepare(state, b)
        j = i + stage_ahead
        if pipeline and j < len(batches):
            tr.offload_stage(batches[j])
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses, tr.offload["categorical"]


def test_stage_ring_bit_identical_across_depths():
    """Staging 1, 2, or 3 batches ahead (and under-filling a deep ring)
    must train bit-identically to the synchronous path — a stale staged
    payload falls back, never corrupts."""
    base, _ = _offload_run(1, pipeline=False)
    for depth, ahead in ((1, None), (2, None), (3, None), (2, 1)):
        losses, _ = _offload_run(depth, stage_ahead=ahead)
        np.testing.assert_array_equal(base, losses)


def test_stage_ring_deep_hits_and_occupancy_gauges():
    """With a roomy cache (no eviction churn) a depth-2 ring should serve
    staged payloads, not fall back — and publish per-slot occupancy."""
    _, ot = _offload_run(2)
    assert ot._pipe_hits > 0
    assert set(ot._slot_hits) | set(ot._slot_misses) <= {0, 1}
    rep = metrics.report()
    assert "offload.pipeline_occupancy" in rep
    slot_keys = [k for k in rep
                 if k.startswith('offload.pipeline_occupancy{slot=')]
    assert slot_keys, sorted(rep)


def test_stage_ring_rejects_bad_depth():
    tr = Trainer(_offload_model(), embed.Adagrad(learning_rate=0.3),
                 offload_pipeline=True, offload_stage_depth=0)
    with pytest.raises(ValueError, match="stage_depth"):
        tr.init(_offload_batches(steps=1)[0])


# ---------------------------------------------------------------------------
# per-table adaptive annex sizing (policy.size_mig) unit pins
# ---------------------------------------------------------------------------


def test_size_mig_adapts_to_measured_imbalance():
    from openembedding_tpu.placement.policy import (PlacementPolicy,
                                                    TableTelemetry)
    pol = PlacementPolicy(1 << 20, mig_rows=64, imbalance_target=1.05)
    cov = [(8, 0.5)]
    load = np.array([100.0] * 7 + [200.0])   # shard 7 runs hot
    hot_homed = [(7 + 8 * k, 100) for k in range(20)]  # ids with id%8==7

    tels = [
        # no measured load vector yet -> static default
        TableTelemetry("cold", 4, cov, total=9000.0, top_ids=hot_homed),
        # balanced -> floor
        TableTelemetry("flat", 4, cov, total=9000.0, top_ids=hot_homed,
                       shard_positions=np.full(8, 100.0)),
        # skewed, sketch covers the excess -> sized between the clamps
        TableTelemetry("skew", 4, cov, total=9000.0, top_ids=hot_homed,
                       shard_positions=load),
        # skewed but tracked mass can't cover the excess -> cap
        TableTelemetry("deep", 4, cov, total=9000.0, top_ids=[(7, 10)],
                       shard_positions=load),
    ]
    sized = pol.size_mig(tels)
    assert sized["cold"] == 64
    assert sized["flat"] == 16           # mig_rows // 4
    # excess = 200 - 1.05*112.5 = 81.875; each hot-homed id covers
    # 100/9000*900 = 10 -> 9 ids needed -> M = 2*9 = 18
    assert sized["skew"] == 18
    assert sized["deep"] == 256          # 4 * mig_rows
    # off-shard heavy hitters must not count toward coverage
    mixed = TableTelemetry(
        "mixed", 4, cov, total=9000.0,
        top_ids=[(6, 10**6), (5, 10**6)] + hot_homed,  # id%8 != 7: ignored
        shard_positions=load)
    assert pol.size_mig([mixed])["mixed"] == 18


# ---------------------------------------------------------------------------
# round 23: dense-wire policy hysteresis (no thrash under noisy density)
# ---------------------------------------------------------------------------


def test_dense_wire_policy_hysteresis_no_thrash():
    """A density that oscillates inside the hysteresis band [enter, exit)
    must flip the wire exactly once: enter sparse when d <= enter
    (0.6 x crossover), stay sparse until d >= exit (0.9 x crossover) —
    each flip is a counted re-jit, so thrash here is a compile storm."""
    from openembedding_tpu.placement.policy import PlacementPolicy

    pol = PlacementPolicy(1 << 20, mig_rows=64)
    chunk = 1024
    enter = pol.dense_sparse_enter * pol.dense_wire_crossover
    exit_ = pol.dense_sparse_exit * pol.dense_wire_crossover
    assert enter < exit_ < pol.dense_wire_crossover

    mode, flips = "int8", 0
    # every sample sits strictly between enter and exit except the first,
    # which trips the entry — the band must absorb all the oscillation
    stream = [0.10] + [enter + 0.01, exit_ - 0.01, enter + 0.005,
                       exit_ - 0.002] * 4
    for d in stream:
        new, k, _reason = pol.recommend_dense_wire(d, current=mode,
                                                   chunk=chunk,
                                                   steps_since=10**9)
        if new != mode:
            flips += 1
        mode = new
        if mode == "sparse_topk":
            assert 1 <= k <= chunk and k % pol.dense_topk_block == 0
    assert flips == 1 and mode == "sparse_topk"

    # leaving the band upward flips back out...
    new, k, _ = pol.recommend_dense_wire(exit_ + 0.01, current=mode,
                                         chunk=chunk, steps_since=10**9)
    assert new == "int8" and k is None
    # ...but never inside the cooldown window
    new, _k, reason = pol.recommend_dense_wire(
        0.01, current="int8", chunk=chunk,
        steps_since=pol.dense_wire_cooldown_steps - 1)
    assert new == "int8" and "cooldown" in reason
    # unusable densities never recommend a change
    for bad in (float("nan"), -1.0):
        new, k, _ = pol.recommend_dense_wire(bad, current="int8",
                                             chunk=chunk, steps_since=10**9)
        assert new == "int8" and k is None
