"""ZeRO dense-state sharding (round 14): `MeshTrainer(dense_shard=True)`
replaces the dense-grad psum + replicated optimizer apply with
reduce_scatter -> 1/S local opt-state shard update -> all_gather
(`parallel/zero.py`, arXiv:2004.13336).

Acceptance (ISSUE 10):
- fp32 training is BIT-exact vs the replicated baseline: losses, dense
  params and (externalized) optimizer slots after N steps, per optimizer;
- on-disk artifacts — sharded checkpoint, standalone export, incremental
  sync deltas — are byte-identical to a ZeRO-off control run (the
  `externalize` hook unshards before every writer);
- checkpoints are cross-compatible: a ZeRO-off dump loads into a ZeRO-on
  trainer (and vice versa) and training continues bit-exact;
- the flat layout round-trips bitwise and the scalar-slot invariant is
  enforced at conversion time.
"""

import functools
import os

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.model import EmbeddingModel
from openembedding_tpu.parallel import MeshTrainer, make_mesh
from openembedding_tpu.parallel import zero
from openembedding_tpu.utils import metrics

S = 8  # conftest forces 8 virtual CPU devices
B = 64
VOCAB = 256


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics._REGISTRY.clear()
    yield
    metrics._REGISTRY.clear()


class _Tower(nn.Module):
    """Vector + matrix + scalar dense params: exercises multi-leaf flatten
    offsets, and Adam's scalar beta-power slots ride the scalar path."""

    @nn.compact
    def __call__(self, embedded, dense):
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        w = self.param("w", nn.initializers.normal(0.02), (8, 4), jnp.float32)
        out = jnp.sum(embedded["a"].astype(jnp.float32) @ w, axis=(1, 2))
        out = out + jnp.sum(embedded["b"].astype(jnp.float32), axis=(1, 2))
        return out + bias[0]


def _model():
    return EmbeddingModel(_Tower(), [
        embed.Embedding(VOCAB, 8, name="a"),
        embed.Embedding(-1, 8, name="b", capacity=4096),
    ])


def _batches(n, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        a = rng.integers(0, VOCAB, (B, 4)).astype(np.int32)
        b = rng.integers(0, 1 << 40, (B, 3)).astype(np.int64)
        out.append({"sparse": {"a": a, "b": b},
                    "label": rng.integers(0, 2, (B,)).astype(np.float32)})
    return out


def _trees_bitwise_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# fp32 bit-parity: sharded update == replicated update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_opt", [
    lambda: embed.Adagrad(learning_rate=0.1),
    lambda: embed.Adam(learning_rate=0.01),
], ids=["adagrad", "adam"])
def test_zero_fp32_bit_parity(make_opt):
    """THE acceptance pin: 4 steps with dense_shard on vs off — losses,
    dense params, and externalized optimizer slots all bitwise equal
    (psum_scatter is bit-identical to psum-then-slice on a fixed mesh,
    and the per-chunk optimizer math is elementwise)."""
    def run(dense_shard):
        batches = _batches(4)
        tr = MeshTrainer(_model(), make_opt(), mesh=make_mesh(),
                         wire="fp32", dense_shard=dense_shard)
        state = tr.init(batches[0])
        if dense_shard:
            assert zero.is_sharded_slots(state.dense_slots)
        step = tr.jit_train_step(batches[0], state)
        losses = []
        for b in batches:
            state, m = step(state, b)
            losses.append(np.asarray(m["loss"]).tobytes())
        return tr.externalize(state), losses

    s0, l0 = run(False)
    s1, l1 = run(True)
    assert l0 == l1
    _trees_bitwise_equal(s0.dense_params, s1.dense_params)
    _trees_bitwise_equal(s0.dense_slots, s1.dense_slots)


def test_zero_shard_unshard_round_trip():
    """dense_to_sharded -> dense_to_replicated is byte-identical, and the
    sharded form is the flat `{__zero__: ...}` layout with per-shard chunks."""
    batches = _batches(1)
    tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), dense_shard=True)
    state = tr.init(batches[0])
    assert zero.is_sharded_slots(state.dense_slots)
    plan = tr._zero_plan
    assert plan.num_shards == S
    assert plan.padded == plan.chunk * S >= plan.total
    flat = state.dense_slots[zero.ZERO_KEY]
    for k, v in flat.items():
        assert v.shape == ((1, 1) if k in plan.scalar_slots
                           else (1, plan.padded))
    rep = tr.dense_to_replicated(state)
    assert not zero.is_sharded_slots(rep.dense_slots)
    back = tr.dense_to_sharded(rep)
    _trees_bitwise_equal(state.dense_slots, back.dense_slots)
    # gauges from the sharded update path are registered under dense.*
    step = tr.jit_train_step(batches[0], state)
    state, _ = step(state, batches[0])
    rep_m = metrics.report()
    assert rep_m["dense.zero_shards"] == S
    assert rep_m["dense.opt_state_bytes_per_replica"] > 0


def test_zero_single_shard_is_noop():
    """dense_shard on a 1-device mesh stays in the replicated layout (no
    collective exists to win anything; zero_enabled gates on S > 1)."""
    batches = _batches(1)
    tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(jax.devices()[:1]), dense_shard=True)
    assert not tr.zero_enabled
    state = tr.init(batches[0])
    assert not zero.is_sharded_slots(state.dense_slots)
    step = tr.jit_train_step(batches[0], state)
    state, m = step(state, batches[0])
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# Persistence obliviousness: checkpoint / export / deltas byte-identical
# ---------------------------------------------------------------------------


def _run_training(tmp_path, tag, *, dense_shard, dense_wire=None):
    from openembedding_tpu.export import export_standalone
    from openembedding_tpu.persist import IncrementalPersister, PersistPolicy
    batches = _batches(6, seed=7)
    tr = MeshTrainer(_model(), embed.Adam(learning_rate=0.01),
                     mesh=make_mesh(), wire="fp32", dense_shard=dense_shard,
                     dense_wire=dense_wire)
    state = tr.init(batches[0])
    step = tr.jit_train_step(batches[0], state)
    root = tmp_path / tag
    losses = []
    with IncrementalPersister(tr, tr.model, str(root / "persist"), window=1,
                              policy=PersistPolicy(every_steps=2),
                              full_every=100) as p:
        for b in batches:
            state, m = step(state, b)
            losses.append(float(m["loss"]))
            p.maybe_persist(state, batch=b)
        p.wait()
    tr.save(state, str(root / "ckpt"), model_sign="t")
    export_standalone(tr.externalize(state), tr.model, str(root / "export"),
                      model_sign="t-0")
    return losses


@pytest.fixture(scope="module")
def control_run(tmp_path_factory):
    """-> (root, losses) of the replicated fp32 run every artifact test
    compares against: the same six steps and the same three writers each
    time, so it is made once a process."""
    root = tmp_path_factory.mktemp("zero_control")
    return root / "c", _run_training(root, "c", dense_shard=False)


def _assert_trees_equal(off_root, on_root, skip=("model_meta",)):
    found = 0
    for root, _dirs, files in os.walk(off_root):
        for fn in files:
            if fn in skip:
                continue
            p_off = os.path.join(root, fn)
            p_on = p_off.replace(str(off_root), str(on_root))
            with open(p_off, "rb") as fa, open(p_on, "rb") as fb:
                assert fa.read() == fb.read(), f"differs: {p_off}"
            found += 1
    assert found > 0


def test_zero_checkpoint_export_delta_byte_identical(tmp_path, control_run):
    """A dense_shard run's on-disk artifacts — sharded checkpoint,
    standalone export, incremental sync deltas — equal a ZeRO-off control
    run's byte for byte (every writer goes through `externalize`)."""
    off, l_off = control_run
    l_on = _run_training(tmp_path, "on", dense_shard=True)
    assert l_off == l_on
    _assert_trees_equal(off / "ckpt", tmp_path / "on" / "ckpt")
    _assert_trees_equal(off / "export", tmp_path / "on" / "export",
                        skip=("model_meta", "model_meta.json"))
    import glob
    offs = sorted(glob.glob(str(off / "persist" / "**" / "table_*.npz"),
                            recursive=True))
    assert offs
    for p_off in offs:
        p_on = p_off.replace(str(off), str(tmp_path / "on"))
        a, b = np.load(p_off), np.load(p_on)
        assert sorted(a.files) == sorted(b.files), p_off
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"{p_off}:{k}")


def test_zero_checkpoint_cross_compatible(tmp_path):
    """A ZeRO-off dump loads into a ZeRO-on trainer (and vice versa), and
    continued training stays bit-exact — the serialized form is ONE layout
    (replicated), conversion happens at the load/save boundary."""
    batches = _batches(5, seed=11)
    # one trainer, so ONE compiled step, a `dense_shard` value: what a combo
    # is about is the state that crosses the dump. The state that trains is
    # made anew by each `init`; the one `load` fills is a template (`load`
    # reads its shapes and shardings and returns arrays of its own), made
    # once a destination
    trainers = {shard: MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                                   mesh=make_mesh(), dense_shard=shard)
                for shard in (False, True)}
    templates = {shard: tr.init(batches[0]) for shard, tr in trainers.items()}

    def run(save_shard, load_shard):
        tr = trainers[save_shard]
        state = tr.init(batches[0])
        step = tr.jit_train_step(batches[0], state)
        for b in batches[:2]:
            state, _ = step(state, b)
        path = str(tmp_path / f"ckpt_{save_shard}_{load_shard}")
        tr.save(state, path, model_sign="x")
        tr2 = trainers[load_shard]
        st2 = tr2.load(templates[load_shard], path)
        if load_shard:
            assert zero.is_sharded_slots(st2.dense_slots)
        step2 = tr2.jit_train_step(batches[0], st2)
        losses = []
        for b in batches[2:]:
            st2, m = step2(st2, b)
            losses.append(np.asarray(m["loss"]).tobytes())
        return tr2.externalize(st2), losses

    s_base, l_base = run(False, False)
    for combo in ((False, True), (True, False), (True, True)):
        s, l = run(*combo)
        assert l == l_base, combo
        _trees_bitwise_equal(s_base.dense_params, s.dense_params)
        _trees_bitwise_equal(s_base.dense_slots, s.dense_slots)


# ---------------------------------------------------------------------------
# parallel/zero.py units
# ---------------------------------------------------------------------------


def _toy_plan(num_shards=4):
    params = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              "b": jnp.asarray([7.0], jnp.float32)}
    opt = embed.Adam(learning_rate=0.01)
    return params, opt, zero.build_plan(params, opt, num_shards)


def test_zero_flatten_round_trip():
    params, _, plan = _toy_plan()
    flat = zero.flatten_tree(plan, params)
    assert flat.shape == (plan.padded,) and plan.total == 7
    back = zero.unflatten_tree(plan, flat, params)
    _trees_bitwise_equal(params, back)
    # padding lanes are zero (reduce_scatter must not see garbage)
    assert not np.asarray(flat[plan.total:]).any()


def test_zero_scalar_slot_guard():
    """Diverging scalar slots (e.g. Adam beta powers in a hand-edited
    state) must fail conversion loudly, not silently pick one leaf's."""
    params, opt, plan = _toy_plan()
    assert plan.scalar_slots  # Adam: beta powers

    def leaf_slots(p):
        return {name: (jnp.ones((1, 1), jnp.float32)
                       if name in plan.scalar_slots
                       else jnp.zeros((1, p.size), jnp.float32))
                for name in (*plan.vector_slots, *plan.scalar_slots)}

    slots = jax.tree_util.tree_map(leaf_slots, params)
    zero.check_scalar_slots_equal(plan, slots)  # equal: fine
    name = sorted(plan.scalar_slots)[0]
    slots["b"][name] = jnp.asarray([[2.0]], jnp.float32)
    with pytest.raises(ValueError, match="dense_shard"):
        zero.check_scalar_slots_equal(plan, slots)


def test_zero_rejects_wide_dtypes():
    params = {"a": jnp.zeros((3,), jnp.float64)}
    with pytest.raises(ValueError, match="f32|float64|4-byte"):
        zero.build_plan(params, embed.Adagrad(learning_rate=0.1), 4)


# ---------------------------------------------------------------------------
# round 17: quantized dense collectives (dense_wire)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _four_steps_of_zero(dense_wire):
    batches = _batches(4, seed=3)
    tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), wire="fp32", dense_shard=True,
                     dense_wire=dense_wire)
    state = tr.init(batches[0])
    step = tr.jit_train_step(batches[0], state)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return tr, state, losses


@pytest.mark.parametrize("fmt", ["bf16", "int8", "sparse_topk"])
def test_dense_wire_trains_close_to_fp32(fmt):
    """`dense_wire` swaps the fp32 psum_scatter for the in-band-encoded
    two-stage reduce (encode -> a2a partials -> per-replica fp32 sum) and
    ships the param all_gather on the bf16 carrier, one lossy step per
    gradient. The ZeRO plan aligns chunks to the codec block, int8 carries
    fp32 masters + per-chunk EF residuals as extra `__zero__` slots, and N
    steps stay within format tolerance of the lossless round-14 path."""
    from openembedding_tpu.ops import wire as wire_mod

    tr_f, st_f, l_f = _four_steps_of_zero(None)  # the same run in every case
    tr_q, st_q, l_q = _four_steps_of_zero(fmt)
    plan = tr_q._zero_plan
    assert plan.chunk % wire_mod.INBAND_BLOCK == 0
    flat = st_q.dense_slots[zero.ZERO_KEY]
    assert zero.DENSE_MASTER_KEY in flat
    # int8 and sparse_topk both need error feedback (quantization bias /
    # untransmitted mass); bf16 truncation rides without. On this toy model
    # chunk == 32 so the auto top-k resolves to k == chunk: the sparse path
    # exercises the full encode -> a2a -> scatter-sum pipeline while every
    # element still ships (int8-quantized), keeping the int8 loss tier.
    assert (zero.DENSE_EF_KEY in flat) == (fmt in ("int8", "sparse_topk"))
    assert np.all(np.isfinite(l_q))
    np.testing.assert_allclose(l_q, l_f, rtol=0.02, atol=0.02)
    # externalize folds the masters back and drops the wire-only slots:
    # same tree schema as the lossless run, params within tolerance
    ext_f = tr_f.externalize(st_f)
    ext_q = tr_q.externalize(st_q)
    assert (jax.tree_util.tree_structure(ext_q.dense_slots)
            == jax.tree_util.tree_structure(ext_f.dense_slots))
    assert (jax.tree_util.tree_structure(ext_q.dense_params)
            == jax.tree_util.tree_structure(ext_f.dense_params))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=0.05, atol=0.05),
        ext_q.dense_params, ext_f.dense_params)
    # gauges: the quantized path reports a2a bytes, not a reduce_scatter
    rep = metrics.report()
    assert rep["dense.a2a_bytes"] > 0
    assert rep["dense.reduce_scatter_bytes"] == 0
    assert rep["dense.wire_bytes_per_step"] > 0


def test_dense_wire_checkpoint_cross_compatible(tmp_path):
    """The serialized form stays ONE layout (replicated fp32 — masters
    folded into dense_params, EF wire residuals dropped/reseeded): a dump
    saved under any of {replicated, ZeRO, ZeRO-bf16, ZeRO-int8,
    ZeRO-sparse} loads into any other, the loaded external state is bitwise
    the saved one, and training continues finite."""
    batches = _batches(3, seed=13)
    configs = {
        "replicated": {},
        "zero": {"dense_shard": True},
        "zero_bf16": {"dense_shard": True, "dense_wire": "bf16"},
        "zero_int8": {"dense_shard": True, "dense_wire": "int8"},
        "zero_sparse": {"dense_shard": True, "dense_wire": "sparse_topk"},
    }

    # one trainer, so ONE compiled step, a configuration, as source and as
    # destination alike: every pair is about the state that crosses the dump,
    # and the program a destination runs depends on its configuration alone.
    # A source trains a state of its own `init`; what `load` fills is a
    # template (it reads shapes and shardings and returns arrays of its
    # own: the step after it donates them, never the template's), made once
    # a destination
    trainers = {cfg: MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                                 mesh=make_mesh(), wire="fp32", **kw)
                for cfg, kw in configs.items()}
    templates = {cfg: tr.init(batches[0]) for cfg, tr in trainers.items()}

    saved = {}
    for cfg in ("replicated", "zero_int8", "zero_sparse"):
        tr = trainers[cfg]
        state = tr.init(batches[0])
        step = tr.jit_train_step(batches[0], state)
        for b in batches[:2]:
            state, _ = step(state, b)
        path = str(tmp_path / cfg)
        tr.save(state, path, model_sign="x")
        saved[cfg] = (path, tr.externalize(state))

    for src, (path, ext_src) in saved.items():
        for dst in configs:
            tr2 = trainers[dst]
            st2 = tr2.load(templates[dst], path)
            if dst != "replicated":
                assert zero.is_sharded_slots(st2.dense_slots)
                flat = st2.dense_slots[zero.ZERO_KEY]
                assert ((zero.DENSE_MASTER_KEY in flat)
                        == bool(configs[dst].get("dense_wire")))
            ext2 = tr2.externalize(st2)
            _trees_bitwise_equal(ext_src.dense_params, ext2.dense_params)
            _trees_bitwise_equal(ext_src.dense_slots, ext2.dense_slots)
            step2 = tr2.jit_train_step(batches[0], st2)
            st2, m = step2(st2, batches[2])
            assert np.isfinite(float(m["loss"])), (src, dst)


@pytest.mark.parametrize("fmt", ["int8", "sparse_topk"])
def test_dense_wire_artifacts_schema_oblivious_and_reload(tmp_path, fmt,
                                                          control_run):
    """A narrow-wire run (int8 or sparse_topk) writes artifacts — sharded
    checkpoint, standalone export, incremental sync deltas — with EXACTLY
    the file set and array schema of a replicated fp32 control run (masters
    fold into dense_params; `__dense_ef__`/`__dense_master__` never leak to
    disk), and its checkpoint reloads into a fresh dense_wire trainer which
    keeps training."""
    l_q = _run_training(tmp_path, "q", dense_shard=True, dense_wire=fmt)
    assert np.all(np.isfinite(l_q))

    def listing(root):
        out = {}
        for r, _dirs, files in os.walk(root):
            for fn in files:
                p = os.path.join(r, fn)
                out[os.path.relpath(p, root)] = p
        return out

    q, c = listing(tmp_path / "q"), listing(control_run[0])
    assert sorted(q) == sorted(c)
    checked = 0
    for rel, p in q.items():
        if not rel.endswith(".npz"):
            continue
        a, b = np.load(p), np.load(c[rel])
        assert sorted(a.files) == sorted(b.files), rel
        for k in a.files:
            assert "__dense_ef__" not in k and "__dense_master__" not in k, k
            assert a[k].shape == b[k].shape and a[k].dtype == b[k].dtype, \
                (rel, k)
        checked += 1
    assert checked > 0

    tr = MeshTrainer(_model(), embed.Adam(learning_rate=0.01),
                     mesh=make_mesh(), wire="fp32", dense_shard=True,
                     dense_wire=fmt)
    batches = _batches(2, seed=7)
    st = tr.init(batches[0])
    st = tr.load(st, str(tmp_path / "q" / "ckpt"))
    flat = st.dense_slots[zero.ZERO_KEY]
    assert zero.DENSE_MASTER_KEY in flat and zero.DENSE_EF_KEY in flat
    step = tr.jit_train_step(batches[0], st)
    st, m = step(st, batches[1])
    assert np.isfinite(float(m["loss"]))


def test_dense_wire_validation():
    """Config errors fail at construction: dense_wire needs dense_shard,
    unknown formats are rejected, and "fp32"/"none" mean OFF."""
    with pytest.raises(ValueError, match="dense_shard"):
        MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                    mesh=make_mesh(), dense_wire="int8")
    with pytest.raises(ValueError, match="dense_wire"):
        MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                    mesh=make_mesh(), dense_shard=True, dense_wire="int4")
    tr = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), dense_shard=True, dense_wire="fp32")
    assert tr.dense_wire is None
    # dense_topk only sizes the sparse_topk payload, and must be positive
    with pytest.raises(ValueError, match="dense_topk"):
        MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                    mesh=make_mesh(), dense_shard=True, dense_wire="int8",
                    dense_topk=32)
    with pytest.raises(ValueError, match="dense_topk"):
        MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                    mesh=make_mesh(), dense_shard=True,
                    dense_wire="sparse_topk", dense_topk=0)
    # set_dense_wire re-validates (it raises before touching the state)
    tr2 = MeshTrainer(_model(), embed.Adagrad(learning_rate=0.1),
                      mesh=make_mesh(), dense_shard=True, dense_wire="int8")
    with pytest.raises(ValueError, match="dense_topk"):
        tr2.set_dense_wire(None, "int8", dense_topk=4)
    with pytest.raises(ValueError, match="dense_wire"):
        tr2.set_dense_wire(None, "int4")


# ---------------------------------------------------------------------------
# round 23: stream-sparse dense wire (sparse_topk) units
# ---------------------------------------------------------------------------


def test_sparse_topk_codec_round_trip():
    """pack_topk/unpack_topk: per row the k largest-|x| elements survive
    within int8 in-band quantization error, every untransmitted element
    decodes to EXACT 0.0 (the receiver scatter-sums partials, so stray
    nonzeros would corrupt other sources' contributions), and the index
    lanes are collision-free (<= k nonzeros per row). k=8/40 exercise
    partial codec blocks, k=96 the k == m degenerate case."""
    from openembedding_tpu.ops import wire as wire_mod

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 96)), jnp.float32)
    xn = np.asarray(x)
    for k in (8, 32, 40, 96):
        w = wire_mod.pack_topk(x, k)
        assert w.shape == (4, wire_mod.topk_wire_width(k))
        assert w.dtype == jnp.int8
        out = np.asarray(wire_mod.unpack_topk(w, k, x.shape[-1]))
        for r in range(x.shape[0]):
            idx = np.argsort(-np.abs(xn[r]))[:k]
            mask = np.zeros(x.shape[-1], bool)
            mask[idx] = True
            assert not out[r][~mask].any(), (k, r)
            assert (out[r] != 0).sum() <= k
            np.testing.assert_allclose(
                out[r][mask], xn[r][mask],
                atol=np.abs(xn).max() / 127 + 1e-7, err_msg=f"k={k} row={r}")


def test_sparse_topk_wire_width_partial_blocks():
    """topk_wire_width = int8 in-band rows (value lanes + scales, padded to
    whole codec blocks) + 4 bitcast-int32 index lanes per element; partial
    blocks price a whole block of value lanes, the index lanes are exact."""
    from openembedding_tpu.ops import wire as wire_mod

    for k in (1, 8, 32, 40, 96):
        want = wire_mod.rows_wire_width(k, "int8") + 4 * k
        assert wire_mod.topk_wire_width(k) == want, k
    assert wire_mod.topk_wire_width(32) == 164


def test_sparse_topk_error_feedback_converges():
    """Error feedback at fixed k < chunk: feeding the residual (true value
    minus decoded transmission, which also captures int8 quantization
    error) back into the next encode makes the TIME-AVERAGE of decoded
    transmissions converge to the true per-step gradient at ~1/T — the
    untransmitted mass is delayed, never lost (arXiv:1905.04035)."""
    S_, chunk, k = 4, 32, 8
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.standard_normal(S_ * chunk), jnp.float32)
    gn = np.asarray(g, np.float64)
    resid = jnp.zeros_like(g)
    sent = np.zeros(S_ * chunk, np.float64)
    errs = {}
    for t in range(1, 51):
        x = g + resid
        enc = zero.encode_flat_topk(x, S_, k)
        dec = zero.decode_flat_topk(enc, k, chunk).reshape(-1)
        resid = x - dec
        sent += np.asarray(dec, np.float64)
        if t in (5, 50):
            errs[t] = np.abs(sent / t - gn).max()
    # telescoping: sent/T - g == -resid_T/T exactly, so convergence only
    # needs the residual to stay bounded — pin both
    assert np.abs(np.asarray(resid)).max() < 2 * np.abs(gn).max()
    assert errs[50] < errs[5] / 4
    assert errs[50] < 0.1


def test_sparse_topk_dense_wire_cost():
    """dense_wire_cost prices sparse honestly: no reduce_scatter, a2a = S
    payloads of topk_wire_width(k) int8 lanes, params all_gather unchanged
    on the 2-byte carrier — and requires the resolved k."""
    from openembedding_tpu.ops import wire as wire_mod

    params = {"w": jnp.zeros((40,), jnp.float32)}
    plan = zero.build_plan(params, embed.Adagrad(learning_rate=0.1), S)
    cost = zero.dense_wire_cost(plan, "sparse_topk", topk=32)
    assert cost["format"] == "sparse_topk" and cost["k"] == 32
    assert cost["rs_bytes"] == 0
    assert cost["a2a_bytes"] == S * wire_mod.topk_wire_width(32)
    assert cost["ag_bytes"] == plan.padded * 2
    assert cost["bytes_per_step"] == cost["a2a_bytes"] + cost["ag_bytes"]
    with pytest.raises(ValueError, match="topk"):
        zero.dense_wire_cost(plan, "sparse_topk")


def test_sparse_topk_policy_k_halves_int8_grad_bytes():
    """In the sparse regime (density 0.01) the policy's k ships at most half
    the int8 dense path's gradient a2a bytes; at density 0.5 the sparse
    payload would cost more than int8 and the policy refuses it."""
    from openembedding_tpu.placement.policy import PlacementPolicy

    pol = PlacementPolicy(hot_budget_bytes=0)
    params = {"w": jnp.zeros((4096,), jnp.float32)}
    plan = zero.build_plan(params, embed.Adagrad(learning_rate=0.1), S)
    int8 = zero.dense_wire_cost(plan, "int8")["a2a_bytes"]

    mode, k, _ = pol.recommend_dense_wire(0.01, "int8", chunk=plan.chunk)
    assert mode == "sparse_topk"
    sparse = zero.dense_wire_cost(plan, "sparse_topk", topk=k)["a2a_bytes"]
    assert sparse <= 0.5 * int8
    mode, k, _ = pol.recommend_dense_wire(0.5, "int8", chunk=plan.chunk)
    assert mode == "int8" and k is None
    dense_k = pol._dense_topk(0.5, plan.chunk)
    assert zero.dense_wire_cost(plan, "sparse_topk",
                                topk=dense_k)["a2a_bytes"] > int8
